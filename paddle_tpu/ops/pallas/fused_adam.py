"""Fused Adam/AdamW update — one Pallas pass over (param, grad, m, v).

Reference role: operators/optimizers/adam_op.* and the fused-optimizer
tier (operators/fused/, multi_tensor_adam in later reference versions):
one kernel reads each tensor once and writes p', m', v' — no
intermediate m̂/v̂/update buffers.

On TPU, XLA already fuses the adam expression tree into a small number
of elementwise kernels, so the measured win is modest (see
``tools/op_bench.py --fused-adam`` for the number on the attached
chip); the kernel exists to close the fused-op tier and as the pattern
for update rules XLA fuses badly.

The update rule matches ``optimizer.Adam.update`` exactly (the
``lr_t = lr·√(1−β₂ᵗ)/(1−β₁ᵗ)`` formulation, adam_op.h):

    m' = β₁·m + (1−β₁)·g
    v' = β₂·v + (1−β₂)·g²
    p' = p − lr_t·m'/(√v' + ε) − wd_lr·p     (wd_lr = lr·coeff, AdamW)

Layout: the flat parameter is reshaped to (rows, 128) lanes and tiled
over rows; scalar hyperparameters ride as a (8, 1) block so a changing
learning rate never retraces.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.common import backend_is_tpu, no_x64

BLOCK_ROWS = 1024
_LANES = 128

# tests flip this to run in interpreter mode on CPU
_INTERPRET = False


def supported() -> bool:
    return backend_is_tpu() or _INTERPRET


def _adam_kernel(p_ref, g_ref, m_ref, v_ref, s_ref, po_ref, mo_ref, vo_ref):
    lr_t = s_ref[0, 0]
    beta1 = s_ref[1, 0]
    beta2 = s_ref[2, 0]
    eps = s_ref[3, 0]
    wd_lr = s_ref[4, 0]

    p = p_ref[...]
    g = g_ref[...].astype(jnp.float32)
    m = beta1 * m_ref[...] + (1.0 - beta1) * g
    v = beta2 * v_ref[...] + (1.0 - beta2) * (g * g)
    po_ref[...] = p - lr_t * m / (jnp.sqrt(v) + eps) - wd_lr * p
    mo_ref[...] = m
    vo_ref[...] = v


def fused_adam_update(p, g, m, v, *, lr_t, beta1, beta2, eps, wd_lr=0.0):
    """One fused Adam step on a single tensor.

    ``lr_t`` is the bias-corrected rate (lr·√(1−β₂ᵗ)/(1−β₁ᵗ)); ``wd_lr``
    is the decoupled AdamW decay (lr·coeff), 0 for plain Adam (whose L2
    decay arrives inside ``g`` via the regularizer pipeline).  All
    scalars may be traced — no retrace per step.

    Returns (p', m', v') with the input shapes/dtypes.
    """
    from jax.experimental import pallas as pl

    shape = p.shape
    n = p.size
    rows = -(-n // _LANES)

    block = min(BLOCK_ROWS, rows)
    rows_p = -(-rows // block) * block
    pad = rows_p * _LANES - n

    def flat(x):
        x = x.reshape(-1).astype(jnp.float32)
        if pad:
            x = jnp.pad(x, (0, pad))
        return x.reshape(rows_p, _LANES)

    scalars = jnp.stack([
        jnp.asarray(lr_t, jnp.float32),
        jnp.asarray(beta1, jnp.float32),
        jnp.asarray(beta2, jnp.float32),
        jnp.asarray(eps, jnp.float32),
        jnp.asarray(wd_lr, jnp.float32),
        jnp.zeros((), jnp.float32),
        jnp.zeros((), jnp.float32),
        jnp.zeros((), jnp.float32),
    ]).reshape(8, 1)

    row_spec = pl.BlockSpec((block, _LANES), lambda i: (i, 0))
    with no_x64():
        po, mo, vo = pl.pallas_call(
            _adam_kernel,
            grid=(rows_p // block,),
            in_specs=[row_spec, row_spec, row_spec, row_spec,
                      pl.BlockSpec((8, 1), lambda i: (0, 0))],
            out_specs=[row_spec, row_spec, row_spec],
            out_shape=[jax.ShapeDtypeStruct((rows_p, _LANES), jnp.float32)
                       ] * 3,
            name="fused_adam",
            interpret=_INTERPRET,
        )(flat(p), flat(g), flat(m), flat(v), scalars)

    def unflat(x, dtype):
        return x.reshape(-1)[:n].reshape(shape).astype(dtype)

    return (unflat(po, p.dtype), unflat(mo, m.dtype), unflat(vo, v.dtype))


def xla_reference(p, g, m, v, *, lr_t, beta1, beta2, eps, wd_lr=0.0):
    """Unfused reference (the optimizer.Adam.update expression tree)."""
    g = g.astype(jnp.float32)
    pf = p.astype(jnp.float32)
    m2 = beta1 * m + (1 - beta1) * g
    v2 = beta2 * v + (1 - beta2) * g * g
    new_p = pf - lr_t * m2 / (jnp.sqrt(v2) + eps) - wd_lr * pf
    return new_p.astype(p.dtype), m2, v2

"""The state of the KDA chunked delta rule carried across its chunks
(Pallas, TPU).

``nn/functional/kda.py`` computes, for every chunk ``n`` at once, the
``(d_k, d_k)`` matrix ``M_n`` and the ``(d_k, d_v)`` matrix ``B_n`` of
the state's update across the chunk; what is left is sequential:

    S_0 = 0;  entering_n = S_n;  S_{n+1} = M_n S_n + B_n

``carry(m, b)`` returns ``entering`` for ``m`` (batch, chunks, heads, d_k,
d_k) and ``b`` (batch, chunks, heads, d_k, d_v), in their dtype, which is
the state's.  The grid walks the chunks innermost and in order, a block
of heads at a time (``_heads_per_block``); the state stays in VMEM from
the first chunk to the last, so each chunk costs one read of ``M_n`` and
``B_n`` and one write of ``entering_n``, and no round trip of the state.

The gradient is a kernel of its own (``jax.custom_vjp``): from ``M`` and
``entering`` (the residuals) and ``E``, the cotangent of ``entering``, it
walks the chunks in reverse through its index maps and carries ``c``, the
cotangent of ``S_{n+1}``, in VMEM from zero (the last state is no output):

    dB_n = c;  dM_n = c entering_n^T;  c <- E_n + M_n^T c

Every product is at the highest precision, as in the rest of the rule.
Each kernel is traced once a process for each distinct shape
(``common.traced_once``): six layers call it in three passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.common import (backend_is_tpu, no_x64,
                                          traced_once)

__all__ = ["supported", "carry"]

_INTERPRET = False

_HIGHEST = jax.lax.Precision.HIGHEST
# a block of M or B (a block of heads of one chunk); the backward keeps
# five such blocks double-buffered beside the carried one
_BLOCK_BYTES = 1 << 20
_VMEM_LIMIT_BYTES = 32 << 20


def supported(dk: int, dv: int, dtype) -> bool:
    """Whether a state ``(d_k, d_v)`` of ``dtype`` is carried by these
    kernels: both widths lane multiples, a float dtype the MXU takes."""
    if not (backend_is_tpu() or _INTERPRET):
        return False
    return (dk % 128 == 0 and dv % 128 == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def _heads_per_block(heads: int, dk: int, dv: int, itemsize: int) -> int:
    """The most heads, a divisor of ``heads``, whose block of ``M`` and of
    ``B`` stays inside ``_BLOCK_BYTES``; one at least."""
    width = max(dk, dv) * dk * itemsize
    return max(d for d in range(1, heads + 1)
               if heads % d == 0 and (d == 1 or d * width <= _BLOCK_BYTES))


def _dot(a, b, contract):
    """``a`` and ``b`` contracted over the axes ``contract``, accumulated
    in float32: float32 operands at the highest precision, bf16 ones in
    the MXU's one pass, which is exact for them."""
    return jax.lax.dot_general(
        a, b, (contract, ((), ())),
        precision=_HIGHEST if a.dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32)


def _forward_kernel(m_ref, b_ref, o_ref, s_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    for j in range(s_ref.shape[0]):
        s = s_ref[j]
        o_ref[0, 0, j] = s
        s_ref[j] = (_dot(m_ref[0, 0, j], s, ((1,), (0,)))
                    + b_ref[0, 0, j]).astype(s.dtype)


def _backward_kernel(m_ref, s_ref, e_ref, dm_ref, db_ref, c_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        c_ref[...] = jnp.zeros_like(c_ref)

    for j in range(c_ref.shape[0]):
        c = c_ref[j]
        db_ref[0, 0, j] = c
        dm_ref[0, 0, j] = _dot(c, s_ref[0, 0, j],
                               ((1,), (1,))).astype(dm_ref.dtype)
        c_ref[j] = (e_ref[0, 0, j]
                    + _dot(m_ref[0, 0, j], c, ((0,), (0,)))).astype(c.dtype)


def _specs(m, b, reverse: bool):
    """The grid (batch, head blocks, chunks) and a block spec for arrays
    shaped like ``m`` and like ``b``, chunk ``t`` of the grid reading
    chunk ``n - 1 - t`` where ``reverse``."""
    from jax.experimental import pallas as pl

    bsz, n, heads, dk, dv = b.shape
    hb = _heads_per_block(heads, dk, dv, b.dtype.itemsize)

    def at(i, g, t):
        return (i, n - 1 - t if reverse else t, g, 0, 0)

    return ((bsz, heads // hb, n),
            pl.BlockSpec((1, 1, hb, dk, dk), at),
            pl.BlockSpec((1, 1, hb, dk, dv), at), hb)


def _params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


@traced_once(static_argnums=(2,))
def _forward(m, b, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid, m_spec, b_spec, hb = _specs(m, b, reverse=False)
    dk, dv = b.shape[-2:]
    with no_x64():
        return pl.pallas_call(
            _forward_kernel, grid=grid, in_specs=[m_spec, b_spec],
            out_specs=b_spec,
            out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), b.dtype)],
            compiler_params=_params(), interpret=interpret,
            name="kda_carry",
        )(m, b)


@traced_once(static_argnums=(3,))
def _backward(m, entering, e, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid, m_spec, b_spec, hb = _specs(m, entering, reverse=True)
    dk, dv = entering.shape[-2:]
    with no_x64():
        return pl.pallas_call(
            _backward_kernel, grid=grid, in_specs=[m_spec, b_spec, b_spec],
            out_specs=[m_spec, b_spec],
            out_shape=[jax.ShapeDtypeStruct(m.shape, m.dtype),
                       jax.ShapeDtypeStruct(entering.shape, entering.dtype)],
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), entering.dtype)],
            compiler_params=_params(), interpret=interpret,
            name="kda_carry_bwd",
        )(m, entering, e)


@jax.custom_vjp
def carry(m, b):
    """``entering`` (batch, chunks, heads, d_k, d_v), the state entering
    each chunk, for ``m`` (.., d_k, d_k) and ``b`` (.., d_k, d_v) of one
    dtype, the state's."""
    return _forward(m, b, _INTERPRET)


def _carry_fwd(m, b):
    entering = _forward(m, b, _INTERPRET)
    return entering, (m, entering)


def _carry_bwd(res, e):
    m, entering = res
    return tuple(_backward(m, entering, e, _INTERPRET))


carry.defvjp(_carry_fwd, _carry_bwd)

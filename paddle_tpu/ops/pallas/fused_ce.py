"""Fused linear + softmax cross-entropy — Pallas TPU kernels, fwd + bwd.

Plays the reference's fused softmax-CE role
(paddle/fluid/operators/softmax_with_cross_entropy_op.* and the fused-op
tier under operators/fused/) for the LM-head case where it matters: the
(N, V) logits of ``h @ W.T`` are never materialised in HBM.  For GPT-2
(N = B·S = 8192, V = 50257) the baseline path writes and re-reads
~1.7 GB of f32 logits in each direction; here every logits tile lives in
VMEM only, and HBM traffic is O(N·H + V·H) per pass.

Forward: grid (n_blocks, v_blocks), vocab innermost — running (max,
sum-exp) scratch per row block, exactly the flash-attention online
softmax but with no value matrix.  Emits logz (N,) as the residual.
The "gold" logit ``h·W[label]`` is a cheap O(N·H) XLA gather outside.

Backward (p-tiles recomputed from logz, FlashAttention-style):
  - dh:   grid (n_blocks, v_blocks):  dh  += (g·p) @ W,  acc in VMEM.
  - dW:   grid (v_blocks, n_blocks):  dW  += (g·p).T @ h, acc in VMEM.
The label one-hot terms (−g·W[label] into dh, scatter −g·h into dW) are
O(N·H) XLA gathers/scatters outside the kernels.  p is cast to the input
dtype (bf16 on chip) for the second matmul so the MXU runs at full rate;
accumulation stays f32 via preferred_element_type.

Vocab sizes that don't divide the block (50257 = 29·1733 has no useful
factor) ride a padded weight matrix; padded columns are masked to -inf
with an iota guard so the padding never perturbs logsumexp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Block defaults — sized for ~16 MB VMEM (see module docstring math):
# fwd/dh keep an (bn, H) f32 accumulator, dw a (bv, H) one.
BLOCK_N_FWD = 2048
BLOCK_N_BWD = 1024
BLOCK_V = 512
BLOCK_V_DW = 2048
BLOCK_N_DW = 256
_MIN_BLOCK = 128

# tests flip this to run the kernels in interpreter mode on CPU
_INTERPRET = False


def supported(n: int, h: int) -> bool:
    """Can the fused kernel serve this (N tokens, H hidden) head?

    Any token count works: like the vocab axis, a non-divisible N rides
    zero-padded rows (padded loss/grad rows are exactly zero and sliced
    off), and N=0 short-circuits before the kernels.
    """
    if not (backend_is_tpu() or _INTERPRET):
        return False
    return n >= 0 and h % 128 == 0


def _pick(pref: int, size: int) -> int:
    b = min(pref, size)
    while b > _MIN_BLOCK and size % b:
        b //= 2
    return max(b, _MIN_BLOCK)


from paddle_tpu.ops.pallas.common import (  # noqa: E402
    backend_is_tpu, dot_nt as _dot_nt, no_x64)


# ---------------------------------------------------------------------------
# forward: logz = logsumexp_v(h @ W.T)
# ---------------------------------------------------------------------------


def _fwd_kernel(h_ref, w_ref, logz_ref, m_scr, l_scr, *, block_v, n_vb, v):
    from jax.experimental import pallas as pl

    vb = pl.program_id(1)

    @pl.when(vb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)

    s = _dot_nt(h_ref[...], w_ref[...])                 # (bn, bv) f32
    col = vb * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < v, s, -jnp.inf)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    alpha = jnp.where(jnp.isfinite(m_prev), alpha, 0.0)
    m_scr[...] = m_new
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)

    @pl.when(vb == n_vb - 1)
    def _finish():
        logz_ref[...] = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))


def _ce_logz(h, w_pad, v):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, hd = h.shape
    v_pad = w_pad.shape[0]
    block_n = _pick(BLOCK_N_FWD, n)
    block_v = _pick(BLOCK_V, v_pad)
    n_vb = v_pad // block_v

    kernel = functools.partial(_fwd_kernel, block_v=block_v, n_vb=n_vb, v=v)
    with no_x64():
        logz = pl.pallas_call(
            kernel,
            grid=(n // block_n, n_vb),
            in_specs=[
                pl.BlockSpec((block_n, hd), lambda nb, vb: (nb, 0)),
                pl.BlockSpec((block_v, hd), lambda nb, vb: (vb, 0)),
            ],
            out_specs=pl.BlockSpec((block_n, 1), lambda nb, vb: (nb, 0)),
            out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
            scratch_shapes=[pltpu.VMEM((block_n, 1), jnp.float32),
                            pltpu.VMEM((block_n, 1), jnp.float32)],
            name="fused_ce_fwd",
            interpret=_INTERPRET,
        )(h, w_pad)
    return logz


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dh_kernel(h_ref, w_ref, logz_ref, g_ref, dh_ref, acc_scr, *, block_v,
               n_vb, v):
    from jax.experimental import pallas as pl

    vb = pl.program_id(1)

    @pl.when(vb == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = _dot_nt(h_ref[...], w_ref[...])                 # (bn, bv) f32
    col = vb * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < v, s, -jnp.inf)
    p = jnp.exp(s - logz_ref[...]) * g_ref[...]         # (bn, bv)
    # cast to the weight dtype so the MXU runs at bf16 rate; f32 acc
    acc_scr[...] += jnp.dot(p.astype(w_ref.dtype), w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(vb == n_vb - 1)
    def _finish():
        dh_ref[...] = acc_scr[...].astype(dh_ref.dtype)


def _dw_kernel(h_ref, w_ref, logz_ref, g_ref, dw_ref, acc_scr, *, block_v,
               n_nb, v):
    from jax.experimental import pallas as pl

    vb = pl.program_id(0)
    nb = pl.program_id(1)

    @pl.when(nb == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = _dot_nt(h_ref[...], w_ref[...])                 # (bn, bv) f32
    col = vb * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < v, s, -jnp.inf)
    p = jnp.exp(s - logz_ref[...]) * g_ref[...]         # (bn, bv)
    # dW_tile += p.T @ h  — contract the token axis
    acc_scr[...] += jax.lax.dot_general(
        p.astype(h_ref.dtype), h_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(nb == n_nb - 1)
    def _finish():
        dw_ref[...] = acc_scr[...].astype(dw_ref.dtype)


def _ce_bwd_kernels(h, w_pad, logz, g, v):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, hd = h.shape
    v_pad = w_pad.shape[0]
    g2 = g.reshape(n, 1).astype(jnp.float32)

    block_n = _pick(BLOCK_N_BWD, n)
    block_v = _pick(BLOCK_V, v_pad)
    with no_x64():
        dh = pl.pallas_call(
            functools.partial(_dh_kernel, block_v=block_v,
                              n_vb=v_pad // block_v, v=v),
            grid=(n // block_n, v_pad // block_v),
            in_specs=[
                pl.BlockSpec((block_n, hd), lambda nb, vb: (nb, 0)),
                pl.BlockSpec((block_v, hd), lambda nb, vb: (vb, 0)),
                pl.BlockSpec((block_n, 1), lambda nb, vb: (nb, 0)),
                pl.BlockSpec((block_n, 1), lambda nb, vb: (nb, 0)),
            ],
            out_specs=pl.BlockSpec((block_n, hd), lambda nb, vb: (nb, 0)),
            out_shape=jax.ShapeDtypeStruct((n, hd), h.dtype),
            scratch_shapes=[pltpu.VMEM((block_n, hd), jnp.float32)],
            name="fused_ce_dh",
            interpret=_INTERPRET,
        )(h, w_pad, logz, g2)

        block_vd = _pick(BLOCK_V_DW, v_pad)
        block_nd = _pick(BLOCK_N_DW, n)
        dw = pl.pallas_call(
            functools.partial(_dw_kernel, block_v=block_vd,
                              n_nb=n // block_nd, v=v),
            grid=(v_pad // block_vd, n // block_nd),
            in_specs=[
                pl.BlockSpec((block_nd, hd), lambda vb, nb: (nb, 0)),
                pl.BlockSpec((block_vd, hd), lambda vb, nb: (vb, 0)),
                pl.BlockSpec((block_nd, 1), lambda vb, nb: (nb, 0)),
                pl.BlockSpec((block_nd, 1), lambda vb, nb: (nb, 0)),
            ],
            out_specs=pl.BlockSpec((block_vd, hd), lambda vb, nb: (vb, 0)),
            out_shape=jax.ShapeDtypeStruct((v_pad, hd), w_pad.dtype),
            scratch_shapes=[pltpu.VMEM((block_vd, hd), jnp.float32)],
            name="fused_ce_dw",
            interpret=_INTERPRET,
        )(h, w_pad, logz, g2)
    return dh, dw


# ---------------------------------------------------------------------------
# custom-vjp wrapper + public API
# ---------------------------------------------------------------------------


def _pad_w(w):
    v = w.shape[0]
    v_pad = -(-v // _MIN_BLOCK) * _MIN_BLOCK
    if v_pad != v:
        w = jnp.pad(w, ((0, v_pad - v), (0, 0)))
    return w


def _pad_n(x):
    """Zero-pad the token axis to a _MIN_BLOCK multiple — the grids
    floor n/block, so a remainder would silently drop trailing tokens
    (the PTA601 finding).  Zero rows are exact: the fwd's padded logz
    rows are sliced off, and the bwd pads g with zeros so every padded
    p·g tile is exactly 0 (no dw perturbation)."""
    n = x.shape[0]
    n_pad = max(_MIN_BLOCK, -(-n // _MIN_BLOCK) * _MIN_BLOCK)
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n),) + ((0, 0),) * (x.ndim - 1))
    return x


@jax.custom_vjp
def _fused_ce(h, w, labels_f):
    loss, _ = _fused_ce_fwd(h, w, labels_f)
    return loss


def _fused_ce_fwd(h, w, labels_f):
    v = w.shape[0]
    n = h.shape[0]
    lab = labels_f.astype(jnp.int32)
    if n == 0:
        logz = jnp.zeros((0,), jnp.float32)
        return logz, (h, w, lab, logz)
    w_pad = _pad_w(w)
    logz = _ce_logz(_pad_n(h), w_pad, v)[:n, 0]         # (n,)
    gold_w = jnp.take(w, jnp.clip(lab, 0, v - 1), axis=0)
    gold = jnp.sum(h.astype(jnp.float32) * gold_w.astype(jnp.float32),
                   axis=-1)
    loss = logz - gold                                  # (n,) f32
    return loss, (h, w, lab, logz)


def _fused_ce_bwd(res, g):
    h, w, lab, logz = res
    v, hd = w.shape
    n = h.shape[0]
    if n == 0:
        return (jnp.zeros_like(h), jnp.zeros_like(w),
                jnp.zeros_like(res[2], dtype=jnp.float32))
    w_pad = _pad_w(w)
    # padded rows carry g=0, so their p·g tiles are exactly 0 in both
    # kernels; logz pads with zeros (any finite value works under g=0)
    dh, dw_pad = _ce_bwd_kernels(
        _pad_n(h), w_pad, _pad_n(logz.reshape(n, 1)),
        _pad_n(g.reshape(n, 1)), v)
    dh = dh[:n]
    dw = dw_pad[:v]
    # one-hot (gold) terms, O(N·H) XLA gather/scatter
    gf = g.reshape(n, 1).astype(jnp.float32)
    lab_c = jnp.clip(lab, 0, v - 1)
    dh = dh - (gf * jnp.take(w, lab_c, axis=0).astype(jnp.float32)
               ).astype(dh.dtype)
    # scatter-accumulate in f32: repeated labels (frequent tokens) would
    # round to nothing in a bf16 accumulator
    gold_scatter = jnp.zeros((v, hd), jnp.float32).at[lab_c].add(
        gf * h.astype(jnp.float32))
    dw = (dw.astype(jnp.float32) - gold_scatter).astype(dw.dtype)
    return dh, dw, jnp.zeros_like(res[2], dtype=jnp.float32)


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def fused_linear_cross_entropy(h, w, labels):
    """Per-token ``-log softmax(h @ w.T)[label]`` without materialising
    logits.

    Args:
      h: (N, H) hidden states (any float dtype; bf16 on chip).
      w: (V, H) classifier/embedding weight (tied LM head).
      labels: (N,) integer class ids.  Negative ids are treated as
        padding: their loss entry is computed against class 0 and should
        be masked by the caller (the gradient contribution is whatever
        the caller's mask makes of it — multiply the per-token loss by
        the mask *before* summing).

    Returns (N,) float32 per-token loss.
    """
    # labels ride as f32 (exact for ids < 2^24): custom_vjp wants float
    # cotangents for every positional arg (in-repo precedent:
    # flash_attention segment ids)
    return _fused_ce(h, w, labels.astype(jnp.float32))


def xla_reference(h, w, labels):
    """Unfused reference (materialises logits) for tests/benches."""
    lg = (h @ w.T).astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    v = w.shape[0]
    lab = jnp.clip(labels, 0, v - 1)
    gold = jnp.take_along_axis(lg, lab[:, None], axis=-1)[:, 0]
    return logz - gold

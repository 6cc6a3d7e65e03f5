"""State-space (Mamba-2) mixer and its norms, on raw arrays.

The reference framework has no state-space layer.  These are the pieces a
model file composes (``models/nemotron_h.py``): RMSNorm, the gated RMSNorm
over groups of channels, the causal depthwise convolution, the SSD scan
and the whole mixer.  Everything is ``jax.numpy`` / ``jax.lax`` and
differentiable by jax; no kernel.  The device scopes ``in_proj``,
``conv``, ``scan``, ``gate_norm`` and ``out`` are set here, the region
around them (``ssm``) by the caller.

SSD (Dao & Gu 2024, "Transformers are SSMs", section 6): the recurrence

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t,      y_t = C_t . h_t

is computed in chunks of ``chunk`` positions.  Within a chunk the output
is a masked ``C B^T`` product weighted by the decay between the two
positions; across chunks the ``(heads, head_dim, state)`` state is
carried by a ``lax.scan`` over chunks.  Decays, their cumulative sums and
the carried state are float32 whatever the inputs are.  A sequence that
is no multiple of the chunk is padded at its end with positions of
``dt = 0`` (decay 1, no input: the state passes through unchanged) and
the padded outputs are cut off: it is padded, not refused.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.framework import monitor

__all__ = ["rms_norm_array", "gated_group_rms_norm", "causal_depthwise_conv1d",
           "ssd_chunked", "mamba2_mixer"]

# what the carried state and the decays are computed in; the builder's
# check on the chip sets bfloat16 here to show that the hidden-state
# comparison sees it (PERF.md section 6, PR 27).  Not an option.
_STATE_DTYPE = jnp.float32

monitor.describe("ssm_chunks_traced_total",
                 "chunks (batch x chunks a sequence) of the SSD scan, "
                 "added once per traced call of ssd_chunked (a trace-time "
                 "count)")


def rms_norm_array(x, weight, eps: float):
    """``x / sqrt(mean(x^2) + eps) * weight`` over the last axis,
    statistics in float32, result in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def gated_group_rms_norm(y, gate, weight, groups: int, eps: float):
    """``GroupRMSNorm_G(y * silu(gate)) * weight``: the last axis is cut
    into ``groups`` equal groups, each normalised by its own mean square
    (Mamba-2's gated norm with ``norm_before_gate = False``)."""
    yf = y.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    grouped = yf.reshape(*yf.shape[:-1], groups, yf.shape[-1] // groups)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, -1, keepdims=True) + eps)
    return (grouped.reshape(yf.shape)
            * weight.astype(jnp.float32)).astype(y.dtype)


def causal_depthwise_conv1d(x, weight, bias):
    """``out[t, c] = bias[c] + sum_j weight[j, c] x[t - (k-1) + j, c]``
    for ``x`` (batch, seq, channels) and ``weight`` (k, channels), zeros
    before the sequence: k shifted multiply-adds."""
    k, s = weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = bias
    for j in range(k):
        out = out + padded[:, j:j + s] * weight[j]
    return out.astype(x.dtype)


def ssd_chunked(x, dt, a, b_in, c_in, chunk: int):
    """The SSD scan.  ``x`` (batch, seq, heads, head_dim); ``dt`` (batch,
    seq, heads), already positive; ``a`` (heads,), negative; ``b_in``,
    ``c_in`` (batch, seq, groups, state), head ``h`` reading group
    ``h // (heads / groups)``.  Returns ``y`` like ``x`` (without the
    ``D x`` skip)."""
    bsz, seq, heads, dim = x.shape
    groups, state = b_in.shape[2:]
    per = heads // groups
    pad = -seq % chunk
    if pad:
        x, dt, b_in, c_in = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b_in, c_in))
    n = (seq + pad) // chunk
    monitor.stat_add("ssm_chunks_traced_total", bsz * n)
    wide = _STATE_DTYPE            # float32: decays, their sums, the state
    xc = x.reshape(bsz, n, chunk, groups, per, dim)
    bc = b_in.reshape(bsz, n, chunk, groups, state)
    cc = c_in.reshape(bsz, n, chunk, groups, state)
    dtc = dt.astype(wide).reshape(bsz, n, chunk, groups, per)
    dtc = dtc.transpose(0, 1, 3, 4, 2)                  # (b, n, g, r, q)
    # log-decay of every step, and its running sum inside the chunk
    cum = jnp.cumsum(dtc * a.astype(wide).reshape(groups, per, 1), axis=-1)

    # within a chunk: y_l = sum_{s <= l} (C_l . B_s) e^{cum_l - cum_s}
    # dt_s x_s
    scores = jnp.einsum("bnlgk,bnsgk->bngls", cc, bc,
                        preferred_element_type=wide)
    span = cum[..., :, None] - cum[..., None, :]         # (b,n,g,r,l,s)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, span, -jnp.inf))
    mix = scores[:, :, :, None] * decay * dtc[..., None, :]
    y = jnp.einsum("bngrls,bnsgrp->bnlgrp", mix.astype(x.dtype), xc,
                   preferred_element_type=wide)

    # what a chunk adds to the state by its end, and the state carried in
    to_end = jnp.exp(cum[..., -1:] - cum) * dtc          # (b, n, g, r, q)
    xw = (xc.astype(wide) * to_end.transpose(0, 1, 4, 2, 3)[..., None])
    added = jnp.einsum("bnsgk,bnsgrp->bngrpk", bc, xw.astype(x.dtype),
                       preferred_element_type=wide)
    whole = jnp.exp(cum[..., -1])                        # (b, n, g, r)

    def carry(h, step):
        keep, new = step
        return (h * keep[..., None, None] + new).astype(wide), h

    zero = jnp.zeros((bsz, groups, per, dim, state), wide)
    _, entering = jax.lax.scan(
        carry, zero, (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(added, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)              # (b,n,g,r,p,k)
    across = jnp.einsum("bnlgk,bngrpk->bnlgrp", cc.astype(wide), entering,
                        preferred_element_type=wide)
    y = y + across * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]
    y = y.reshape(bsz, n * chunk, heads, dim)[:, :seq]
    return y.astype(x.dtype)


def mamba2_mixer(u, in_w, conv_w, conv_b, dt_bias, a_log, d_skip, norm_w,
                 out_w, *, heads: int, head_dim: int, groups: int,
                 state: int, chunk: int, eps: float):
    """One Mamba-2 mixer on ``u`` (batch, seq, hidden), ``heads`` heads of
    ``head_dim`` in ``groups`` groups of B / C with ``state`` states:

        [z | xBC | dt] = u W_in       (widths H P, H P + 2 G N, H)
        xBC = silu(conv1d_causal_depthwise(xBC) + b_conv)
        x, B, C = split(xBC);  dt = softplus(dt + dt_bias);  A = -exp(A_log)
        h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t;  y_t = C_t . h_t + D x_t
        out = (GroupRMSNorm_G(y * silu(z)) * w) W_out

    No bias except the convolution's."""
    bsz, seq = u.shape[:2]
    inner, bc = heads * head_dim, groups * state
    with jax.named_scope("in_proj"):
        z, xbc, dt = jnp.split(u @ in_w, [inner, 2 * inner + 2 * bc], -1)
    with jax.named_scope("conv"):
        xbc = jax.nn.silu(causal_depthwise_conv1d(xbc, conv_w, conv_b))
    with jax.named_scope("scan"):
        x, b_in, c_in = jnp.split(xbc, [inner, inner + bc], -1)
        x = x.reshape(bsz, seq, heads, head_dim)
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + dt_bias.astype(jnp.float32))
        y = ssd_chunked(x, dt, -jnp.exp(a_log.astype(jnp.float32)),
                        b_in.reshape(bsz, seq, groups, state),
                        c_in.reshape(bsz, seq, groups, state), chunk)
        y = y + x * d_skip.astype(x.dtype)[:, None]
    with jax.named_scope("gate_norm"):
        y = gated_group_rms_norm(y.reshape(bsz, seq, inner), z, norm_w,
                                 groups, eps)
    with jax.named_scope("out"):
        return y @ out_w

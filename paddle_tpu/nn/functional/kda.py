"""Kimi Delta Attention (KDA): a gated delta rule with a decay per channel.

The reference framework has no linear-attention layer.  This is the mixer
of Kimi Linear (arXiv:2510.26692) as a model file composes it
(``models/bailing_hybrid.py``): per head, with ``d_k``-wide keys and
``d_v``-wide values, a ``(d_k, d_v)`` state

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with ``alpha_t`` in (0, 1] per key channel and ``beta_t`` in (0, 1) per
head.  Everything but the state's pass across chunks is ``jax.numpy`` /
``jax.lax``, differentiable by jax.  The device scopes ``qkv``, ``conv``,
``gate``, ``scan``, ``out_norm`` and ``out`` are set here, the region
around them (``kda``) and its ``ln`` by the caller.

**Chunks** (``kda_chunked``).  The sequence is cut into chunks of ``C``
positions; within one, with ``g_t = log alpha_t`` and ``gamma_i = sum_{t
<= i} g_t`` from the chunk's start (``Gamma = exp(gamma)``), the WY form
of the delta rule gives, for the state ``S`` entering the chunk,

    T = (I + StrictLower(Diag(beta) (K o Gamma)(K / Gamma)^T))^-1 Diag(beta)
    W = T (K o Gamma),   U = T V,   Delta = U - W S
    O = (Q o Gamma) S + Lower((Q o Gamma)(K / Gamma)^T) Delta
    S <- Diag(Gamma_C) S + (K o Gamma_C / Gamma)^T Delta

Everything but ``S`` is computed for all chunks at once: ``W``, ``U``,
``Q o Gamma``, the masked ``A_qk`` and ``K o Gamma_C / Gamma``.  The
state's pass across the chunks takes them and walks the chunks in order,
``Delta``, ``O`` and the state's update at each (``kda_carry.chunk``; no
``(d_k, d_k)`` matrix of a chunk is built): by the Pallas kernels of
``ops/pallas/kda_carry.py``, forward and backward, which hold the state
in VMEM across all chunks, where ``kda_carry.supported`` takes the widths
and the state's dtype (a TPU, lane-wide heads), else by the same step in
a ``lax.scan``.  Every product of the rule is float32 at the highest
matmul precision, so that on float32 inputs the chunks agree with the
token recurrence to float32's rounding.  The unit lower-triangular
inverse is taken by blocks of 16: forward substitution inside the
diagonal blocks, 16 steps of a row each, in float32, and block products
below them (its gradient, ``T^T dT T^T``, two matmuls).

**Decay ratios in range.**  ``(K o Gamma)(K / Gamma)^T`` asks for
``exp(gamma_i - gamma_j)``, at most 1 where ``j <= i``, but its factors
``exp(gamma_i)`` and ``exp(-gamma_j)`` reach ``exp(+-C * |g|)``: with the
gate's lower bound of -5 and ``C = 64``, ``e^320``, beyond float32.  So the
products are factored through ``Gamma`` only inside **sub-chunks of 16
positions**: row ``i`` in sub-chunk ``a`` takes ``exp(gamma_i - gamma_a)``
and column ``j`` takes ``exp(gamma_a - gamma_j)``, ``gamma_a`` the
sub-chunk's first position; both factors lie within ``e^+-80`` (15 steps of
at most 5 on one side; on the other ``gamma_a - gamma_j <= 0`` for ``j``
before the sub-chunk), and columns past the sub-chunk, which the causal
mask drops, are zero before the product.  Sixteen is the widest sub-chunk
that keeps ``e^(16 x 5)`` inside float32's ``e^88``: this is why the
published gate has a lower bound.  Every other factor (``Gamma``,
``Gamma_C / Gamma``, ``Gamma_C``) is an ``exp`` of a sum of non-positive
steps and lies in (0, 1].  A sequence that is no multiple of ``C`` is
padded with positions of ``g = 0`` and ``beta = 0`` that pass the state
through unchanged, and their outputs cut off.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from paddle_tpu.framework import monitor
from paddle_tpu.nn.functional import ssm as _ssm

__all__ = ["l2_norm", "kda_gate", "kda_chunked", "kda_mixer"]

SUB_CHUNK = 16

# what the state carried across chunks is held in (its products take it
# in float32); tools/ling3_check.py sets bfloat16 here to show that its
# comparison of the scan with the token recurrence sees it.  Not an option.
_STATE_DTYPE = jnp.float32

_HIGHEST = jax.lax.Precision.HIGHEST

monitor.describe("kda_chunks_traced_total",
                 "chunks (batch x heads x chunks a sequence) of the KDA "
                 "chunked delta rule, added once per traced call of "
                 "kda_chunked (a trace-time count)")
monitor.describe("kda_carry_kernel_total",
                 "traced calls of kda_chunked whose state pass across "
                 "chunks (delta, output and the state's update at each) "
                 "took the Pallas kernels of ops/pallas/kda_carry.py (a "
                 "trace-time count)")


def l2_norm(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, in float32,
    result in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True) + eps)
            ).astype(x.dtype)


def kda_gate(f, a_log, dt_bias, lower_bound: float):
    """``log alpha = lower_bound * sigmoid(exp(A_log) * (f + dt_bias))``,
    the lower-bounded ("safe") gate, float32: ``f`` (..., heads, d_k),
    ``a_log`` (heads,), ``dt_bias`` (heads, d_k).  Each step lies in
    (lower_bound, 0)."""
    z = (f.astype(jnp.float32) + dt_bias.astype(jnp.float32)) \
        * jnp.exp(a_log.astype(jnp.float32))[:, None]
    return lower_bound * jax.nn.sigmoid(z)


@jax.custom_vjp
def _unit_lower_inverse(m):
    """``(I - m)^-1`` for ``m`` (..., C, C) strictly lower triangular, by
    blocks of ``SUB_CHUNK``: each diagonal block by forward substitution,
    a row a step, ``x_i = e_i + sum_{j<i} m_ij x_j``, in float32 on the
    vector unit; then the blocks below it, block row after block row,
    ``X_ab = X_aa sum_{b<=c<a} M_ac X_cb``.  (A product of ``(I +
    m^(2^k))`` is not stable: after the convolution's SiLU the keys of a
    chunk point alike, ``m``'s entries are near -beta, and its powers grow
    as the binomial coefficients of C while the inverse stays small.)"""
    return _substitute(m)


def _substitute(m):
    sub = SUB_CHUNK
    n = m.shape[-1] // sub
    block = m.reshape(*m.shape[:-2], n, sub, n, sub)
    diag = jnp.stack([block[..., a, :, a, :] for a in range(n)], axis=-3)
    eye = jnp.eye(sub, dtype=m.dtype)

    def row(i, x):
        below = jnp.sum(diag[..., i, :, None] * x, axis=-2)
        return jax.lax.dynamic_update_index_in_dim(x, eye[i] + below, i, -2)

    inv = jax.lax.fori_loop(0, sub, row, jnp.zeros_like(diag))
    x = {}
    for a in range(n):
        x[a, a] = inv[..., a, :, :]
        for b in range(a):
            acc = sum(jnp.matmul(block[..., a, :, c, :], x[c, b],
                                 precision=_HIGHEST) for c in range(b, a))
            x[a, b] = jnp.matmul(x[a, a], acc, precision=_HIGHEST)
    zero = jnp.zeros_like(x[0, 0])
    return jnp.concatenate([jnp.concatenate(
        [x.get((a, b), zero) for b in range(n)], axis=-1)
        for a in range(n)], axis=-2)


def _inverse_fwd(m):
    t = _substitute(m)
    return t, t


def _inverse_bwd(t, dt):
    # t = (I - m)^-1: dt = t dm t, so dm = t^T dt t^T
    tt = jnp.swapaxes(t, -1, -2)
    return (jnp.matmul(jnp.matmul(tt, dt, precision=_HIGHEST), tt,
                       precision=_HIGHEST),)


_unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _state_pass(w, u, qg, aqk, kt, gc):
    """``O`` (b, n, heads, C, d_v) of the state's pass across the chunks,
    the state held in ``_STATE_DTYPE``: by the kernels where they take it,
    else by the same step in a ``lax.scan``."""
    from paddle_tpu.ops.pallas import kda_carry
    if kda_carry.supported(w.shape[-1], u.shape[-1], _STATE_DTYPE):
        monitor.stat_add("kda_carry_kernel_total", 1)
        return kda_carry.state_pass(_STATE_DTYPE, w, u, qg, aqk, kt, gc)
    return kda_carry.scan_pass(_STATE_DTYPE, w, u, qg, aqk, kt, gc)


def kda_chunked(q, k, v, g, beta, chunk: int = 64):
    """The chunked delta rule of the module's text.  ``q``, ``k`` (batch,
    seq, heads, d_k), ``v`` (batch, seq, heads, d_v): q already scaled;
    ``g`` (batch, seq, heads, d_k) float32 log decays in [-80/15, 0];
    ``beta`` (batch, seq, heads).  Returns ``o`` like ``v``."""
    bsz, seq, heads, dk = q.shape
    dv = v.shape[-1]
    if chunk % SUB_CHUNK:
        raise ValueError(f"chunk {chunk} is no multiple of {SUB_CHUNK}")
    pad = -seq % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    n = (seq + pad) // chunk
    monitor.stat_add("kda_chunks_traced_total", bsz * heads * n)
    f32 = jnp.float32

    def blocks(t):                          # (b, n, heads, C, width)
        return t.reshape(bsz, n, chunk, heads, -1).transpose(0, 1, 3, 2, 4)

    qc, kc, vc = (blocks(t).astype(f32) for t in (q, k, v))
    bc = blocks(beta[..., None].astype(f32))[..., 0]          # (b,n,h,C)
    gam = jnp.cumsum(blocks(g.astype(f32)), axis=-2)          # gamma_i
    subs = chunk // SUB_CHUNK
    first = gam[..., ::SUB_CHUNK, :]                          # gamma_a
    pos = jnp.arange(chunk)
    # row i of sub-chunk a: exp(gamma_i - gamma_a) <= 1
    rel = jnp.exp(gam - jnp.repeat(first, SUB_CHUNK, axis=-2))
    # column j under sub-chunk a: exp(gamma_a - gamma_j), zero past a
    within = (pos[None, :] < (jnp.arange(subs)[:, None] + 1) * SUB_CHUNK)
    inv = jnp.exp(jnp.where(within[:, :, None],
                            first[..., :, None, :] - gam[..., None, :, :],
                            -jnp.inf))                        # (.., a, C, dk)
    kin = kc[..., None, :, :] * inv

    def by_sub(x):                  # x o exp(gamma - gamma_a) (.., a, 16, dk)
        return (x * rel).reshape(*x.shape[:-2], subs, SUB_CHUNK, dk)

    def square(left):               # (.., C, C): left_i . (k_j / Gamma_j)
        # at the highest precision like every product here: at the TPU's
        # default of one bf16 pass these products, and the inverse built
        # on them, would carry bf16's rounding into every chunk's output
        return jnp.einsum("...aid,...ajd->...aij", by_sub(left), kin,
                          precision=_HIGHEST,
                          preferred_element_type=f32).reshape(
            *left.shape[:-1], chunk)

    strict = pos[:, None] > pos[None, :]
    akk = jnp.where(strict, square(kc), 0.0)
    # the diagonal, q_i . k_i, has no decay: taken as it is, its gradient
    # does not reach gamma by two factors that cancel (which costs the
    # gradient of g float32's last digits times exp(+-75))
    aqk = jnp.where(strict, square(qc), 0.0) \
        + jnp.eye(chunk, dtype=f32) * jnp.sum(qc * kc, -1)[..., None]
    t = _unit_lower_inverse(-bc[..., :, None] * akk) * bc[..., None, :]
    decay = jnp.exp(gam)                                      # Gamma_i
    w = jnp.matmul(t, kc * decay, precision=_HIGHEST)
    u = jnp.matmul(t, vc, precision=_HIGHEST)
    to_end = kc * jnp.exp(gam[..., -1:, :] - gam)             # K o G_C / G
    o = _state_pass(w, u, qc * decay, aqk, to_end, decay[..., -1:, :])
    o = o.transpose(0, 1, 3, 2, 4).reshape(bsz, n * chunk, heads, dv)
    return o[:, :seq].astype(v.dtype)


def kda_mixer(u, qkv_w, conv_w, beta_w, alpha_w, dt_bias, a_log, gate_w,
              norm_w, out_w, *, heads: int, head_dim: int, chunk: int,
              lower_bound: float, eps: float):
    """One KDA mixer on ``u`` (batch, seq, hidden), ``heads`` heads with
    keys and values ``head_dim`` wide:

        [q | k | v] = silu(conv1d_causal_depthwise(u W_qkv))   (no bias)
        q, k = L2Norm(q), L2Norm(k) per head;  beta = sigmoid(u W_beta)
        log alpha = lower_bound * sigmoid(exp(A_log) (u W_alpha + dt_bias))
        o = the delta rule on (q / sqrt(head_dim), k, v, alpha, beta)
        out = (RMSNorm_head(o) * w * sigmoid(u W_gate)) W_out

    ``conv_w`` (k, 3 x heads x head_dim); ``dt_bias`` (heads x head_dim,);
    ``a_log`` (heads,); ``norm_w`` (head_dim,), one for every head."""
    bsz, seq = u.shape[:2]
    width = heads * head_dim
    with jax.named_scope("qkv"):
        qkv = u @ qkv_w
    with jax.named_scope("conv"):
        qkv = jax.nn.silu(_ssm.causal_depthwise_conv1d(
            qkv, conv_w, jnp.zeros((), qkv.dtype)))
        q, k, v = (t.reshape(bsz, seq, heads, head_dim)
                   for t in jnp.split(qkv, 3, axis=-1))
        q = l2_norm(q) * jnp.asarray(1.0 / math.sqrt(head_dim), q.dtype)
        k = l2_norm(k)
    with jax.named_scope("gate"):
        g = kda_gate((u @ alpha_w).reshape(bsz, seq, heads, head_dim), a_log,
                     dt_bias.reshape(heads, head_dim), lower_bound)
        beta = jax.nn.sigmoid((u @ beta_w).astype(jnp.float32))
    with jax.named_scope("scan"):
        o = kda_chunked(q, k, v, g, beta, chunk)
    with jax.named_scope("out_norm"):
        o = _ssm.rms_norm_array(o, norm_w, eps).reshape(bsz, seq, width)
        o = o * jax.nn.sigmoid(u @ gate_w)
    with jax.named_scope("out"):
        return o @ out_w

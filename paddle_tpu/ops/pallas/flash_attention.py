"""Flash attention — Pallas TPU kernels, forward AND backward, with masks.

Replaces (and exceeds) the reference's fused attention kernels
(paddle/fluid/operators/fused/multihead_matmul_op.cu — which takes a
bias_qk mask input, and fused_embedding_eltwise_layernorm) with a
training-capable blockwise online-softmax attention: the S×S score matrix
never leaves VMEM, so HBM traffic is O(S·D) instead of O(S²) in BOTH
directions.

Masking (all composable with causal):
  - ``bias``: additive float mask, broadcastable (B|1, H|1, Sq|1, Sk).
    Loaded tile-wise; for the common padding shape (B, 1, 1, Sk) the
    extra HBM traffic is O(B·Sk) — negligible.  Bool masks are converted
    by the dispatcher to 0/-inf additive form.  d(bias) is computed by a
    dedicated reduction kernel (dead-code-eliminated under jit when the
    mask does not require grad — the usual case).
  - ``q_segment_ids``/``kv_segment_ids``: (B, Sq)/(B, Sk) int ids for
    packed sequences; q attends to k iff ids match.  O(B·S) memory where
    a materialised packed mask would be O(B·S²).

Forward: grid (batch*heads, q_blocks, kv_blocks); the kv axis is the
innermost, sequentially-executed grid axis, so running (max, sum-exp, acc)
state lives in VMEM scratch.  The per-row logsumexp is written out as a
residual for the backward.

Backward: three kernels, all recomputing p-tiles from (q, k, lse, mask):
  - dq:     grid (bh, q_blocks, kv_blocks), dq accumulates in VMEM over kv.
  - dk/dv:  grid (bh, kv_blocks, q_blocks), dk/dv accumulate over q.
  - dbias:  grid (g, kv_blocks, q_blocks, r) where g indexes the bias'
    own batch*head extent and r sweeps the broadcast (reduced) b/h
    extent; ds tiles accumulate in VMEM over the innermost reduction
    axes.  Only traced when a bias is present; DCE'd when unused.
The softmax-jacobian row term delta = rowsum(dO * O) is an O(S·D) XLA
precompute.  This is the standard FlashAttention-2 backward dataflow.

Two-level tiling: where a call has no bias, segments or tail, sq == sk and
the operands of one (batch, head) fit ``_RESIDENT_VMEM_BYTES``, the same
dataflow runs as a different loop nest.  What is resident is large, what
is computed at once is small: forward and dq run on a grid (bh, q_blocks)
with K and V whole in VMEM, dk/dv on (bh, kv_blocks) with Q, dO, lse and
delta whole, and the innermost axis is a static loop inside the kernel
over sub-tiles that carries its accumulators as values.  Under a causal
mask each sub-tile is clipped to the bounding box of its visible scores,
the loop stops at the diagonal, and only the sub-tiles the diagonal
crosses pay for the iota/compare/select mask: a grid step costs more than
the work a finer *grid* would skip (the measured reason S = 1024 sat on
one (1024, 1024) tile and computed the whole square), a sub-tile of
straight-line code does not.

Rows with no visible key (fully masked) produce output 0 with zero
gradients (lse = -inf); the XLA fallback's uniform-attention behaviour on
such rows is an artifact of its -1e30 clamp, not a semantic to preserve.

Causal masking is END-ALIGNED (query i sees keys j with j <= i + sk - sq),
matching the XLA fallback's ``tril(k=sk-sq)`` convention; ``supported()``
rejects causal sq > sk, where end-alignment would leave fully-masked rows.

Layout: (B, S, H, D) [paddle MultiHeadAttention layout].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# The tile a kernel computes at once, where ``flash_blocks.json`` holds no
# measured entry for the call's shape (``_blocks_for``); ``_pick_block``
# halves it toward _MIN_BLOCK for sequences it does not divide.  On the
# three-axis grid it is also what one grid step fetches, and 512/512 was
# the in-model winner there at long S (pre-ledger sweeps).  On the
# two-level nest it is (the block a grid step owns, the most a sub-tile
# takes of the resident axis), or the reverse in dk/dv, and sub-tiles are
# clipped to what a causal mask leaves of them; there a large block with
# small pieces wins, because the matmuls stay tall while the pieces follow
# the diagonal (measured at S = 1024, d = 64: PERF.md, PR 26).
BLOCK_Q = 512
BLOCK_K = 512
_MIN_BLOCK = 128

# Two-level tiling keeps these operands of one (batch, head) in VMEM for a
# whole row of the grid: K and V (forward, dq), or Q, dO, lse and delta
# (dk/dv).  Their VMEM footprint (lanes padded to 128) may be this many
# bytes; the pipeline double-buffers it, so the kernels hold twice that of
# the 16 MiB Mosaic scopes by default, beside their working tiles.  2 MiB
# admits S <= 1024 at d <= 128 in any dtype up to four bytes, which is as
# far as the nest has been measured (PERF.md, PR 26).
_RESIDENT_VMEM_BYTES = 2 * 2 ** 20

# tests flip this to run the kernels in interpreter mode on CPU
_INTERPRET = False

_NEG_INF = float("-inf")

from paddle_tpu.framework import monitor  # noqa: E402
from paddle_tpu.ops.pallas.common import (  # noqa: E402
    backend_is_tpu, dot_nt as _dot_nt, no_x64)

monitor.describe("flash_subtiles_computed_total",
                 "squares of the score matrix, of side gcd(block_q, "
                 "block_k), that the flash kernels' two-level loop nest "
                 "executes, over batch x heads, added once per traced "
                 "kernel call (a trace-time count)")
monitor.describe("flash_subtiles_skipped_total",
                 "squares of the score matrix above the causal diagonal "
                 "that the flash kernels' two-level loop nest leaves out, "
                 "over batch x heads, added once per traced kernel call (a "
                 "trace-time count)")
monitor.describe("flash_dispatch_kernel_total",
                 "attention calls that flash_attention.supported() sent to "
                 "the Pallas kernels, added once per decision, which a "
                 "model takes while it is traced (a trace-time count)")
monitor.describe("flash_dispatch_xla_for_speed_total",
                 "attention calls the Pallas kernels could have computed "
                 "that flash_attention.supported() left to XLA because it "
                 "is faster at their shape (not: incapable), added once "
                 "per decision (a trace-time count)")


def _canon_bias_shape(bias_shape, b, h, sq, sk):
    """Canonicalise a broadcastable mask/bias shape to (Bb, Hb, Sqb, Sk).

    Returns the 4-tuple, or None if the shape can't ride the kernel
    (each dim must be 1 or full; the key dim must be full).
    """
    s = tuple(int(d) for d in bias_shape)
    if len(s) > 4 or len(s) < 1:
        return None
    s = (1,) * (4 - len(s)) + s
    bb, hb, sqb, skb = s
    if skb != sk:
        return None
    if bb not in (1, b) or hb not in (1, h) or sqb not in (1, sq):
        return None
    return (bb, hb, sqb, skb)


def _capable(q_shape, k_shape, no_mask, causal, bias_shape,
             segments) -> bool:
    """Can the kernels compute this call at all?  Backend, rank, head
    width, a mask they can express, block divisibility of a masked call;
    nothing here is about speed."""
    if not no_mask and bias_shape is None and not segments:
        return False
    if not (backend_is_tpu() or _INTERPRET):
        return False
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    b, sq, h, d = q_shape
    sk = k_shape[1]
    if causal and sq > sk:
        # end-aligned causal with more queries than keys leaves rows with
        # no visible key; semantics degenerate — use the XLA path
        return False
    # 192: latent attention's q.k width (128 + 64 rotary), one block
    # spanning the whole width, as Mosaic compiles it for a v5e
    if d % 128 != 0 and d not in (64, 192):
        return False
    if bias_shape is not None and \
            _canon_bias_shape(bias_shape, b, h, sq, sk) is None:
        return False
    if bias_shape is not None or segments:
        # the bias/segment tile specs are not tail-masked, so the mask
        # path keeps the block-divisibility requirement
        block_q = _pick_block(BLOCK_Q, sq)
        block_k = _pick_block(BLOCK_K, sk)
        if sq % block_q or sk % block_k:
            return False
        return sq % _MIN_BLOCK == 0 and sk % _MIN_BLOCK == 0 \
            and sq >= _MIN_BLOCK and sk >= _MIN_BLOCK
    # no mask: non-divisible sequences ride cdiv grids with tail-masked
    # blocks (out-of-range keys scored -inf, tail q/do rows zeroed in the
    # backward contractions); sub-block sequences still fall back to XLA
    return sq >= _MIN_BLOCK and sk >= _MIN_BLOCK


# The sequence lengths at which a square, mask-free, non-causal call of
# d = 64 runs faster on the kernels than on XLA's attention with a
# materialised score tensor.  Measured in the model (my chip run, PR 31:
# BERT-base, bf16, per-layer remat, 16,384 tokens a step on one v5e,
# ``tools/flash_gate_sweep.py``; XLA against the kernels, ms a step; 42 and
# 21 sequences, 16,128 tokens, at S = 384 and 768):
#
#     S    step             attn core        tokens/s
#   128   106.9 / 157.5     8.3 / 60.9      152,994 / 103,813
#   256   119.0 / 146.9    18.0 / 50.0      137,404 / 111,256
#   384   132.3 / 145.5    34.5 / 49.6      121,655 / 110,618
#   512   146.0 / 141.9    45.8 / 44.3      111,986 / 115,247
#   768   163.6 / 165.1    66.0 / 68.8       98,400 /  97,497
#
# The kernels' own time at S = 512 is 29.0 ms of that core (forward 0.50,
# dq 0.64, dk/dv 0.77 ms a layer at the table's (512, 512) tiles); the
# other 15 ms are the ``_fold`` / ``_unfold`` transposes and ``delta``
# around them, which do not shrink with S, and a head's grid step, which
# S = 128 pays 1,536 times a call.  S = 768 has no tile that covers its
# square ((256, 256), which at S = 512 costs 1.6 times the (512, 512)
# one): tune it before it is listed.
_FULL_D64_FASTER_AT = (512,)


def _faster_than_xla(sq: int, sk: int, d: int, causal: bool,
                     masked: bool) -> bool:
    """Is the kernel the faster of the two paths for a call it can
    compute?  A pure function of what the call shows when it is traced.
    Causal calls and calls with 1024 queries or keys or more keep the
    kernel (the GPT-2 and hybrid cells; XLA's O(S^2) score tensor only
    grows).  Below that, only what ``_FULL_D64_FASTER_AT`` was measured
    for: a bias or segment ids, a tail (a ViT's S = 197), sq != sk and
    d = 128 under 1024 were not swept and stay on XLA, as before PR 31."""
    if causal or sq >= 1024 or sk >= 1024:
        return True
    return not masked and sq == sk and d == 64 \
        and sq in _FULL_D64_FASTER_AT


def supported(q_shape, k_shape, no_mask: bool = True, causal: bool = False,
              bias_shape=None, segments: bool = False) -> bool:
    """Should the Pallas kernels serve this attention call?  They must be
    able to (``_capable``) and be the faster path (``_faster_than_xla``);
    each decision on speed is counted, once per call of this function,
    which the models make while they are traced.

    ``no_mask`` is the legacy round-2 argument: a mask used to force the
    XLA fallback.  Now a mask is fine as long as it is expressible as a
    canonical additive bias (``bias_shape``) and/or segment ids.
    Interpret mode (the CPU tests) takes every capable call to the
    kernels, whatever its size.
    """
    if not _capable(q_shape, k_shape, no_mask, causal, bias_shape,
                    segments):
        return False
    if _INTERPRET:
        return True
    faster = _faster_than_xla(q_shape[1], k_shape[1], q_shape[3],
                              bool(causal),
                              bias_shape is not None or bool(segments))
    monitor.stat_add("flash_dispatch_kernel_total" if faster
                     else "flash_dispatch_xla_for_speed_total", 1)
    return faster


def _pick_block(pref: int, seq: int) -> int:
    """Largest block <= pref that divides seq, halving down to _MIN_BLOCK
    (keeps e.g. seq=384 on the kernel path instead of silently falling
    back to the O(S^2) XLA reference)."""
    b = min(pref, seq)
    while b > _MIN_BLOCK and seq % b:
        b //= 2
    return max(b, _MIN_BLOCK)


def _blocks_for(sq, sk, d, dtype, causal, biased, direction="fwd"):
    """(block_q, block_k) — the measured autotune cache first (keyed on
    shape/dtype/mask class and, for the backward, the direction: the
    dq/dkv kernels have different per-tile reuse than the forward so
    their winning tile can differ), else the BLOCK_Q/K heuristic; either
    way halved until it divides the sequence."""
    from paddle_tpu.ops.pallas import autotune
    hit = autotune.lookup(sq, sk, d, str(dtype), causal, biased,
                          direction=direction)
    bq, bk = hit if hit else (BLOCK_Q, BLOCK_K)
    return _pick_block(bq, sq), _pick_block(bk, sk)


def _vmem_bytes(rows, cols, itemsize):
    """Bytes a (rows, cols) operand takes in VMEM: lanes padded to 128,
    rows to the dtype's sublane packing (8 rows of 32 bits)."""
    sub = 8 * max(1, 4 // itemsize)
    return -(-rows // sub) * sub * -(-cols // 128) * 128 * itemsize


def _two_level(sq, sk, d, dtype, block_q, block_k, has_mask):
    """Does this call run the two-level nest (module docstring)?  Decided
    from what the call shows at trace time: no bias or segments, a square
    score matrix the tiles divide, and the resident operands of one
    (batch, head) within ``_RESIDENT_VMEM_BYTES``.  Causal or not: without
    a mask the loop runs to the last sub-tile."""
    if has_mask or sq != sk or sq % block_q or sk % block_k:
        return False
    item = jnp.dtype(dtype).itemsize
    kv = 2 * _vmem_bytes(sk, d, item)                    # forward, dq
    q_side = 2 * _vmem_bytes(sq, d, item) + 2 * _vmem_bytes(sq, 1, 4)
    return max(kv, q_side) <= _RESIDENT_VMEM_BYTES


def _count_subtiles(bh, s, block, width, causal, block_is_q):
    """Add one traced call of a two-level kernel to the monitor: the
    scores its sub-tiles cover (``_sub_tiles``, every block of the grid)
    and the rest of the s x s square, over ``bh`` heads, in squares of side
    gcd(block, width), so that lopsided tiles count area.  Taken when the
    call is traced, not when it runs; the three-axis grid is not counted."""
    unit = math.gcd(block, width) ** 2
    computed = sum(n * (hi - lo) for b0 in range(0, s, block)
                   for _, n, lo, hi, _ in _sub_tiles(b0, block, s, width,
                                                     causal, block_is_q))
    monitor.stat_add("flash_subtiles_computed_total", bh * computed // unit)
    monitor.stat_add("flash_subtiles_skipped_total",
                     bh * (s * s - computed) // unit)


def _at_block(pl, n, run):
    """``run(i)`` with the second grid index as a Python int: one
    ``pl.when`` branch per block, so every loop bound and slice in ``run``
    is static and Mosaic schedules each block's sub-tiles as straight-line
    code.  (A ``fori_loop`` with bounds computed from ``program_id`` ran
    the same sub-tiles 1.4-2.2 times slower on the v5e: PERF.md, PR 26.)"""
    i = pl.program_id(1)
    for c in range(n):
        pl.when(i == c)(functools.partial(run, c))


def _sub_tiles(b0, size, limit, width, causal, block_is_q):
    """The sub-tiles the two-level nest computes for one block of the grid:
    the block [b0, b0 + size) of one axis of the score matrix against
    pieces of at most ``width`` of the other, resident axis (length
    ``limit``; sq == sk).  Static (start, n, lo, hi, masked) tuples: the
    piece [start, start + n) of the resident axis, and the part [lo, hi) of
    the block, relative to b0, that its visible scores span.

    Under a causal mask a sub-tile is the bounding box of what the mask
    leaves of it: a q block stops at its last row's key and a piece of
    keys skips the rows before it (``block_is_q``); a kv block starts at
    its first column's query and a piece of queries skips the columns
    after it.  ``masked`` says whether the diagonal crosses the box; the
    boxes wholly under it run without the mask."""
    if not causal:
        return [(c, min(width, limit - c), 0, size, False)
                for c in range(0, limit, width)]
    tiles = []
    if block_is_q:
        for c in range(0, b0 + size, width):
            n = min(width, b0 + size - c)
            lo = max(c - b0, 0)
            tiles.append((c, n, lo, size, c + n - 1 > b0 + lo))
    else:
        for r in range(b0, limit, width):
            n = min(width, limit - r)
            hi = min(size, r + n - b0)
            tiles.append((r, n, 0, hi, r < b0 + hi - 1))
    return tiles


def _set_rows(x, lo, hi, new):
    """``x`` with its rows [lo, hi) replaced by ``new`` (static bounds)."""
    parts = ([x[:lo]] if lo else []) + [new] + \
        ([x[hi:]] if hi < x.shape[0] else [])
    return new if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _bias_g_map(bb, hb, h):
    """bh (= b*h + head) → block index into the folded (Bb*Hb, ...) bias."""
    if bb == 1 and hb == 1:
        return lambda bh: 0
    if bb == 1:
        return lambda bh: bh % h       # bias indexed by head only
    if hb == 1:
        return lambda bh: bh // h      # bias indexed by batch only
    return lambda bh: bh


def _mask_tile(s, bias_ref, qs_ref, ks_ref):
    """Apply bias/segment tiles to a (bq, bk) score tile."""
    if bias_ref is not None:
        s = s + bias_ref[0].astype(jnp.float32)
    if qs_ref is not None:
        s = jnp.where(qs_ref[0] == ks_ref[0], s, _NEG_INF)
    return s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(*args, scale, causal, block_k, block_q, n_kb, off,
                has_bias, has_segs, sk, tail_k):
    from jax.experimental import pallas as pl

    n_in = 3 + (1 if has_bias else 0) + (2 if has_segs else 0)
    q_ref, k_ref, v_ref = args[:3]
    i = 3
    bias_ref = None
    qs_ref = ks_ref = None
    if has_bias:
        bias_ref = args[i]
        i += 1
    if has_segs:
        qs_ref, ks_ref = args[i], args[i + 1]
        i += 2
    o_ref, lse_ref = args[n_in], args[n_in + 1]
    m_scr, l_scr, acc_scr = args[n_in + 2:]

    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal (end-aligned): kv blocks strictly beyond the shifted diagonal
    # contribute nothing
    needed = True
    if causal:
        needed = kb * jnp.int32(block_k) < \
            (qi + 1) * jnp.int32(block_q) + jnp.int32(off)

    @pl.when(needed)
    def _compute():
        q = q_ref[0]                                   # (bq, d) input dtype
        k = k_ref[0]                                   # (bk, d)
        v = v_ref[0]
        # MXU at input rate (bf16 on chip), f32 accumulation; scale applied
        # to the f32 product
        s = _dot_nt(q, k) * scale                      # (bq, bk) f32
        s = _mask_tile(s, bias_ref, qs_ref, ks_ref)
        if causal or tail_k:
            k_idx = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
        if tail_k:
            # the last kv block overruns sk: out-of-range key columns
            # score -inf (exp to 0) and their value rows are zeroed so
            # padding garbage never reaches the p·v accumulate
            s = jnp.where(k_idx < sk, s, -jnp.inf)
            v_row = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, v.shape, 0)
            v = jnp.where(v_row < sk, v, 0)
        if causal:
            q_idx = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            s = jnp.where(q_idx + off >= k_idx, s, -jnp.inf)
        m_prev = m_scr[...]                            # (bq, 1)
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(jnp.isfinite(m_new), p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        alpha = jnp.where(jnp.isfinite(m_prev), alpha, 0.0)
        m_scr[...] = m_new
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(kb == n_kb - 1)
    def _finish():
        l = l_scr[...]
        out = acc_scr[...] / jnp.maximum(l, 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)
        # logsumexp residual; rows with zero mass get -inf (p rebuild → 0)
        lse_ref[0] = jnp.where(
            l > 0.0, m_scr[...] + jnp.log(jnp.maximum(l, 1e-30)), -jnp.inf)


def _fwd_resident_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale,
                         causal, block_q, block_k):
    """Forward on the two-level nest: grid (bh, qi), K and V whole in
    VMEM, the kv axis a static loop over sub-tiles that carries (m, l,
    acc) as values; a piece of keys updates the rows from its first key
    on.  sq == sk, so the first piece (key 0) is visible to every row and
    initialises the state: m is finite from then on and ``_fwd_kernel``'s
    guards for rows without a visible key have nothing to catch."""
    from jax.experimental import pallas as pl

    sk = k_ref.shape[1]

    def run(qi):
        r0 = qi * block_q
        for c, n, lo, hi, masked in _sub_tiles(r0, block_q, sk, block_k,
                                               causal, True):
            rows = slice(lo, hi)
            v = v_ref[0, c:c + n, :]
            s = _dot_nt(q_ref[0, rows, :], k_ref[0, c:c + n, :]) * scale
            if masked:
                s = _causal_mask(s, r0 + lo, c)
            m_new = jnp.max(s, axis=1, keepdims=True)
            if c:
                m_prev = m[rows]
                m_new = jnp.maximum(m_prev, m_new)
                alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = jnp.sum(p, axis=1, keepdims=True)
            pv = jnp.dot(p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
            if c:
                m = _set_rows(m, lo, hi, m_new)
                l = _set_rows(l, lo, hi, alpha * l[rows] + l_new)
                acc = _set_rows(acc, lo, hi, alpha * acc[rows] + pv)
            else:
                m, l, acc = m_new, l_new, pv
        o_ref[0] = (acc / l).astype(o_ref.dtype)
        lse_ref[0] = m + jnp.log(l)

    _at_block(pl, sk // block_q, run)


def _causal_mask(s, row0, col0):
    """-inf where a score's key (col0 + column) is after its query."""
    q_idx = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_idx = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_idx >= k_idx, s, -jnp.inf)


def _rebuild_p_at(q, k, lse, scale, masked, row0, col0):
    """``_rebuild_p`` for a sub-tile of the two-level nest, whose first score
    is query ``row0`` against key ``col0``: no bias, segments or tail, and
    no row without a visible key, so lse is finite and needs no guard."""
    s = _dot_nt(q, k) * scale
    if masked:
        s = _causal_mask(s, row0, col0)
    return jnp.exp(s - lse)


def _mask_specs(pl, b, h, sqb, g_map, block_q, block_k, has_bias, has_segs,
                order):
    """Block specs for (bias?, qseg?, kseg?) under grid order
    'qk' = (bh, qi, kb) or 'kq' = (bh, kb, qi)."""
    specs = []
    if order == "qk":
        pick = lambda f: (lambda bh, qi, kb: f(bh, qi, kb))
    else:
        pick = lambda f: (lambda bh, kb, qi: f(bh, qi, kb))
    if has_bias:
        bq_b = block_q if sqb > 1 else 1
        specs.append(pl.BlockSpec(
            (1, bq_b, block_k),
            pick(lambda bh, qi, kb: (g_map(bh), qi if sqb > 1 else 0, kb))))
    if has_segs:
        specs.append(pl.BlockSpec(
            (1, block_q, 1), pick(lambda bh, qi, kb: (bh // h, qi, 0))))
        specs.append(pl.BlockSpec(
            (1, 1, block_k), pick(lambda bh, qi, kb: (bh // h, 0, kb))))
    return specs


def _mask_inputs(bias, qseg, kseg):
    ins = []
    if bias is not None:
        bb, hb, sqb, sk = bias.shape
        ins.append(bias.reshape(bb * hb, sqb, sk))
    if qseg is not None:
        ins.append(qseg[:, :, None])
        ins.append(kseg[:, None, :])
    return ins


def _fold(x, b, h):
    """(B, S, H, D) → (B*H, S, D) — the kernels' tiling layout."""
    s, d = x.shape[1], x.shape[3]
    return jnp.einsum("bshd->bhsd", x).reshape(b * h, s, d)


def _unfold(x, b, h):
    """(B*H, S, D) → (B, S, H, D)."""
    s, d = x.shape[1], x.shape[2]
    return jnp.einsum("bhsd->bshd", x.reshape(b, h, s, d))


# The model calls the kernels once a layer, and a two-level kernel is a
# straight-line loop of sub-tiles in every ``pl.when`` branch: seconds of
# Python to trace, which a step of 24 layers would pay 24 times at every
# start.  The nest's ``pallas_call``s therefore go through jax's trace
# cache (one trace per shape, dtype and tile); ``inline`` leaves the
# caller's jaxpr what a direct call would have left.  ``pallas_call`` is an
# argument, and so part of the cache's key: ``framework.analysis`` swaps
# ``pl.pallas_call`` for a recorder, whose trace must not be found again.
_traced_once = functools.partial(jax.jit, inline=True)


@functools.partial(_traced_once, static_argnums=(3, 4, 5, 6, 7, 8))
def _fwd_resident_call(qt, kt, vt, scale, causal, block_q, block_k,
                       interpret, pallas_call):
    """The forward's ``pallas_call`` on the two-level nest: (out, lse)."""
    from jax.experimental import pallas as pl

    bh, sq, d = qt.shape
    sk = kt.shape[1]
    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0))
    kv_spec = pl.BlockSpec((1, sk, d), lambda bh, qi: (bh, 0, 0))
    with no_x64():
        return pallas_call(
            functools.partial(_fwd_resident_kernel, scale=scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k),
            grid=(bh, sq // block_q),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[q_spec,
                       pl.BlockSpec((1, block_q, 1),
                                    lambda bh, qi: (bh, qi, 0))],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, d), qt.dtype),
                jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
            ],
            name="flash_fwd",
            interpret=interpret,
        )(qt, kt, vt)


def _flash_fwd(q, k, v, bias, qseg, kseg, scale, causal):
    """Returns (out (B,S,H,D), lse (B*H, Sq, 1) float32)."""
    b, sq, h, d = q.shape
    out_f, lse = _flash_fwd_folded(_fold(q, b, h), _fold(k, b, h),
                                   _fold(v, b, h), bias, qseg, kseg,
                                   scale, causal, h)
    return _unfold(out_f, b, h), lse


def _flash_fwd_folded(qt, kt, vt, bias, qseg, kseg, scale, causal, h):
    """Core forward on pre-folded (B*H, S, D) operands.

    Returns (out (B*H, Sq, D), lse (B*H, Sq, 1) f32).  Folding is split
    out so the custom-vjp can keep the folded operands as residuals: the
    backward kernels want exactly this layout, and re-deriving it from
    (B,S,H,D) residuals cost ~5 ms/step of pure HBM copies on a GPT-2
    345M profile taken before PR 1 (not re-measured since).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = qt.shape
    b = bh // h
    sk = kt.shape[1]
    has_bias = bias is not None
    has_segs = qseg is not None
    block_q, block_k = _blocks_for(sq, sk, d, qt.dtype, causal,
                                   has_bias or has_segs)
    n_qb = -(-sq // block_q)
    n_kb = -(-sk // block_k)
    if _two_level(sq, sk, d, qt.dtype, block_q, block_k,
                  has_bias or has_segs):
        _count_subtiles(bh, sq, block_q, block_k, causal, True)
        return _fwd_resident_call(qt, kt, vt, scale, causal, block_q,
                                  block_k, _INTERPRET, pl.pallas_call)
    if has_bias:
        bb, hb, sqb, _ = bias.shape
        g_map = _bias_g_map(bb, hb, h)
    else:
        sqb, g_map = 1, None

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_k=block_k, block_q=block_q, n_kb=n_kb,
                               off=sk - sq, has_bias=has_bias,
                               has_segs=has_segs, sk=sk,
                               tail_k=bool(sk % block_k))
    # Mosaic rejects 64-bit types; the framework enables x64 globally, so
    # pin 32-bit mode for the kernel trace (index maps would emit i64)
    with no_x64():
        out, lse = pl.pallas_call(
            kernel,
            grid=(bh, n_qb, n_kb),
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda bh, qi, kb: (bh, qi, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda bh, qi, kb: (bh, kb, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda bh, qi, kb: (bh, kb, 0)),
            ] + _mask_specs(pl, b, h, sqb, g_map, block_q, block_k,
                            has_bias, has_segs, "qk"),
            out_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda bh, qi, kb: (bh, qi, 0)),
                pl.BlockSpec((1, block_q, 1),
                             lambda bh, qi, kb: (bh, qi, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, d), qt.dtype),
                jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
            name="flash_fwd",
            interpret=_INTERPRET,
        )(qt, kt, vt, *_mask_inputs(bias, qseg, kseg))
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _rebuild_p(q, k, lse, scale, causal, qi, kb, block_q, block_k, off,
               bias_ref=None, qs_ref=None, ks_ref=None, sk=0,
               tail_k=False):
    """Recompute the (bq, bk) probability tile from saved lse.  q/k stay in
    input dtype (bf16 on chip); the product accumulates f32.

    Non-finite-input behavior (changed from the earlier full-tile
    ``isfinite(s)`` guard): only the fully-masked-row case (lse=-inf) is
    zeroed below; a +inf/nan *score* with finite lse — corrupt q/k or a
    user bias carrying +inf/nan — now nan-propagates into p and the
    grads, where the old guard silently zeroed it.  Finite inputs are
    unaffected (masking uses -inf, which exps to 0).  The propagated nan
    is the intended signal: FLAGS_check_nan_inf (or ResilientTrainStep)
    catches it at step granularity — if you are debugging nan grads that
    trace here, inspect the inputs/bias, not this kernel."""
    s = _dot_nt(q, k) * scale
    s = _mask_tile(s, bias_ref, qs_ref, ks_ref)
    if causal or tail_k:
        k_idx = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if tail_k:
        # out-of-range key columns of the tail kv block (callers zero the
        # matching k/v rows, so these columns are 0·q dots, not garbage)
        s = jnp.where(k_idx < sk, s, -jnp.inf)
    if causal:
        q_idx = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(q_idx + off >= k_idx, s, -jnp.inf)
    p = jnp.exp(s - lse)
    # masked entries (s=-inf, lse finite) already exp to 0; the only nan
    # source is a fully-masked row (lse=-inf), so one (bq,1) row guard
    # replaces two full-tile isfinite sweeps
    return jnp.where(jnp.isfinite(lse), p, 0.0)


def _split_bwd_args(args, has_bias, has_segs, n_out):
    """(q, k, v, do, lse, delta, bias?, qs?, ks?) + outs + scratch."""
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = args[:6]
    i = 6
    bias_ref = qs_ref = ks_ref = None
    if has_bias:
        bias_ref = args[i]
        i += 1
    if has_segs:
        qs_ref, ks_ref = args[i], args[i + 1]
        i += 2
    outs = args[i:i + n_out]
    scratch = args[i + n_out:]
    return (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            bias_ref, qs_ref, ks_ref, outs, scratch)


def _tail_zero(x, origin, limit):
    """Zero rows of a (rows, d) tile whose global index >= limit."""
    row = origin + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(row < limit, x, 0)


def _bwd_dq_kernel(*args, scale, causal, block_q, block_k, n_kb, off,
                   has_bias, has_segs, sk, tail_k):
    from jax.experimental import pallas as pl

    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref, qs_ref,
     ks_ref, (dq_ref,), (acc_scr,)) = _split_bwd_args(
        args, has_bias, has_segs, 1)

    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    needed = True
    if causal:
        needed = kb * jnp.int32(block_k) < \
            (qi + 1) * jnp.int32(block_q) + jnp.int32(off)

    @pl.when(needed)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                               # (bq, 1)
        delta = delta_ref[0]
        if tail_k:
            # zero the overrun k/v rows: ds's zero tail columns must
            # contract against zeros, not padding garbage (0·garbage is
            # NaN-poisoned in interpret mode)
            k = _tail_zero(k, kb * block_k, sk)
            v = _tail_zero(v, kb * block_k, sk)
        p = _rebuild_p(q, k, lse, scale, causal, qi, kb, block_q, block_k,
                       off, bias_ref, qs_ref, ks_ref, sk=sk, tail_k=tail_k)
        dp = _dot_nt(do, v)                            # (bq, bk) f32
        ds = p * (dp - delta)
        acc_scr[...] += jnp.dot(ds.astype(k.dtype), k,
                                preferred_element_type=jnp.float32) * scale

    @pl.when(kb == n_kb - 1)
    def _finish():
        dq_ref[0] = acc_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*args, scale, causal, block_q, block_k, n_qb, off,
                    has_bias, has_segs, sq, tail_q, sk, tail_k):
    from jax.experimental import pallas as pl

    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref, qs_ref,
     ks_ref, (dk_ref, dv_ref), (dk_scr, dv_scr)) = _split_bwd_args(
        args, has_bias, has_segs, 2)

    kb = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    needed = True
    if causal:
        needed = kb * jnp.int32(block_k) < \
            (qi + 1) * jnp.int32(block_q) + jnp.int32(off)

    @pl.when(needed)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        if tail_q:
            # the tail q block's overrun rows carry garbage q/do/lse/
            # delta; they are contracted INTO every dk/dv entry here, so
            # both operands of each contraction must be zeroed rows
            q = _tail_zero(q, qi * block_q, sq)
            do = _tail_zero(do, qi * block_q, sq)
        if tail_k:
            k = _tail_zero(k, kb * block_k, sk)
            v = _tail_zero(v, kb * block_k, sk)
        p = _rebuild_p(q, k, lse, scale, causal, qi, kb, block_q, block_k,
                       off, bias_ref, qs_ref, ks_ref, sk=sk, tail_k=tail_k)
        if tail_q:
            # p rows from garbage lse are NaN — zero them explicitly
            p = _tail_zero(p, qi * block_q, sq)
        # contract the query axis: pT@do and dsT@q with bf16 operands
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = _dot_nt(do, v)
        ds = p * (dp - delta)
        if tail_q:
            # garbage delta rows poison ds even where p is 0 (0·NaN)
            ds = _tail_zero(ds, qi * block_q, sq)
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(qi == n_qb - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_resident_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dq_ref, *, scale, causal, block_q, block_k):
    """dq on the two-level nest: grid (bh, qi), K and V whole in VMEM,
    the kv axis a static loop to the diagonal that carries the
    accumulator; a piece of keys adds to the rows from its first key on."""
    from jax.experimental import pallas as pl

    sk = k_ref.shape[1]

    def run(qi):
        r0 = qi * block_q
        for c, n, lo, hi, masked in _sub_tiles(r0, block_q, sk, block_k,
                                               causal, True):
            rows = slice(lo, hi)
            k = k_ref[0, c:c + n, :]
            p = _rebuild_p_at(q_ref[0, rows, :], k, lse_ref[0, rows, :],
                              scale, masked, r0 + lo, c)
            dp = _dot_nt(do_ref[0, rows, :], v_ref[0, c:c + n, :])
            ds = p * (dp - delta_ref[0, rows, :])
            dq = jnp.dot(ds.astype(k.dtype), k,
                         preferred_element_type=jnp.float32)
            acc = _set_rows(acc, lo, hi, acc[rows] + dq) if c else dq
        dq_ref[0] = (acc * scale).astype(dq_ref.dtype)

    _at_block(pl, sk // block_q, run)


def _bwd_dkv_resident_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                             dk_ref, dv_ref, *, scale, causal, block_q,
                             block_k):
    """dk/dv on the two-level nest, the transpose of the above: grid
    (bh, kb), Q, dO, lse and delta whole in VMEM, the q axis a static loop
    that carries both accumulators; a piece of queries adds to the columns
    up to its last query.  The loop runs from the last piece, which sees
    every column of the block and so starts the accumulators, back to the
    diagonal."""
    from jax.experimental import pallas as pl

    sq = q_ref.shape[1]

    def run(kb):
        c0 = kb * block_k
        dk = dv = None
        for r, n, lo, hi, masked in reversed(_sub_tiles(
                c0, block_k, sq, block_q, causal, False)):
            rows, cols = slice(r, r + n), slice(lo, hi)
            q = q_ref[0, rows, :]
            do = do_ref[0, rows, :]
            p = _rebuild_p_at(q, k_ref[0, cols, :], lse_ref[0, rows, :],
                              scale, masked, r, c0 + lo)
            # contract the query axis: pT@do and dsT@q with bf16 operands
            dv_t = jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = _dot_nt(do, v_ref[0, cols, :])
            ds = p * (dp - delta_ref[0, rows, :])
            dk_t = jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if dk is None:
                dk, dv = dk_t, dv_t
            else:
                dk = _set_rows(dk, lo, hi, dk[cols] + dk_t)
                dv = _set_rows(dv, lo, hi, dv[cols] + dv_t)
        dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)

    _at_block(pl, sq // block_k, run)


def _bwd_dbias_kernel(*args, scale, causal, block_q, block_k, n_qb, n_r,
                      off, sq_full, has_segs):
    """ds accumulated over the bias' broadcast extents.

    Grid (g, kb, qi, r): r sweeps the reduced batch*head extent; when the
    bias has no query dim (sq_full=False) qi is reduced as well.  Both
    reduction axes are innermost, so output-block revisits are
    consecutive — accumulate in VMEM, write on the last visit.
    """
    from jax.experimental import pallas as pl

    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref, qs_ref,
     ks_ref, (db_ref,), (db_scr,)) = _split_bwd_args(args, True, has_segs, 1)

    kb = pl.program_id(1)
    qi = pl.program_id(2)
    r = pl.program_id(3)

    first = (r == 0) if sq_full else jnp.logical_and(r == 0, qi == 0)
    last = (r == n_r - 1) if sq_full else \
        jnp.logical_and(r == n_r - 1, qi == n_qb - 1)

    @pl.when(first)
    def _init():
        db_scr[...] = jnp.zeros_like(db_scr)

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]
    delta = delta_ref[0]
    p = _rebuild_p(q, k, lse, scale, causal, qi, kb, block_q, block_k,
                   off, bias_ref, qs_ref, ks_ref)
    dp = _dot_nt(do, v)
    ds = p * (dp - delta)
    if sq_full:
        db_scr[...] += ds
    else:
        db_scr[...] += jnp.sum(ds, axis=0, keepdims=True)

    @pl.when(last)
    def _finish():
        db_ref[0] = db_scr[...].astype(db_ref.dtype)


def _flash_bwd_folded(qt, kt, vt, bias, qseg, kseg, ot, lse, do, scale,
                      causal, h, want_dbias=True):
    """Backward on the pre-folded residuals saved by the forward.

    ``qt/kt/vt/ot`` are (B*H, S, D) — exactly the kernels' layout, so the
    only layout transpose left in the whole backward is folding the
    incoming ``do`` cotangent and unfolding the dq/dk/dv results.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = qt.shape
    b = bh // h
    sk = kt.shape[1]
    has_bias = bias is not None
    has_segs = qseg is not None
    block_q, block_k = _blocks_for(sq, sk, d, qt.dtype, causal,
                                   has_bias or has_segs, direction="bwd")
    n_qb = -(-sq // block_q)
    n_kb = -(-sk // block_k)
    tail_q = bool(sq % block_q)
    tail_k = bool(sk % block_k)
    off = sk - sq

    if has_bias:
        bb, hb, sqb, _ = bias.shape
        g_map = _bias_g_map(bb, hb, h)
    else:
        sqb, g_map = 1, None

    dot = _fold(do, b, h)
    # delta_i = sum_d dO_i · O_i  (softmax-jacobian row term), O(S·D)
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1, keepdims=True)

    # the two-level nest reads a tile of its own for dk/dv (autotune)
    dkv_blocks = _blocks_for(sq, sk, d, qt.dtype, causal,
                             has_bias or has_segs, direction="dkv")
    if all(_two_level(sq, sk, d, qt.dtype, *blocks, has_bias or has_segs)
           for blocks in ((block_q, block_k), dkv_blocks)):
        _count_subtiles(bh, sq, block_q, block_k, causal, True)
        _count_subtiles(bh, sq, dkv_blocks[1], dkv_blocks[0], causal, False)
        dq, dk, dv = _bwd_resident_calls(qt, kt, vt, dot, lse, delta, scale,
                                         causal, (block_q, block_k),
                                         dkv_blocks, _INTERPRET,
                                         pl.pallas_call)
        return _unfold(dq, b, h), _unfold(dk, b, h), _unfold(dv, b, h), None

    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, qi, kb: (bh, qi, 0))
    k_spec = pl.BlockSpec((1, block_k, d), lambda bh, qi, kb: (bh, kb, 0))
    row_spec = pl.BlockSpec((1, block_q, 1), lambda bh, qi, kb: (bh, qi, 0))
    # dkv grid order is (bh, kb, qi)
    q_spec_t = pl.BlockSpec((1, block_q, d), lambda bh, kb, qi: (bh, qi, 0))
    k_spec_t = pl.BlockSpec((1, block_k, d), lambda bh, kb, qi: (bh, kb, 0))
    row_spec_t = pl.BlockSpec((1, block_q, 1),
                              lambda bh, kb, qi: (bh, qi, 0))

    mask_ins = _mask_inputs(bias, qseg, kseg)

    with no_x64():
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k, n_kb=n_kb,
                              off=off, has_bias=has_bias, has_segs=has_segs,
                              sk=sk, tail_k=tail_k),
            grid=(bh, n_qb, n_kb),
            in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec]
            + _mask_specs(pl, b, h, sqb, g_map, block_q, block_k,
                          has_bias, has_segs, "qk"),
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct((bh, sq, d), qt.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            name="flash_bwd_dq",
            interpret=_INTERPRET,
        )(qt, kt, vt, dot, lse, delta, *mask_ins)

        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k, n_qb=n_qb,
                              off=off, has_bias=has_bias, has_segs=has_segs,
                              sq=sq, tail_q=tail_q, sk=sk, tail_k=tail_k),
            grid=(bh, n_kb, n_qb),
            in_specs=[q_spec_t, k_spec_t, k_spec_t, q_spec_t, row_spec_t,
                      row_spec_t]
            + _mask_specs(pl, b, h, sqb, g_map, block_q, block_k,
                          has_bias, has_segs, "kq"),
            out_specs=[k_spec_t, k_spec_t],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sk, d), kt.dtype),
                jax.ShapeDtypeStruct((bh, sk, d), vt.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)],
            name="flash_bwd_dkv",
            interpret=_INTERPRET,
        )(qt, kt, vt, dot, lse, delta, *mask_ins)

        dbias = None
        if has_bias and want_dbias:
            dbias = _dbias_call(pl, pltpu, qt, kt, vt, dot, lse, delta,
                                mask_ins, bias, qseg is not None, b, h, sq,
                                sk, d, block_q, block_k, scale, causal, off)

    return (_unfold(dq, b, h), _unfold(dk, b, h), _unfold(dv, b, h),
            dbias)


@functools.partial(_traced_once, static_argnums=(6, 7, 8, 9, 10, 11))
def _bwd_resident_calls(qt, kt, vt, dot, lse, delta, scale, causal,
                        dq_blocks, dkv_blocks, interpret, pallas_call):
    """The two backward kernels on the two-level nest: (dq, dk, dv), folded.
    ``tile`` blocks an operand along the grid's second axis, ``whole``
    keeps it resident for the (batch, head): its index map ignores that
    axis, so the pipeline fetches it once a row of the grid."""
    from jax.experimental import pallas as pl

    bh, s, d = qt.shape
    tile = lambda rows, cols: pl.BlockSpec((1, rows, cols),
                                           lambda bh, i: (bh, i, 0))
    whole = lambda rows, cols: pl.BlockSpec((1, rows, cols),
                                            lambda bh, i: (bh, 0, 0))
    block_q, block_k = dq_blocks
    with no_x64():
        dq = pallas_call(
            functools.partial(_bwd_dq_resident_kernel, scale=scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k),
            grid=(bh, s // block_q),
            in_specs=[tile(block_q, d), whole(s, d), whole(s, d),
                      tile(block_q, d), tile(block_q, 1), tile(block_q, 1)],
            out_specs=tile(block_q, d),
            out_shape=jax.ShapeDtypeStruct((bh, s, d), qt.dtype),
            name="flash_bwd_dq",
            interpret=interpret,
        )(qt, kt, vt, dot, lse, delta)
    block_q, block_k = dkv_blocks
    with no_x64():
        dk, dv = pallas_call(
            functools.partial(_bwd_dkv_resident_kernel, scale=scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k),
            grid=(bh, s // block_k),
            in_specs=[whole(s, d), tile(block_k, d), tile(block_k, d),
                      whole(s, d), whole(s, 1), whole(s, 1)],
            out_specs=[tile(block_k, d), tile(block_k, d)],
            out_shape=[
                jax.ShapeDtypeStruct((bh, s, d), kt.dtype),
                jax.ShapeDtypeStruct((bh, s, d), vt.dtype),
            ],
            name="flash_bwd_dkv",
            interpret=interpret,
        )(qt, kt, vt, dot, lse, delta)
    return dq, dk, dv


def _dbias_call(pl, pltpu, qt, kt, vt, dot, lse, delta, mask_ins, bias,
                has_segs, b, h, sq, sk, d, block_q, block_k, scale, causal,
                off):
    """ds reduced over the bias' broadcast dims.  bh = g·mg + r·mr maps the
    (bias-extent, reduction-extent) grid coordinates back to batch*head."""
    bb, hb, sqb, _ = bias.shape
    sq_full = sqb > 1
    n_qb = sq // block_q
    n_kb = sk // block_k
    if bb == 1 and hb == 1:
        mg, mr, n_r = 0, 1, b * h
    elif bb == 1:
        mg, mr, n_r = 1, h, b          # g = head, reduce over batch
    elif hb == 1:
        mg, mr, n_r = h, 1, h          # g = batch, reduce over heads
    else:
        mg, mr, n_r = 1, 0, 1

    bh_of = lambda g, r: g * mg + r * mr
    dspec = lambda f: pl.BlockSpec((1, block_q, d), f)
    kspec = lambda f: pl.BlockSpec((1, block_k, d), f)
    rspec = lambda f: pl.BlockSpec((1, block_q, 1), f)
    in_specs = [
        dspec(lambda g, kb, qi, r: (bh_of(g, r), qi, 0)),       # q
        kspec(lambda g, kb, qi, r: (bh_of(g, r), kb, 0)),       # k
        kspec(lambda g, kb, qi, r: (bh_of(g, r), kb, 0)),       # v
        dspec(lambda g, kb, qi, r: (bh_of(g, r), qi, 0)),       # do
        rspec(lambda g, kb, qi, r: (bh_of(g, r), qi, 0)),       # lse
        rspec(lambda g, kb, qi, r: (bh_of(g, r), qi, 0)),       # delta
        pl.BlockSpec((1, block_q if sq_full else 1, block_k),
                     lambda g, kb, qi, r: (g, qi if sq_full else 0, kb)),
    ]
    if has_segs:
        in_specs.append(pl.BlockSpec(
            (1, block_q, 1),
            lambda g, kb, qi, r: (bh_of(g, r) // h, qi, 0)))
        in_specs.append(pl.BlockSpec(
            (1, 1, block_k),
            lambda g, kb, qi, r: (bh_of(g, r) // h, 0, kb)))

    bq_b = block_q if sq_full else 1
    out_spec = pl.BlockSpec(
        (1, bq_b, block_k),
        lambda g, kb, qi, r: (g, qi if sq_full else 0, kb))

    db = pl.pallas_call(
        functools.partial(_bwd_dbias_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_qb=n_qb,
                          n_r=n_r, off=off, sq_full=sq_full,
                          has_segs=has_segs),
        grid=(bb * hb, n_kb, n_qb, n_r),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((bb * hb, sqb, sk), bias.dtype),
        scratch_shapes=[pltpu.VMEM((bq_b, block_k), jnp.float32)],
        name="flash_bwd_dbias",
        interpret=_INTERPRET,
    )(qt, kt, vt, dot, lse, delta, *mask_ins)
    return db.reshape(bb, hb, sqb, sk)


def _xla_reference(q, k, v, scale, causal, bias=None, q_seg=None,
                   kv_seg=None):
    qh = jnp.einsum("bshd->bhsd", q)
    kh = jnp.einsum("bshd->bhsd", k)
    vh = jnp.einsum("bshd->bhsd", v)
    s = jnp.einsum("bhsd,bhtd->bhst", qh, kh) * scale
    if bias is not None:
        s = s + bias
    if q_seg is not None:
        seg = q_seg[:, None, :, None] == kv_seg[:, None, None, :]
        s = jnp.where(seg, s, -1e30)
    if causal:
        sq_, sk_ = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq_, sk_), dtype=bool), k=sk_ - sq_)
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    o = jnp.einsum("bhst,bhtd->bhsd", p, vh)
    return jnp.einsum("bhsd->bshd", o)


# ---------------------------------------------------------------------------
# custom-vjp wrapper + public dispatcher
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _flash(q, k, v, bias, qseg, kseg, causal, scale):
    out, _ = _flash_fwd(q, k, v, bias, qseg, kseg, scale, causal)
    return out


def _fa_fwd(q, k, v, bias, qseg, kseg, causal, scale):
    # fold ONCE; the folded operands + folded output are the residuals, so
    # the backward kernels read them directly instead of re-deriving the
    # (B*H, S, D) layout from (B,S,H,D) (a measured ~5 ms/step of copies
    # on GPT-2 345M).  The head count is NOT a residual: the backward
    # recovers it statically from the cotangent's (B, Sq, H, D) shape.
    # Memory tradeoff: the folded out_f residual lives alongside the
    # unfolded output until the backward consumes it — one extra
    # activation-sized buffer per attention layer.  Under jax.checkpoint
    # (remat, the near-capacity configuration) residuals are recomputed,
    # not stored, so the cost applies only to no-remat runs with HBM to
    # spare — exactly when the 5 ms matters more than the buffer.
    b, sq, h, d = q.shape
    qt, kt, vt = _fold(q, b, h), _fold(k, b, h), _fold(v, b, h)
    out_f, lse = _flash_fwd_folded(qt, kt, vt, bias, qseg, kseg, scale,
                                   causal, h)
    return _unfold(out_f, b, h), (qt, kt, vt, bias, qseg, kseg, out_f, lse)


def _fa_bwd(causal, scale, res, g):
    qt, kt, vt, bias, qseg, kseg, ot, lse = res
    dq, dk, dv, dbias = _flash_bwd_folded(qt, kt, vt, bias, qseg, kseg,
                                          ot, lse, g, scale, causal,
                                          g.shape[2])
    dseg = None if qseg is None else jnp.zeros_like(qseg)
    dkseg = None if kseg is None else jnp.zeros_like(kseg)
    return (dq, dk, dv, dbias, dseg, dkseg)


_flash.defvjp(_fa_fwd, _fa_bwd)


# bias-nondiff variant: identical forward, but the backward skips the
# dbias reduction kernel entirely.  Under jit the diff'able variant's
# unused dbias would be DCE'd anyway, but the eager tape executes bwd
# rules eagerly — padding masks (never trained) must not pay the extra
# O(S²)-tile sweep there.
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _flash_nodbias(q, k, v, bias, qseg, kseg, causal, scale):
    out, _ = _flash_fwd(q, k, v, bias, qseg, kseg, scale, causal)
    return out


def _fa_bwd_nodbias(causal, scale, res, g):
    qt, kt, vt, bias, qseg, kseg, ot, lse = res
    dq, dk, dv, _ = _flash_bwd_folded(qt, kt, vt, bias, qseg, kseg, ot,
                                      lse, g, scale, causal, g.shape[2],
                                      want_dbias=False)
    dbias = None if bias is None else jnp.zeros_like(bias)
    dseg = None if qseg is None else jnp.zeros_like(qseg)
    dkseg = None if kseg is None else jnp.zeros_like(kseg)
    return (dq, dk, dv, dbias, dseg, dkseg)


_flash_nodbias.defvjp(_fa_fwd, _fa_bwd_nodbias)


def flash_attention(q, k, v, causal=False, scale=None, bias=None,
                    q_segment_ids=None, kv_segment_ids=None,
                    bias_grad=True):
    """Blockwise attention with optional additive bias / segment masking.

    ``bias``: float additive mask broadcastable to (B, H, Sq, Sk) (each
    leading dim full or 1; key dim full), or a bool mask of the same
    shapes (True = attend).  ``*_segment_ids``: (B, S) int ids; q·k pairs
    with different ids are masked (packed-sequence attention).
    ``bias_grad=False`` promises the bias cotangent is unneeded (padding
    masks): its gradient is returned as zeros and the dbias kernel never
    runs — callers with learned biases (e.g. relative-position) keep the
    default.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    b, sq, h, _ = q.shape
    sk = k.shape[1]
    if bias is not None:
        canon = _canon_bias_shape(bias.shape, b, h, sq, sk)
        if canon is None:
            raise ValueError(
                f"flash_attention: bias shape {tuple(bias.shape)} is not "
                f"broadcastable-canonical for q{tuple(q.shape)}/"
                f"k{tuple(k.shape)}")
        if bias.dtype == jnp.bool_:
            bias = jnp.where(bias, 0.0, _NEG_INF).astype(jnp.float32)
        elif bias.dtype != jnp.bfloat16:
            # Mosaic rejects 64-bit inputs (x64 is on framework-wide) and
            # _mask_tile computes in f32 anyway
            bias = bias.astype(jnp.float32)
        bias = bias.reshape(canon)
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("flash_attention: pass both segment-id arrays or "
                         "neither")
    if q_segment_ids is not None:
        # float32 internally: custom_vjp cotangents for int arrays are
        # awkward (float0); exact for ids < 2^24
        q_segment_ids = q_segment_ids.astype(jnp.float32)
        kv_segment_ids = kv_segment_ids.astype(jnp.float32)
    impl = _flash if bias_grad else _flash_nodbias
    return impl(q, k, v, bias, q_segment_ids, kv_segment_ids, bool(causal),
                float(scale))

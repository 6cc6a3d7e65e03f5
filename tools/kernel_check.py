#!/usr/bin/env python
"""Compile every Pallas kernel with Mosaic on the attached TPU and compare
it with its XLA reference — the check behind ``flash_blocks.json`` ("holds
only tiles that compiled") and behind any change to a kernel body.

    python tools/kernel_check.py            # table on stdout, JSON under
                                            # chiprun_out/kernel_check.json

Per kernel: compiled yes/no and the max abs error of each output against
the module's own reference.  What it runs, with what the repo already has:

* flash forward + dq/dk/dv at every key of ``flash_blocks.json`` (its own
  S, d, mask class and tile), against ``flash_attention._xla_reference``
  evaluated in float32 at the highest matmul precision — the ground truth;
  the same reference in bf16 is measured beside it (``xla_bf16_err``), so
  the table shows what XLA's own bf16 path loses on the same input.  An
  output passes when its max abs error is within ``verify.SCALE_TOL`` of
  the largest reference value;
* the benchmark's own calls, (8, 16, 1024, 64) causal and (32, 12, 512,
  64) non-causal bf16, the same way: the fused backward
  (``flash_bwd_dq_dkv``), with the split dq and dk/dv pair's error beside
  it (``split_pair_err``);
* the two-level nest at the edge of its VMEM budget, S=1024 d=128 float32,
  where the fused backward's accumulator does not fit and the pair runs;
* ``verify.check_flash_candidate`` (compiled vs interpret vs reference on
  ``verify.boundary_corpus``: the non-divisible tail paths and a square of
  several tiles a side) at the largest and the smallest tile and at the
  tiles the table holds for the benchmark's call;
* the bias (padding, and full with its dbias kernel) and segment-id paths
  at S=2048;
* ``fused_ce`` at (N=8192, H=1024, V=50304): loss, dh, dW;
* ``fused_adam`` on one 1024x4096 leaf;
* ``ring_quant._kernel_quant`` at int8 and int4 on (4096, 1024) rows
  against ``wire.quantize_rows_traced``;
* ``grouped_matmul`` at the hybrid cell's shapes (a buffer of 34816 rows
  for 4096 tokens, 8 experts of 1024 x 2688) and one skewed load (838
  rows down to none): the gather plain and gated, the grouped matmul's
  four variants, both weight gradients and the scatter, against float32
  matmuls a group at the highest precision, over the tiles the load
  fills;
* the SwiGLU experts on those kernels at the Ling cell's shapes (8192
  tokens of 2560, 8 experts of 2560 -> 768 -> 2560, the token copy by
  blocks of columns), ``moe._sorted_swiglu`` forward and backward (the
  ``swiglu`` prologue, the ``dswiglu`` epilogue) under an even load of
  about 128 rows an expert and a skewed one (1100 rows down to none),
  against the dense mask in float32 at the highest precision: y, dx,
  dW13, dW2 and the gates' gradient;
* ``kda_carry`` at the Ling cell's shape (1 sequence, 128 chunks of 64,
  4 heads, a float32 state of 128 x 128): the state pass and its backward
  (``kda_carry.state_pass``) against the same step in a ``lax.scan``
  (``kda_carry.scan_pass``) at the highest precision and jax's transpose
  of it, each output's worst difference relative to its largest value,
  and the device time of each side's forward and backward (a profiler
  trace of a few calls: the ``XLA Modules`` events on the chip, their
  mean).

``python tools/kernel_check.py grouped`` runs the rows of one family
(``flash``, ``tail``, ``other``, ``grouped``, ``kda_carry``) alone.

Exits non-zero when a kernel does not compile or leaves its tolerance.
Needs the TPU: one process, no children.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ROWS = []
CE_SHAPE = (8192, 1024, 50304)       # GPT-2 345M head at batch 8 x 1024
ADAM_SHAPE = (1024, 4096)
QUANT_SHAPE = (4096, 1024)
GROUPED_SHAPE = (34816, 8, 1024, 2688)   # rows, experts, latent, inner
GROUPED_TOKENS = 4096
GROUPED_LOAD = (838, 400, 200, 100, 50, 20, 5, 0)
SWIGLU_SHAPE = (8192, 8, 2560, 768)      # tokens, experts, hidden, inner
SWIGLU_TOP_K = 8
SWIGLU_SKEWED = (1100, 400, 200, 100, 50, 20, 5, 0)
KDA_CARRY_SHAPE = (1, 128, 4, 128, 128)   # batch, chunks, heads, d_k, d_v
KDA_CHUNK = 64
# the two float32 state passes, the kernels' and XLA's scan, each at the
# highest precision; both are float32 to the last places
KDA_CARRY_TOL = 2e-5


def check(name, run_kernel, run_reference, labels, tol=None, run_also=None):
    """One row: compile + run the kernel, then hold each output's max abs
    error to ``tol`` (one share for all outputs or one per label; default
    ``verify.SCALE_TOL``) of the reference's largest value.  ``run_also``
    maps a column (``xla_bf16_err``, ``split_pair_err``) to another
    implementation measured against the same reference, for
    information."""
    from paddle_tpu.ops.pallas import verify
    t0 = time.perf_counter()
    row = {"kernel": name, "compiled": False, "within_tolerance": False}
    try:
        got = run_kernel()
        np.asarray(got[0])             # the kernel ran to the end
        row["compiled"] = True
        want = run_reference()
        errs = verify.max_errors(got, want)
        tols = tol if isinstance(tol, (list, tuple)) else \
            [verify.SCALE_TOL if tol is None else tol] * len(errs)
        row["max_abs_err"] = {lb: float(f"{e:.3g}")
                              for lb, (e, _) in zip(labels, errs)}
        row["ref_max_abs"] = {lb: float(f"{m:.3g}")
                              for lb, (_, m) in zip(labels, errs)}
        row["within_tolerance"] = all(
            e <= t * m for (e, m), t in zip(errs, tols))
        for column, run in (run_also or {}).items():
            row[column] = {
                lb: float(f"{e:.3g}") for lb, (e, _) in
                zip(labels, verify.max_errors(run(), want))}
    except Exception as e:             # noqa: BLE001 — the row reports it
        traceback.print_exc()
        row["error"] = " ".join(str(e).split())[:400]
    row["s"] = round(time.perf_counter() - t0, 1)
    ROWS.append(row)
    print(json.dumps(row), flush=True)


def flash_rows():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import autotune
    from paddle_tpu.ops.pallas import flash_attention as fa

    def case(name, sq, sk, d, causal, bias_shape=None, segs=False,
             bias_grad=False, b=1, h=2, dtype=jnp.bfloat16,
             split_beside=False):
        rng = np.random.default_rng(sq + sk + d)
        q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, d)),
                               jnp.bfloat16) for s in (sq, sk, sk))
        bias = None if bias_shape is None else jnp.asarray(
            rng.standard_normal(bias_shape), jnp.float32)
        seg = None if not segs else jnp.asarray(
            np.sort(rng.integers(0, 4, size=(b, sq)), axis=1), jnp.int32)
        scale = 1.0 / float(np.sqrt(d))
        diff = (0, 1, 2, 3) if bias_grad else (0, 1, 2)
        # no bias: the default (differentiable) variant the models call
        bias_grad = bias_grad or bias is None

        def grads(attn, dtype=dtype):
            return jax.jit(jax.value_and_grad(
                lambda a, b_, c, m: (attn(a, b_, c, m).astype(jnp.float32)
                                     ** 2).sum(), argnums=diff))(
                q.astype(dtype), k.astype(dtype), v.astype(dtype), bias)

        def reference(a, b_, c, m):
            return fa._xla_reference(a, b_, c, scale, causal, bias=m,
                                     q_seg=seg, kv_seg=seg)

        def truth():
            with jax.default_matmul_precision("highest"):
                return grads(reference, jnp.float32)

        def flash(a, b_, c, m):
            return fa.flash_attention(
                a, b_, c, causal=causal, scale=scale, bias=m,
                bias_grad=bias_grad, q_segment_ids=seg, kv_segment_ids=seg)

        def split_pair():
            """The nest's dq and dk/dv kernels, as where the fused
            backward's accumulator does not fit."""
            fits = fa._fused_bwd_fits
            fa._fused_bwd_fits = lambda *_: False
            try:
                return grads(lambda *a: flash(*a))
            finally:
                fa._fused_bwd_fits = fits

        also = {"xla_bf16_err": lambda: grads(reference)}
        if split_beside:
            also["split_pair_err"] = split_pair
        check(name, lambda: grads(flash), truth,
              ("loss", "dq", "dk", "dv", "dbias"), run_also=also)

    table = autotune._load()
    for key in sorted(k for k in table
                      if not k.endswith((":bwd", ":dkv", ":fused"))):
        shape, d, _, mask, biased = key.split(":")
        sq, sk = (int(x) for x in shape.split("x"))
        tiles = {"fwd": autotune._entry_blocks(table[key])}
        for direction in ("bwd", "dkv", "fused"):
            if f"{key}:{direction}" in table:
                tiles[direction] = autotune._entry_blocks(
                    table[f"{key}:{direction}"])
        case(f"flash {key} tiles {tiles}", sq, sk, int(d[1:]),
             mask == "causal",
             bias_shape=(1, 1, 1, sk) if biased == "bias" else None)
    # the benchmark's GPT-2 cells call the kernels at exactly this shape,
    # the fused backward, with the split dq and dk/dv pair's error beside
    case("flash GPT-2 345M cell shape (8,16,1024,64) causal", 1024, 1024, 64,
         True, b=8, h=16, split_beside=True)
    # and bert_base.pretrain_b32_s512 at this one: no mask, the nest with
    # no diagonal, at the table's 512x512:d64:full tiles
    case("flash BERT-base cell shape (32,12,512,64) non-causal", 512, 512,
         64, False, b=32, h=12, split_beside=True)
    # the most the two-level nest keeps resident (its VMEM budget's edge)
    case("flash two-level nest at its budget: S=1024 d128 float32 causal",
         1024, 1024, 128, True, dtype=jnp.float32)
    case("flash padding bias (B,1,1,S) S=2048 d64", 2048, 2048, 64, False,
         bias_shape=(2, 1, 1, 2048), b=2)
    case("flash full bias (B,H,S,S) + dbias S=2048 d64 causal", 2048, 2048,
         64, True, bias_shape=(1, 2, 2048, 2048), bias_grad=True)
    case("flash segment ids S=2048 d64 causal", 2048, 2048, 64, True,
         segs=True, b=2)


def tail_rows():
    from paddle_tpu.framework import monitor
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.ops.pallas import autotune, verify

    # the largest and the smallest tile, and the tiles the table holds for
    # the shape the benchmark's cells run (forward, dq, dk/dv and the fused
    # backward)
    tiles = [(1024, 1024), (128, 128)]
    for direction in ("fwd", "bwd", "dkv", "fused"):
        tile = autotune.lookup(1024, 1024, 64, "bfloat16", True, False,
                               direction=direction)
        if tile and tile not in tiles:
            tiles.append(tile)
    set_flags({"pallas_verify": True})
    try:
        for bq, bk in tiles:
            for causal in (False, True):
                t0 = time.perf_counter()
                before = monitor.get_stat("pallas_verify_errors_total")
                fails = verify.check_flash_candidate(bq, bk, d=64,
                                                     causal=causal)
                faults = monitor.get_stat("pallas_verify_errors_total") \
                    - before
                row = {"kernel": f"flash tails corpus({bq},{bk}) d64 "
                                 f"causal={causal} (compiled vs interpret "
                                 f"vs reference)",
                       "compiled": not faults,
                       "within_tolerance": not fails,
                       "failures": fails,
                       "s": round(time.perf_counter() - t0, 1)}
                ROWS.append(row)
                print(json.dumps(row), flush=True)
    finally:
        set_flags({"pallas_verify": False})


def other_rows():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed.wire import (_unpack_nibbles,
                                             quantize_rows_traced)
    from paddle_tpu.ops.pallas import fused_adam, fused_ce, ring_quant

    rng = np.random.default_rng(0)
    n, hd, v = CE_SHAPE
    h = jnp.asarray(rng.standard_normal((n, hd)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((v, hd)) * 0.02, jnp.bfloat16)
    lab = jnp.asarray(rng.integers(0, v, size=(n,)), jnp.int32)

    def ce(fn):
        loss = jax.jit(fn)(h, w, lab)
        dh, dw = jax.jit(jax.grad(lambda a, b: fn(a, b, lab).sum(),
                                  argnums=(0, 1)))(h, w)
        return loss, dh, dw

    check(f"fused_ce fwd + dh + dw N={n} H={hd} V={v}",
          lambda: ce(fused_ce.fused_linear_cross_entropy),
          lambda: ce(fused_ce.xla_reference),
          ("loss", "dh", "dw"))

    p, m, s = (jnp.asarray(rng.standard_normal(ADAM_SHAPE), jnp.float32)
               for _ in range(3))
    g = jnp.asarray(rng.standard_normal(ADAM_SHAPE), jnp.bfloat16)
    hyper = dict(lr_t=1e-3, beta1=0.9, beta2=0.999, eps=1e-8, wd_lr=1e-5)
    check(f"fused_adam {ADAM_SHAPE}",
          lambda: jax.jit(lambda *a: fused_adam.fused_adam_update(
              *a, **hyper))(p, g, m, jnp.abs(s)),
          lambda: jax.jit(lambda *a: fused_adam.xla_reference(
              *a, **hyper))(p, g, m, jnp.abs(s)),
          ("p", "m", "v"), tol=1e-5)

    rows = jnp.asarray(rng.standard_normal(QUANT_SHAPE), jnp.float32)

    def traced(wire):
        bufs = quantize_rows_traced(rows, wire)
        if wire == "int4":            # the kernel's q is the unpacked one
            return _unpack_nibbles(bufs[0], rows.shape[-1], jnp), bufs[1]
        return bufs

    for wire, qmax in (("int8", 127.0), ("int4", 7.0)):
        # q may differ by one step where x/scale lands on a rounding tie
        # computed one ulp apart; the scales must agree
        check(f"ring_quant {wire} {QUANT_SHAPE}",
              lambda: jax.jit(lambda x: ring_quant._kernel_quant(x, qmax))(
                  rows),
              lambda: jax.jit(lambda: traced(wire))(),
              ("q", "scale"), tol=[1.0 / qmax, 1e-6])


def grouped_rows():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import grouped_matmul as gmm

    rows, held, lat, mid = GROUPED_SHAPE
    tokens = GROUPED_TOKENS
    tile = gmm.TILE_ROWS
    rng = np.random.default_rng(0)
    tiles = [max(1, -(-n // tile)) for n in GROUPED_LOAD]
    used = sum(tiles)
    group = np.repeat(np.arange(held), tiles)
    tile_group = jnp.asarray(
        np.r_[group, np.full(rows // tile - used, held - 1)], jnp.int32)
    tiles_used = jnp.asarray([used], jnp.int32)
    # a row is real where it lies within its group's load; a row of
    # padding carries the token ``tokens``, a gate of zero, and zero in
    # the operand the weight gradient contracts over
    real = np.concatenate([np.arange(t * tile) < n
                           for t, n in zip(tiles, GROUPED_LOAD)])
    real = np.r_[real, np.zeros(rows - used * tile, bool)]
    row_token = jnp.asarray(np.where(
        real, rng.integers(0, tokens, rows), tokens), jnp.int32)
    gate = jnp.asarray(np.where(real, rng.random(rows) + 0.5, 0)[:, None],
                       jnp.float32)
    real = real[:, None]
    bf16 = jnp.bfloat16
    src = jnp.asarray(rng.standard_normal((tokens, lat)), jnp.float32)
    r = jnp.asarray(np.abs(rng.standard_normal((rows, mid))), bf16)
    dy = jnp.asarray(np.where(real, rng.standard_normal((rows, lat)), 0),
                     bf16)
    w1 = jnp.asarray(rng.standard_normal((held, lat, mid)) * 0.03, bf16)
    w2 = jnp.asarray(rng.standard_normal((held, mid, lat)) * 0.03, bf16)
    in_use = jnp.asarray(np.arange(rows)[:, None] < used * tile)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)

    def kernels(src, r, dy, w1, w2):
        x = gmm.gather_rows(src, row_token, tiles_used, out_dtype=bf16)
        rel = gmm.group_rows(x, w1, tile_group, tiles_used, epilogue="relu")
        y2 = gmm.group_rows(r, w2, tile_group, tiles_used, prologue="square",
                            out_dtype=jnp.float32)
        dy2, dgate = gmm.gather_rows(src, row_token, tiles_used, gate=gate,
                                     other=y2, out_dtype=bf16)
        dpre = gmm.group_rows(dy, w2, tile_group, tiles_used,
                              transpose_w=True, epilogue="times_2m", m=r)
        dx = gmm.group_rows(r, w1, tile_group, tiles_used, transpose_w=True,
                            out_dtype=jnp.float32)
        dw2 = gmm.group_weights(r, dy, tile_group, tiles_used, held,
                                prologue="square")
        dw1 = gmm.group_weights(dy, r, tile_group, tiles_used, held)
        y = gmm.scatter_rows(y2, gate, row_token, tiles_used, tokens)
        return tuple(jnp.where(in_use, t, 0)
                     for t in (x, rel, y2, dy2, dgate, dpre, dx)) \
            + (dw2, dw1, y)

    def reference(src, r, dy, w1, w2):
        dot = functools.partial(jnp.matmul,
                                precision=jax.lax.Precision.HIGHEST)
        of_row = jnp.repeat(tile_group, tile)[:, None]
        x = jnp.where(in_use, src[jnp.minimum(row_token, tokens - 1)], 0)
        xs, rs, dys = f32(x.astype(bf16)), f32(r), f32(dy)
        rel = y2 = dpre = dx = 0.0
        dw2, dw1 = [], []
        for e in range(held):             # one dense product a group
            mine = jnp.logical_and(of_row == e, in_use)
            a1, a2 = f32(w1[e]), f32(w2[e])
            rel += jnp.where(mine, jax.nn.relu(dot(xs, a1)), 0)
            dx += jnp.where(mine, dot(rs, a1.T), 0)
            y2 += jnp.where(mine, dot(rs * rs, a2), 0)
            dpre += jnp.where(mine, dot(dys, a2.T) * 2 * rs, 0)
            dw2.append(dot(jnp.where(mine, rs * rs, 0).T, dys))
            dw1.append(dot(jnp.where(mine, dys, 0).T, rs))
        y = jnp.zeros((tokens, lat), jnp.float32).at[row_token].add(
            y2 * gate, mode="drop")
        return (x, rel, y2, x * gate, jnp.sum(x * y2, 1, keepdims=True),
                dpre, dx, jnp.stack(dw2), jnp.stack(dw1), y)

    check(f"grouped_matmul rows={rows} experts={held} {lat}x{mid} "
          f"tokens={tokens} load={GROUPED_LOAD}",
          functools.partial(jax.jit(kernels), src, r, dy, w1, w2),
          functools.partial(jax.jit(reference), src, r, dy, w1, w2),
          ("gather", "relu", "y2", "gather_gated", "row_dot", "dpre", "dx",
           "dw2", "dw1", "scatter"))
    swiglu_rows()


def swiglu_rows():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional import moe

    tokens, held, hidden, inner = SWIGLU_SHAPE
    rng = np.random.default_rng(1)
    bf16, f32 = jnp.bfloat16, jnp.float32
    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    x = jnp.asarray(rng.standard_normal((tokens, hidden)), bf16)
    dy = jnp.asarray(rng.standard_normal((tokens, hidden)), bf16)
    w13 = jnp.asarray(rng.standard_normal((held, hidden, 2 * inner)) * 0.02,
                      bf16)
    w2 = jnp.asarray(rng.standard_normal((held, inner, hidden)) * 0.02, bf16)
    even = rng.random((tokens, held)) < 1 / 64
    skewed = np.zeros((tokens, held), bool)
    for e, load in enumerate(SWIGLU_SKEWED):
        skewed[rng.choice(tokens, load, replace=False), e] = True
    for name, hit in (("even", even), ("skewed", skewed)):
        gates = jnp.asarray(np.where(hit, rng.random(hit.shape) * 0.5 + 0.1,
                                     0), f32)
        hit = jnp.asarray(hit)

        def kernels(x, w13, w2, gates, dy, hit=hit):
            y, back = jax.vjp(lambda *a: moe._sorted_swiglu(
                *a, hit, SWIGLU_TOP_K), x, w13, w2, gates)
            dx, dw13, dw2, dgates = back(dy)
            return y, dx, dw13, dw2, dgates

        def reference(x, w13, w2, gates, dy, hit=hit):
            def dense(x, w13, w2, gates):
                y = 0.0
                for e in range(held):
                    a, b = jnp.split(dot(x, w13[e]), 2, axis=-1)
                    y += gates[:, e:e + 1] * dot(jax.nn.silu(a) * b, w2[e])
                return y
            y, back = jax.vjp(dense, *(t.astype(f32)
                                       for t in (x, w13, w2, gates)))
            dx, dw13, dw2, dgates = back(dy.astype(f32))
            return y, dx, dw13, dw2, jnp.where(hit, dgates, 0)

        rows = int(np.asarray(hit).sum())
        check(f"swiglu experts tokens={tokens} experts={held} "
              f"{hidden}->{inner}->{hidden} load={name} rows={rows}",
              functools.partial(jax.jit(kernels), x, w13, w2, gates, dy),
              functools.partial(jax.jit(reference), x, w13, w2, gates, dy),
              ("y", "dx", "dw13", "dw2", "dgates"))


def device_ms(fn, *args, reps: int = 5) -> float:
    """Mean device time of one call of the compiled ``fn``, in ms: the
    ``XLA Modules`` events on the first chip of a profiler trace of
    ``reps`` calls after a warm one."""
    import glob
    import shutil

    import jax
    from jax.profiler import ProfileData

    jax.block_until_ready(fn(*args))
    where = os.path.join(REPO, "chiprun_out", "kernel_check_trace")
    shutil.rmtree(where, ignore_errors=True)
    jax.profiler.start_trace(where)
    try:
        for _ in range(reps):
            jax.block_until_ready(fn(*args))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(where, "**", "*.xplane.pb"),
                      recursive=True)
    total = sum(e.duration_ns for plane in ProfileData.from_file(path).planes
                if plane.name == "/device:TPU:0" for line in plane.lines
                if line.name == "XLA Modules" for e in line.events)
    shutil.rmtree(where, ignore_errors=True)
    return total * 1e-6 / reps


def kda_carry_rows():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import kda_carry

    bsz, n, heads, dk, dv = KDA_CARRY_SHAPE
    lead, chunk = (bsz, n, heads), KDA_CHUNK
    rng = np.random.default_rng(2)
    f32 = jnp.float32

    def normal(shape, std=1.0):
        return jnp.asarray(std * rng.standard_normal(shape), f32)

    # W and Kt small, Gamma_C in (0.5, 0.95): Diag(Gamma_C) - Kt^T W keeps
    # the state bounded over the 128 chunks, as the rule's does
    ins = (normal(lead + (chunk, dk), 0.05), normal(lead + (chunk, dv)),
           normal(lead + (chunk, dk)),
           normal(lead + (chunk, chunk), chunk ** -0.5)
           * np.tril(np.ones((chunk, chunk), np.float32)),
           normal(lead + (chunk, dk), 0.05),
           jnp.asarray(rng.uniform(0.5, 0.95, lead + (1, dk)), f32))
    do = normal(lead + (chunk, dv))
    passes = {"kernel": kda_carry.state_pass, "scan": kda_carry.scan_pass}
    forward = {name: jax.jit(functools.partial(fn, f32))
               for name, fn in passes.items()}
    backward = jax.jit(lambda pullback, do: pullback(do))
    pullbacks = {name: jax.vjp(forward[name], *ins)[1] for name in passes}

    def run(name):
        return (forward[name](*ins), *backward(pullbacks[name], do))

    check(f"kda_state_pass {KDA_CARRY_SHAPE} C={chunk} float32 fwd + vjp",
          lambda: run("kernel"), lambda: run("scan"),
          ("o", "dW", "dU", "dQG", "dA", "dKt", "dGamma_C"),
          tol=KDA_CARRY_TOL)
    row = ROWS[-1]
    if "max_abs_err" not in row:
        return
    row["rel_err"] = {label: float(f"{err / row['ref_max_abs'][label]:.3g}")
                      for label, err in row["max_abs_err"].items()}
    row["device_ms"] = {
        f"{name}.{way}": round(device_ms(*call), 4)
        for name in passes
        for way, call in (("fwd", (forward[name], *ins)),
                          ("bwd", (backward, pullbacks[name], do)))}
    print(json.dumps({"kernel": row["kernel"], "rel_err": row["rel_err"],
                      "device_ms": row["device_ms"]}), flush=True)


def main() -> int:
    import jax

    import paddle_tpu as paddle
    if jax.default_backend() != "tpu":
        print(f"kernel_check: needs the TPU, found backend "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    paddle.device.use_compile_cache()
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}  "
          f"jax {jax.__version__}", flush=True)
    families = {"flash": flash_rows, "tail": tail_rows, "other": other_rows,
                "grouped": grouped_rows, "kda_carry": kda_carry_rows}
    for name in sys.argv[1:] or families:
        families[name]()
    bad = [r["kernel"] for r in ROWS
           if not (r["compiled"] and r["within_tolerance"])]
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "kernel_check.json"), "w") as f:
        json.dump({"device": {"platform": dev.platform,
                              "kind": dev.device_kind,
                              "count": len(jax.devices())},
                   "jax": jax.__version__, "rows": ROWS, "failed": bad}, f,
                  indent=1)
    print(f"\n{len(ROWS) - len(bad)}/{len(ROWS)} kernels compiled and "
          f"within tolerance" + (f"; FAILED: {bad}" if bad else ""),
          flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

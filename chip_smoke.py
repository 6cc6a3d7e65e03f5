#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no children, no network, no git.  It drives the flagship
training path through the public API at the full published width of
GPT-2 345M (24 layers x 1024 hidden x 16 heads, vocabulary 50304, batch
8 x 1024 tokens, bf16 O2 — exactly what ``bench.bench_gpt2_345m`` builds),
with random weights from seed 0, and checks what comes out:

* JAX's default backend is ``tpu`` and ``paddle.get_device()`` is
  ``tpu:0`` — otherwise it exits non-zero with a one-line reason and
  prints no result (an inherited ``JAX_PLATFORMS=cpu`` fails loudly);
* seven steps on one fixed batch: every loss finite, the first near the
  loss of an untrained model (ln V), the last below the first, the
  parameters on a TPU device afterwards;
* the compiled step holds one Mosaic custom call per layer for each of
  the flash forward, dq and dk/dv kernels and never materialises the
  (B, H, S, S) score matrix — the Pallas kernel is in the step and
  ``_xla_attention`` is not;
* the flash kernel agrees with its reference (float32, highest matmul
  precision), forward and backward, on one small input at the model's
  own (S, d);
* with four or more devices, the multi-chip legs in the same process:
  ZeRO-1 data parallel GPT-2 345M over dp=4 against the one-chip loss,
  ``dryrun_multichip(4)`` (pp2 x sp2 1F1B + ring attention) and the
  sharded weight update with the f32 and the int8 ring wire.

Compile seconds and step time are printed as information, with the
device beside them; this is not a benchmark.  The line before the last
(``summary: {...}``) holds every phase's numbers as one JSON object
ending in ``"claim": null``; the last line of standard output is the result the driver reads,
one JSON object with exactly these keys: ``{"ok": true, "device":
{"platform": "tpu", "kind": "...", "count": 1}}``.  A failed phase prints
its traceback, the phases after it still run, ``ok`` is false and the
exit code is non-zero.

    python chip_smoke.py
"""
from __future__ import annotations

import gc
import json
import math
import os
import re
import sys
import time
import traceback

import numpy as np

BATCH, SEQ, STEPS = 8, 1024, 7


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: FAILED: {what}")


def _versions() -> dict:
    from importlib import metadata

    import jax
    import jaxlib
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu, "python": sys.version.split()[0]}


def result_line(ok: bool, device: dict) -> str:
    """The last line of standard output: one JSON object with exactly the
    keys the driver reads, the device as JAX reports it."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def _gpt2_345m():
    from paddle_tpu import optimizer
    from paddle_tpu.models import GPT, gpt2_345m
    cfg = gpt2_345m(remat=False, max_seq_len=SEQ, scan_unroll=24)
    model = GPT(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
    return cfg, model, opt


def _batch(vocab: int, batch: int, seed: int):
    import paddle_tpu as paddle
    ids = np.random.default_rng(seed).integers(
        0, vocab, size=(batch, SEQ)).astype(np.int32)
    return paddle.to_tensor(ids)


def one_chip(devices) -> dict:
    """GPT-2 345M through ``TrainStep`` on one chip."""
    import jax

    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import gpt_loss
    from paddle_tpu.parallel import make_mesh, set_mesh

    set_mesh(make_mesh({"dp": 1}, devices=devices[:1]))
    cfg, model, opt = _gpt2_345m()
    step = TrainStep(model, gpt_loss, opt, amp_level="O2",
                     amp_dtype="bfloat16")
    ids = _batch(cfg.vocab_size, BATCH, seed=0)

    losses, times = [], []
    for _ in range(STEPS):
        t0 = time.perf_counter()
        losses.append(float(step(ids, ids)))     # device->host fetch
        times.append(time.perf_counter() - t0)
        print(f"  step {len(losses)}: loss {losses[-1]:.4f}  "
              f"{times[-1]:.3f} s", flush=True)
    _require(all(math.isfinite(x) for x in losses),
             f"non-finite loss in {losses}")
    _require(abs(losses[0] - math.log(cfg.vocab_size)) < 1.0,
             f"first loss {losses[0]:.3f} is not that of an untrained "
             f"model (ln V = {math.log(cfg.vocab_size):.3f})")
    _require(losses[-1] < losses[0],
             f"loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")
    _require(all(d.platform == "tpu" for p in model.parameters()
                 for d in p._data.devices()),
             "parameters are not on a TPU device after the step")

    # the Pallas kernels are in the compiled step, XLA attention is not
    hlo = step.compiled_text()
    mosaic = [ln for ln in hlo.splitlines()
              if 'custom_call_target="tpu_custom_call"' in ln]
    calls = {k: sum(1 for ln in mosaic
                    if re.search(rf'op_name="[^"]*\b{k}\b', ln))
             for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")}
    _require(all(n == cfg.num_layers for n in calls.values()),
             f"expected {cfg.num_layers} Mosaic calls per flash kernel "
             f"in the compiled step, found {calls}")
    scores = rf"\[{BATCH},{cfg.num_heads},{SEQ},{SEQ}\]"
    _require(re.search(scores, hlo) is None,
             "the compiled step materialises a (B, H, S, S) score matrix")

    steady = sorted(times[2:])[len(times[2:]) // 2]
    print(f"  information: first call (compile + step) {times[0]:.1f} s, "
          f"steady step {steady * 1e3:.1f} ms (median of {STEPS - 2}, "
          f"loss fetched each step) on {jax.devices()[0].device_kind}",
          flush=True)
    return {"losses": [round(x, 4) for x in losses],
            "mosaic_flash_calls": calls,
            "first_call_s": round(times[0], 1),
            "steady_step_ms": round(steady * 1e3, 1)}


def flash_vs_reference() -> dict:
    """The flash kernel against its XLA reference at GPT-2's (S, d)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import verify

    shape = (1, SEQ, 4, 64)
    _require(fa.supported(shape, shape, True, causal=True),
             f"flash supported() rejects the model's own shape {shape}")
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
               for _ in range(3))
    scale = 1.0 / math.sqrt(shape[-1])

    def run(attn, dtype=jnp.bfloat16):
        return jax.jit(jax.value_and_grad(
            lambda a, b, c: (attn(a, b, c).astype(jnp.float32) ** 2).sum(),
            argnums=(0, 1, 2)))(q.astype(dtype), k.astype(dtype),
                                v.astype(dtype))

    got = run(lambda a, b, c: fa.flash_attention(a, b, c, causal=True,
                                                 scale=scale))
    # ground truth: the reference in float32 at the highest matmul
    # precision, held to verify.SCALE_TOL of its largest value
    with jax.default_matmul_precision("highest"):
        want = run(lambda a, b, c: fa._xla_reference(a, b, c, scale, True),
                   jnp.float32)
    errs = {}
    for name, (err, ref_max) in zip(("loss", "dq", "dk", "dv"),
                                    verify.max_errors(got, want)):
        errs[name] = float(f"{err:.3g}")
        _require(err <= verify.SCALE_TOL * ref_max,
                 f"flash {name} disagrees with the float32 reference: max "
                 f"abs err {err:.3g} against a largest value of "
                 f"{ref_max:.3g}")
    print(f"  flash vs float32 reference, max abs err: {errs}", flush=True)
    return {"max_abs_err": errs}


def zero1_dp4(devices, one_chip_first_loss: float) -> dict:
    """Leg 6a: ZeRO-1 data parallel GPT-2 345M over dp=4, same seed and
    first batch as the one-chip leg."""
    import jax

    from paddle_tpu.models import gpt_loss
    from paddle_tpu.parallel import ShardedTrainStep, make_mesh, set_mesh

    devs = devices[:4]
    mesh = make_mesh({"dp": 4}, devices=devs)
    set_mesh(mesh)
    cfg, model, opt = _gpt2_345m()
    step = ShardedTrainStep(model, gpt_loss, opt, mesh=mesh,
                            sharding_stage=1, amp_level="O2",
                            amp_dtype="bfloat16")
    ids = _batch(cfg.vocab_size, BATCH, seed=0)
    first = float(step(ids, ids))
    rel = abs(first - one_chip_first_loss) / abs(one_chip_first_loss)
    print(f"  first loss {first:.4f} vs one chip "
          f"{one_chip_first_loss:.4f} (rel {rel:.2e})", flush=True)
    _require(rel < 1e-3, f"dp=4 first loss {first} vs one chip "
                         f"{one_chip_first_loss}: rel {rel:.2e} >= 1e-3")
    ids32 = _batch(cfg.vocab_size, 4 * BATCH, seed=1)
    losses = [float(step(ids32, ids32)) for _ in range(3)]
    print(f"  global batch {4 * BATCH}: losses {losses}", flush=True)
    _require(all(math.isfinite(x) for x in losses) and
             losses[-1] < losses[0], f"dp=4 loss did not fall: {losses}")
    state = [p._data for p in model.parameters()] + \
        jax.tree_util.tree_leaves(step._opt_states)
    for d in devs:
        held = sum(s.data.nbytes for a in state
                   for s in a.addressable_shards if s.device == d)
        in_use = d.memory_stats()["bytes_in_use"]
        print(f"  {d}: {held / 2**20:.0f} MiB of parameter/optimizer "
              f"shards, {in_use / 2**20:.0f} MiB in use", flush=True)
        _require(held > 0 and in_use > 0,
                 f"{d} holds no training state (the model stayed where "
                 f"it was built)")
    return {"first_loss": round(first, 4),
            "rel_vs_one_chip": float(f"{rel:.2e}"),
            "losses_b32": [round(x, 4) for x in losses]}


def dryrun_pp2_sp2() -> str:
    """Leg 6b: interleaved 1F1B + ring attention on the real devices."""
    import __graft_entry__
    __graft_entry__.dryrun_multichip(4)
    return "ok"


def sharded_update_dp4(devices) -> dict:
    """Leg 6c: the sharded weight update on dp=4, f32 wire and the int8
    ring wire."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models import GPT, gpt_loss, gpt_tiny
    from paddle_tpu.parallel import make_mesh, set_mesh
    from paddle_tpu.parallel.zero import ShardedUpdateTrainStep

    mesh = make_mesh({"dp": 4}, devices=devices[:4])
    set_mesh(mesh)
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, 256, size=(8, 64)).astype(np.int32))
    out = {}
    for label, kw in (("f32", {"wire_dtype": "f32"}),
                      ("ring_int8", {"wire_dtype": "int8", "ring": True})):
        model = GPT(gpt_tiny(num_layers=2, max_seq_len=64))
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=model.parameters())
        step = ShardedUpdateTrainStep(model, gpt_loss, opt, mesh=mesh,
                                      amp_level="O2", amp_dtype="bfloat16",
                                      **kw)
        losses = [float(step(ids, ids)) for _ in range(2)]
        print(f"  wire {label}: losses {losses}", flush=True)
        _require(all(math.isfinite(v) for v in losses) and
                 losses[1] < losses[0],
                 f"sharded update ({label}) did not train: {losses}")
        out[label] = [round(v, 4) for v in losses]
    _require(abs(out["f32"][0] - out["ring_int8"][0]) < 1e-3,
             f"the two wires disagree on the first loss: {out}")
    return out


def main() -> int:
    t_start = time.perf_counter()
    import jax

    import paddle_tpu as paddle

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    versions = _versions()
    print(f"device: {device}  versions: {versions}", flush=True)
    if jax.default_backend() != "tpu" or paddle.get_device() != "tpu:0":
        print(f"chip_smoke: no TPU: jax.default_backend()="
              f"{jax.default_backend()!r}, paddle.get_device()="
              f"{paddle.get_device()!r}, JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}", file=sys.stderr)
        return 1

    cache = paddle.device.use_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"compile cache: {cache} ({entries} entries at start: "
          f"{'warm' if entries else 'cold'})", flush=True)

    phases, failed = {}, []

    def phase(name, title, fn, *args):
        """Run one phase; a failure is printed with its traceback and
        reaches the exit code, and the phases after it still run."""
        in_use = devices[0].memory_stats()["bytes_in_use"] / 2**20
        print(f"{name}: {title}  [{in_use:.0f} MiB in use on "
              f"{devices[0]} at start]", flush=True)
        t0 = time.perf_counter()
        try:
            phases[name] = fn(*args)
        except Exception:             # noqa: BLE001 — reported below
            traceback.print_exc()
            failed.append(name)
        print(f"{name}: {'FAILED' if name in failed else 'ok'} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)
        gc.collect()                  # the phase's model leaves the HBM

    phase("one_chip", f"GPT-2 345M TrainStep, bf16 O2, batch {BATCH} x "
          f"{SEQ}", one_chip, devices)
    phase("flash_vs_reference", "flash kernel against its reference",
          flash_vs_reference)
    if len(devices) >= 4:
        phase("zero1_dp4", "GPT-2 345M ShardedTrainStep, dp=4, ZeRO-1",
              lambda: zero1_dp4(devices, phases["one_chip"]["losses"][0]))
        phase("dryrun_pp2_sp2", "dryrun_multichip(4): 1F1B + ring "
              "attention", dryrun_pp2_sp2)
        phase("sharded_update_dp4", "ShardedUpdateTrainStep, dp=4, f32 and "
              "int8 ring wire", sharded_update_dp4, devices)
    else:
        print(f"multichip: not run ({len(devices)} device)", flush=True)
        phases["multichip"] = f"not run ({len(devices)} device)"

    print("summary: " + json.dumps({
        "versions": versions,
        "compile_cache": {"dir": cache, "entries_at_start": entries},
        "phases": phases, "failed": failed,
        "wall_s": round(time.perf_counter() - t_start, 1),
        "claim": None}), flush=True)
    print(result_line(not failed, device), flush=True)
    if failed:
        print(f"chip_smoke: FAILED phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

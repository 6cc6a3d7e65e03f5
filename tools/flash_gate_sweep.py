#!/usr/bin/env python
"""Where does the flash kernel beat XLA's attention for a short non-causal
call?  The table behind ``flash_attention._faster_than_xla``.

    python tools/flash_gate_sweep.py                       # S = 128 .. 768
    python tools/flash_gate_sweep.py --seqs 512 --seconds 5
    python tools/flash_gate_sweep.py --rehearse            # here, CPU, tiny: counts only

Trains BERT-base through the benchmark's own ``TrainStep`` (its
configuration file, AMP O2, ``remat=True``) at a fixed number of tokens a
step, batch = tokens / S, once with every capable attention call sent to
XLA and once with every one sent to the kernels, and reads each step from
a device trace with the benchmark's reducers: device time a step, the
region ``attn``, its inner scope ``core`` (the kernels or XLA's
materialised scores, their layout copies included), the recomputed
forward, the three kernels by name and the Mosaic calls a step.  One JSON
line a run on stdout and, with every line, ``chiprun_out/flash_gate_sweep
.json``.  Needs the TPU: one process, no children.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CELL = "bert_base.pretrain_b32_s512"      # the configuration's own cell
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def _core_ms(trace, scopes):
    """(ms a step of the operations under ``attn``'s inner scope ``core``,
    every pass, on the first chip; its eight largest, [label, ms])."""
    from collections import defaultdict

    from benchmarks.harness import trace_reduce
    from jax.profiler import ProfileData
    path = scopes.newest_trace_file(scopes.ROOT)
    chip = trace.chips[0]
    ops = scopes.read_events(ProfileData.from_file(path), chip)["ops"]
    with open(path, "rb") as f:
        paths = scopes.hlo_paths(f.read())
    lo, hi = trace.window[chip]
    by = defaultdict(float)
    for program, instruction, text, start, end in ops:
        scope = paths.get(program, {}).get(instruction, ("", ""))[1]
        if "core" in scopes._TOKEN.findall(scope or ""):
            label = trace_reduce.op_label(text) if " = " in text else text
            by[label] += 1e3 * max(0.0, min(end, hi) - max(start, lo)) \
                / trace.steps
    top = sorted(by.items(), key=lambda kv: -kv[1])[:8]
    return sum(by.values()), [[k, round(v, 3)] for k, v in top]


def run_one(seq: int, tokens: int, to_kernel: bool, seconds: float, seed: int,
            rehearse: bool) -> dict:
    """One step built, warmed, timed for ``seconds`` and traced."""
    import jax

    from benchmarks.harness import measure, scopes
    from paddle_tpu.ops.pallas import flash_attention as fa

    cell = measure.load_cell(CELL, rehearse)
    cell["traffic"].update(batch=tokens // seq, seq=seq)
    cell["sizes"]["max_position_embeddings"] = max(
        seq, cell["sizes"]["max_position_embeddings"])
    fa._faster_than_xla = lambda *shape: to_kernel
    if rehearse:                 # the CPU reaches the kernels interpreted
        fa._INTERPRET = to_kernel
    model, step, _, batch = measure._build(cell, seed, jax.devices()[:1])
    for _ in range(3):
        loss = float(step(*batch))
    window = measure.run_loop(step, batch, seconds)
    name = f"gate_sweep_s{seq}_{'kernel' if to_kernel else 'xla'}"
    quiet = lambda *_: None
    trace = measure._traced_stretch(step, batch, name, quiet)
    run = {"say": quiet}
    read = lambda metric: measure._reader("layer_metrics", metric).reduce(
        trace, run)
    core_ms, core_top = _core_ms(trace, scopes)
    batch_size = tokens // seq
    row = {
        "seq": seq, "batch": batch_size,
        "path": "kernel" if to_kernel else "xla", "loss": loss,
        "tokens_per_s": window["dispatched"] * batch_size * seq
        / window["wall_s"],
        "step_device_ms": read("step_device_ms"),
        "attn_ms": read("attn_ms_per_step"),
        "attn_core_ms": core_ms,
        "recompute_ms": read("recompute_ms_per_step"),
        "mosaic_calls": read("mosaic_calls_in_step"),
        **{k + "_ms": 1e3 * trace.per_step(trace.chips[0], k)
           for k in KERNELS},
        "peak_bytes": 0 if rehearse
        else measure._peak_bytes(jax.devices()[0]),
        "attn_core_top": core_top,
    }
    row = {k: round(v, 3) if isinstance(v, float) and k != "loss" else v
           for k, v in row.items()}
    print(json.dumps(row), flush=True)
    del model, step, batch, trace
    gc.collect()
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seqs", default="128,256,384,512,768")
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seed", type=int, default=31)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)

    import jax
    if a.rehearse:
        a.seqs, a.tokens, a.seconds = "128", 256, 0.5
    elif jax.default_backend() != "tpu":
        print("no TPU attached: the sweep measures the chip and nothing "
              "else", file=sys.stderr)
        return 1
    else:
        from paddle_tpu.device import use_compile_cache
        use_compile_cache()
    rows = []
    out = os.path.join(REPO, "chiprun_out", "flash_gate_sweep.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    for seq in (int(s) for s in a.seqs.split(",")):
        for to_kernel in (False, True):
            rows.append(run_one(seq, a.tokens, to_kernel, a.seconds, a.seed,
                                a.rehearse))
            with open(out, "w") as f:
                json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

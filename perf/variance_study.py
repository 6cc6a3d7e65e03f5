#!/usr/bin/env python
"""Run-to-run variance study (round-4 verdict item 4).

Measures N repetitions of (a) the two noisy bench configs (longseq
flash, widedeep PS) and (b) the op_bench suite, on the attached device.
Writes:
  * perf/variance_study.md       — mean/std/CV table
  * tools/op_bench_thresholds.json — per-op gate thresholds sized as
    max(0.15, 6×CV) from the measured distribution (a planted 1.3×
    regression must fail while run-to-run jitter must pass)

Run from the repo root:  python - < perf/variance_study.py
"""
from __future__ import annotations

import io
import json
import os
import sys
import time
from contextlib import redirect_stdout

import numpy as np

sys.path.insert(0, os.getcwd())

N = 5


def capture_bench(fn, metric):
    """Run a bench.py function, harvest one metric value from its JSON
    lines."""
    import bench
    buf = io.StringIO()
    with redirect_stdout(buf):
        fn(True)
    for line in buf.getvalue().splitlines():
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if rec.get("metric") == metric:
            return rec["value"]
    raise RuntimeError(f"metric {metric} not emitted; got:\n{buf.getvalue()}")


def main():
    import bench
    from tools import op_bench

    out = {"bench": {}, "ops": {}}

    # --ops-only: keep the saved bench-leg values (the slow 100M-table
    # runs) and re-measure only the op distribution — used after an
    # estimator change in op_bench.run_one
    ops_only = "--ops-only" in sys.argv
    if ops_only:
        with open("perf/variance_raw.json") as f:
            out["bench"] = json.load(f)["bench"]

    import gc
    raw_path = "perf/variance_raw.json"

    def checkpoint():
        # crash insurance: a host OOM mid-study must not lose the
        # completed measurements
        with open(raw_path, "w") as f:
            json.dump(out, f, indent=1)

    for fn, metric in ([] if ops_only else [
        (bench.bench_longseq_flash,
         "gpt_longseq8k_flashattn_train_tokens_per_sec"),
        (lambda acc: bench.bench_widedeep_ps(acc, extra_legs=False),
         "widedeep_ps_host_table_100M_examples_per_sec"),
    ]):
        vals = []
        for i in range(N):
            v = capture_bench(fn, metric)
            vals.append(v)
            print(f"{metric} run {i+1}/{N}: {v:.1f}", flush=True)
            # the PS leg builds a ~26 GB host table per run — reclaim it
            # before the next build, not at interpreter exit
            gc.collect()
            out["bench"][metric] = vals
            checkpoint()

    # one unrecorded pass eats the per-op compile (the first measured
    # pass otherwise carries a 2-4x compile tail into the distribution)
    for cfg in op_bench.BUILTIN_SUITE:
        op_bench.run_one(cfg, iters=4, repeats=1)
    print("op suite warm pass done", flush=True)
    for i in range(N):
        for cfg in op_bench.BUILTIN_SUITE:
            r = op_bench.run_one(cfg, iters=10)
            out["ops"].setdefault(r["name"], []).append(r["ms"])
        print(f"op suite pass {i+1}/{N} done", flush=True)
        checkpoint()

    # -- write markdown ----------------------------------------------------
    lines = ["# Run-to-run variance study", "",
             f"N = {N} repetitions per config, device-fetch fenced.  Op "
             "rows use the same estimator as the CI gate: a two-length "
             "jitted-scan difference (device-time per iteration; the "
             "host's dispatch cost cancels in the difference), min over "
             "3 dispatches, after one unrecorded "
             "compile-warm pass.", "",
             "| metric | mean | std | CV |", "|---|---|---|---|"]
    for metric, vals in out["bench"].items():
        a = np.asarray(vals)
        lines.append(f"| {metric} | {a.mean():.1f} | {a.std(ddof=1):.1f} "
                     f"| {a.std(ddof=1)/a.mean()*100:.1f}% |")
    thresholds = {}
    for name, vals in out["ops"].items():
        a = np.asarray(vals)
        cv = float(a.std(ddof=1) / a.mean())
        thresholds[name] = round(max(0.15, 6 * cv), 3)
        lines.append(f"| op:{name} (ms) | {a.mean():.3f} | "
                     f"{a.std(ddof=1):.4f} | {cv*100:.1f}% |")
    lines += [
        "", "Gate thresholds (`tools/op_bench_thresholds.json`) are sized "
        "as max(0.15, 6×CV) per op from this distribution: run-to-run "
        "jitter passes with ≥6σ headroom while a planted 1.3× regression "
        "fails every op whose threshold lands below 0.30 (verified by "
        "tests/test_op_bench_gate.py).", "",
        "Raw values:", "```json",
        json.dumps(out, indent=1), "```"]
    with open("perf/variance_study.md", "w") as f:
        f.write("\n".join(lines) + "\n")
    with open("tools/op_bench_thresholds.json", "w") as f:
        json.dump(thresholds, f, indent=1, sort_keys=True)
    print("wrote perf/variance_study.md + tools/op_bench_thresholds.json")


if __name__ == "__main__":
    from paddle_tpu.device import use_compile_cache
    use_compile_cache()
    main()

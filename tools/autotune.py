#!/usr/bin/env python
"""Offline knob search over the run ledger — the autopilot's other half.

``framework/autopilot.py`` reacts at runtime; this tool looks backwards:
it replays measured evidence — ``kind="autotune"`` ledger records (its
own ``--measure`` mode appends them) plus, optionally, a
``perf_report attribute`` profile for a corroborating steady step time —
to search the knob space the runtime controller also drives
(``prefetch_depth`` × ``wire_dtype`` × ``batch_size``) against a
measured objective (mean steady step ms, lower is better), and emits a
**tuned profile**:

    {"schema_version": 1,
     "objective": {"signal": "step_ms_mean", "value": 3.2},
     "knobs": {"prefetch_depth": 2, "wire_dtype": "bf16",
               "batch_size": 8},
     "candidates": [...]}

``TrainStep`` / ``PSTrainStep`` / ``bench.py`` consume it at startup via
``FLAGS_autotune_profile`` →
:func:`paddle_tpu.framework.autopilot.maybe_apply_tuned_profile`, so a
run starts from the tuned operating point instead of defaults.

Modes::

    # measure: run a short PS mini-train per knob combo, append one
    # kind="autotune" record each to the ledger
    python tools/autotune.py --ledger runs.jsonl --measure --steps 24 \
        --grid "prefetch_depth=0,1,2;wire_dtype=f32,bf16;batch_size=8"

    # search: pick the best measured combo, write the tuned profile
    python tools/autotune.py --ledger runs.jsonl --out tuned.json

Measurements go into each record's ``extra`` (NOT ``summary``), so
``perf_report compare`` over the same ledger never mistakes a knob
sweep for a regression.  Deterministic: fixed seeds and shapes; the
per-combo mini-train is the ``health_check.mini_train_ps`` recipe with
the knobs applied.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

DEFAULT_GRID = "prefetch_depth=0,1,2;wire_dtype=f32,bf16;batch_size=8"
WARMUP_STEPS = 3          # compile-carrying steps excluded from timing


def parse_grid(spec: str) -> List[Dict[str, Any]]:
    """``"a=1,2;b=x,y"`` → the cross product as knob dicts (ints where
    they parse, strings otherwise), in deterministic order."""
    axes: List[Tuple[str, List[Any]]] = []
    for part in filter(None, (p.strip() for p in spec.split(";"))):
        name, _, vals = part.partition("=")
        parsed: List[Any] = []
        for v in filter(None, (v.strip() for v in vals.split(","))):
            try:
                parsed.append(int(v))
            except ValueError:
                parsed.append(v)
        if not parsed:
            raise ValueError(f"empty grid axis: {part!r}")
        axes.append((name.strip(), parsed))
    combos: List[Dict[str, Any]] = [{}]
    for name, vals in axes:
        combos = [dict(c, **{name: v}) for c in combos for v in vals]
    return combos


def knob_key(knobs: Dict[str, Any]) -> Tuple:
    return tuple(sorted((k, str(v)) for k, v in knobs.items()))


# -- measure: one deterministic PS mini-train per combo ------------------

def measure_combo(knobs: Dict[str, Any], n_steps: int) -> Dict[str, Any]:
    """Run the fixed-seed PS mini-train under ``knobs`` and return its
    step-time stats.  Per-step wall times come from a local
    ``perf_counter`` ring (cumulative monitor counters would carry the
    previous combo's history)."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import optimizer
    from paddle_tpu.distributed.ps import (DistributedEmbedding,
                                           HostEmbeddingTable,
                                           PSTrainStep)
    from paddle_tpu.distributed.ps.service import (PsClient, PsServer,
                                                   RemoteEmbeddingTable)

    pd = int(knobs.get("prefetch_depth", 0))
    wd = str(knobs.get("wire_dtype", "f32"))
    bs = int(knobs.get("batch_size", 8))

    table = HostEmbeddingTable(256, 9, optimizer="sgd",
                               learning_rate=0.05, seed=0)
    srv = PsServer({"emb": table}, port=0).start()
    cli = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype=wd,
                   backoff_base=0.01)
    try:
        paddle.seed(0)
        emb = DistributedEmbedding(
            256, 9, mode="sync",
            table=RemoteEmbeddingTable(cli, "emb", 9))
        from paddle_tpu.models import WideDeepHost
        model = WideDeepHost(embedding_dim=8, num_fields=4, dense_dim=3,
                             hidden=(16,))
        opt = optimizer.Adam(learning_rate=1e-2,
                             parameters=model.parameters())

        def loss_fn(m, rows, x, y):
            return F.binary_cross_entropy_with_logits(
                m(rows, x), y).mean()

        step = PSTrainStep(model, loss_fn, opt, emb,
                           transfer_dtype="float32", prefetch_depth=pd)
        rng = np.random.default_rng(3)
        ids = rng.integers(0, 256,
                           size=(n_steps, bs, 4)).astype(np.int64)
        x = paddle.to_tensor(rng.standard_normal((bs, 3))
                             .astype(np.float32))
        y = paddle.to_tensor(rng.random((bs, 1)).astype(np.float32))
        times: List[float] = []
        losses: List[float] = []
        for n in range(n_steps):
            if pd > 0 and n + 1 < n_steps:
                step.prefetch(ids[n + 1])
            t0 = time.perf_counter()
            losses.append(float(step(ids[n], x, y)))
            times.append((time.perf_counter() - t0) * 1e3)
        step.flush()
        assert all(np.isfinite(losses)), \
            f"autotune mini train diverged under {knobs}: {losses[-5:]}"
    finally:
        try:
            cli.bye()
        finally:
            srv.shutdown()
    steady = times[WARMUP_STEPS:] or times
    return {"step_ms_mean": statistics.fmean(steady),
            "step_ms_p90": sorted(steady)[
                max(0, int(0.9 * len(steady)) - 1)],
            "steps": len(steady)}


def measure(ledger_path: str, grid: List[Dict[str, Any]],
            n_steps: int) -> List[dict]:
    from paddle_tpu.framework import runlog
    ledger = runlog.RunLedger(ledger_path)
    out = []
    for knobs in grid:
        stats = measure_combo(knobs, n_steps)
        label = "-".join(f"{k}{v}" for k, v in sorted(knobs.items()))
        rec = {"schema_version": runlog.SCHEMA_VERSION,
               "kind": "autotune", "label": label,
               "run_id": runlog._run_id(), "ts": time.time(),
               "meta": runlog.run_meta(),
               # measurements live in extra, NOT summary: a knob sweep
               # must never register as a perf_report regression series
               "summary": {},
               "extra": {"knobs": knobs, **stats}}
        ledger.append(rec)
        out.append(rec)
        print(f"measured {label}: "
              f"{stats['step_ms_mean']:.2f} ms/step "
              f"(p90 {stats['step_ms_p90']:.2f}, "
              f"n={stats['steps']})")
    return out


# -- search: replay the ledger, pick the argmin combo --------------------

def search(records: List[dict],
           attribute_profile: Optional[dict] = None) -> dict:
    """Group ``kind="autotune"`` records by knob combo, score each by
    the median of its measured ``step_ms_mean`` (median across repeat
    sweeps rejects a one-off noisy run), and emit the tuned profile for
    the argmin."""
    groups: Dict[Tuple, Dict[str, Any]] = {}
    for r in records:
        if r.get("kind") != "autotune":
            continue
        extra = r.get("extra") or {}
        knobs = extra.get("knobs")
        mean = extra.get("step_ms_mean")
        if not isinstance(knobs, dict) or mean is None:
            continue
        g = groups.setdefault(knob_key(knobs),
                              {"knobs": knobs, "means": []})
        g["means"].append(float(mean))
    if not groups:
        raise SystemExit(
            "autotune: no kind=autotune records with measurements in "
            "the ledger — run --measure first")
    candidates = sorted(
        ({"knobs": g["knobs"], "runs": len(g["means"]),
          "step_ms_mean": statistics.median(g["means"])}
         for g in groups.values()),
        key=lambda c: c["step_ms_mean"])
    best = candidates[0]
    prof = {"schema_version": 1,
            "objective": {"signal": "step_ms_mean",
                          "value": round(best["step_ms_mean"], 4)},
            "knobs": dict(best["knobs"]),
            "candidates": [
                {"knobs": c["knobs"], "runs": c["runs"],
                 "step_ms_mean": round(c["step_ms_mean"], 4)}
                for c in candidates]}
    if attribute_profile:
        # corroboration, not an input to the argmin: the attribute
        # profile's steady step mean for the UNtuned program, so a
        # reader can see what the tuning is up against
        for row in attribute_profile.get("spans") or []:
            if row.get("name") == attribute_profile.get(
                    "step_span", "train.step"):
                prof["objective"]["attribute_step_ms"] = row.get("mean_ms")
    return prof


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="autotune.py",
                                 description=__doc__)
    ap.add_argument("--ledger", required=True,
                    help="run ledger (runlog JSONL) to measure into / "
                    "search over")
    ap.add_argument("--measure", action="store_true",
                    help="run one PS mini-train per grid combo and "
                    "append kind=autotune records")
    ap.add_argument("--steps", type=int, default=24,
                    help="mini-train steps per combo (default 24)")
    ap.add_argument("--grid", default=DEFAULT_GRID,
                    help=f"knob grid (default {DEFAULT_GRID!r})")
    ap.add_argument("--attribute", default=None, metavar="PROF_JSON",
                    help="perf_report attribute profile: its steady "
                    "step mean is recorded in the output objective as "
                    "corroboration")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the tuned profile here (search phase; "
                    "omit to only measure)")
    a = ap.parse_args(argv)

    if a.measure:
        measure(a.ledger, parse_grid(a.grid), a.steps)
    if a.out is None:
        return 0

    from paddle_tpu.framework import runlog
    records = runlog.RunLedger(a.ledger).read()
    attr = None
    if a.attribute:
        with open(a.attribute, "r", encoding="utf-8") as f:
            attr = json.load(f)
    prof = search(records, attr)
    with open(a.out, "w", encoding="utf-8") as f:
        json.dump(prof, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"tuned profile -> {a.out}")
    print(f"  objective step_ms_mean="
          f"{prof['objective']['value']:.3f}")
    print(f"  knobs {prof['knobs']}")
    return 0


if __name__ == "__main__":
    from paddle_tpu.device import use_compile_cache
    use_compile_cache()
    raise SystemExit(main())

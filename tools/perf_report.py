#!/usr/bin/env python
"""Continuous-perf observatory CLI: span<->cost attribution and
cross-run regression detection over the persistent run ledger.

Three subcommands close the measure -> remember -> decide loop the
run ledger (``paddle_tpu/framework/runlog.py``) records for:

* ``attribute`` — join a merged trace's per-span-name aggregates
  (``tools/trace_merge.py summarize``) with the PTA106 analytic
  FLOP/byte cost model (``TrainStep.analyze()``) into a measured
  op-profile: per span name count / mean / p99 ms, and for the step
  program an achieved FLOP/s + bytes/s against the analytic totals,
  with the top-k PTA106 ops carrying a measured ms attributed from the
  step span by flop share.  Emitted as JSON and a
  roofline-style text table.  ``--mini-train N`` is the self-contained
  form (traced N-step train + ``analyze()`` in-process); ``--trace-dir
  + --cost-json`` joins existing artifacts.  ``--check`` gates that
  every top-k op has a positive measured ms and a finite achieved
  FLOP/s (the CI lane's acceptance).

* ``compare`` — run the existing ``health.Detector`` (EWMA + robust
  MAD z-score, deterministic, floor-protected) over ledger series:
  step-time p99, RPC p99, input stall, compile counts, anomaly totals
  (from each record's ``summary``) and every bench-leg metric (from
  ``legs``).  Series form within one ``(kind, label)`` record group.
  Short ledgers still gate: the pre-candidate prefix is cycled through
  the detector's warmup (MAD collapses to 0 on replicated values — the
  ``min_mad``/``rel_floor`` floors are exactly what keeps that sound),
  then every post-warmup run is scored.  Anomalies in the signal's
  WORSE direction are regressions (named, nonzero exit);
  better-direction anomalies are reported as improvements.

* ``blame`` — causal critical-path attribution
  (``framework/blame.py``): rebuild the per-step dependency DAG from a
  trace's span links (prefetch -> step, ingest fetch -> step, deferred
  push -> push_pull RPC), collapse the critical path into a per-step
  blame vector over fixed categories (``compute`` / ``ps_wait`` /
  ``ingest_wait`` / ``collective`` / ``compile`` / ``other``), and
  report totals, shares and the top blocking edges.  ``--check`` gates
  that every link resolves and the categories sum to within tolerance
  of the measured step span; ``--expect-top ps_wait`` is the chaos
  leg's assertion that injected RPC latency moved the bottleneck.
  ``compare`` detects the same categories cross-run
  (``blame_<cat>_ms`` series from each record's summary), so a
  bottleneck SHIFT at flat step time is a named regression.

* ``incidents`` — the postmortem plane's index: list ``kind=incident``
  ledger records (one per auto-captured bundle —
  ``framework/incident.py``) joined by incident id with the
  ``kind=incident_replay`` verdicts ``tools/replay.py --ledger``
  writes back, so reproduced-vs-not (and the bisected divergence
  step) reads next to each capture.

Usage::

    python tools/perf_report.py attribute --mini-train 3 --json prof.json --check
    python tools/perf_report.py attribute --trace-dir /tmp/tr --cost-json cost.json
    python tools/perf_report.py blame --mini-train 12 --check
    python tools/perf_report.py blame --trace-dir /tmp/tr --expect-top ps_wait
    python tools/perf_report.py compare --ledger runs/ledger.jsonl
    python tools/perf_report.py incidents --ledger runs/ledger.jsonl --json inc.json
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import sys
import tempfile
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

__all__ = ["attribute_profile", "format_attribute", "check_profile",
           "mini_train_cost", "leg_signal_cfg", "SUMMARY_SIGNAL_CFG",
           "build_series", "detect_series", "compare_records",
           "format_compare", "incident_rows", "format_incidents",
           "main"]


# ---------------------------------------------------------------------------
# attribute: span <-> cost-model join
# ---------------------------------------------------------------------------

def attribute_profile(rows: List[dict], cost: Optional[dict],
                      step_span: str = "train.step",
                      top_k: int = 5) -> dict:
    """Join trace-summary rows (``trace_merge.summarize``) with a
    structured PTA106 cost report (``Report.cost``) into the measured
    op-profile.  ``step_span`` names the span that executes the costed
    program (one span == one dispatch of it); the top-k cost ops get a
    measured ms attributed from that span's STEADY mean — the single
    heaviest span, i.e. the compile-carrying first dispatch, excluded —
    by flop share (an in-program attribution, honest about being a
    model — the ``attribution`` field says so)."""
    # Flop-share attribution makes each op's achieved FLOP/s equal the
    # PROGRAM rate by construction (flops_i / (mean_ms * flops_i /
    # total) == total / mean_ms) — it is the roofline sanity value the
    # acceptance gate checks for finiteness, not a per-op measurement.
    # The per-op information lives in measured_ms (the time share) and
    # achieved_bytes_per_sec (which DOES vary with each op's byte/flop
    # mix); true per-op rates need per-op spans, which XLA fusion
    # erases anyway.
    spans = {r["name"]: r for r in rows}
    prof: Dict[str, object] = {"schema_version": 1,
                               "step_span": step_span,
                               "spans": rows, "cost": cost, "ops": []}
    step = spans.get(step_span)
    if step is None or not cost:
        return prof
    # steady-state step time: drop the single heaviest span from the
    # mean — the first dispatch carries the XLA compile (hundreds of
    # ms vs sub-ms steps) and would inflate every attributed ms by
    # orders of magnitude.  One span only: nothing to drop.
    count = int(step["count"])
    raw_mean = float(step["mean_ms"])
    if count > 1:
        mean_ms = (float(step["total_ms"]) - float(step["max_ms"])) \
            / (count - 1)
    else:
        mean_ms = raw_mean
    sec = mean_ms / 1e3
    total_f = int(cost.get("total_flops", 0))
    total_b = int(cost.get("total_bytes", 0))
    prof["step"] = {
        "span": step_span,
        "count": count,
        "mean_ms": round(mean_ms, 6),
        "mean_ms_with_compile": raw_mean,
        "p99_ms": step["p99_ms"],
        "flops_per_step": total_f,
        "bytes_per_step": total_b,
        "achieved_flops_per_sec": total_f / sec if sec > 0 else None,
        "achieved_bytes_per_sec": total_b / sec if sec > 0 else None,
        "arithmetic_intensity": (total_f / total_b) if total_b else None,
    }
    ranked = [o for o in cost.get("by_op", []) if o.get("flops", 0) > 0]
    ops = []
    for rank, o in enumerate(ranked[:max(0, int(top_k))], start=1):
        share = o["flops"] / total_f if total_f else 0.0
        ms = mean_ms * share
        ops.append({
            "rank": rank, "op": o["op"], "count": o.get("count", 0),
            "flops": int(o["flops"]), "bytes": int(o.get("bytes", 0)),
            "flop_share": round(share, 4),
            "measured_ms": round(ms, 6),
            "achieved_flops_per_sec":
                o["flops"] / (ms / 1e3) if ms > 0 else None,
            "achieved_bytes_per_sec":
                o.get("bytes", 0) / (ms / 1e3) if ms > 0 else None,
            "attribution": "flop_share",
        })
    prof["ops"] = ops
    return prof


def check_profile(prof: dict, top_k: int = 5) -> List[str]:
    """The acceptance gate: the joined profile must carry a step row and
    top-k op rows whose measured ms is positive and achieved FLOP/s
    finite.  Returns the list of violations (empty = pass)."""
    bad = []
    step = prof.get("step")
    if not step:
        bad.append(f"no step row: span {prof.get('step_span')!r} absent "
                   "from the trace or no cost report joined")
        return bad
    ops = prof.get("ops") or []
    if not ops:
        bad.append("no op rows: cost report has no op with flops > 0")
    for o in ops[:top_k]:
        ms = o.get("measured_ms")
        fps = o.get("achieved_flops_per_sec")
        if not ms or ms <= 0:
            bad.append(f"op {o['op']!r}: no measured ms ({ms!r})")
        if fps is None or not math.isfinite(float(fps)):
            bad.append(f"op {o['op']!r}: achieved FLOP/s not finite "
                       f"({fps!r})")
    return bad


def _human(v: Optional[float], unit: str = "") -> str:
    if v is None:
        return "-"
    for scale, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"),
                          (1e3, "k")):
        if abs(v) >= scale:
            return f"{v / scale:.2f}{suffix}{unit}"
    return f"{v:.2f}{unit}"


def format_attribute(prof: dict) -> str:
    """Render the joined profile as a roofline-style text table."""
    lines = ["== op profile (measured spans x PTA106 analytic cost) =="]
    step = prof.get("step")
    if step:
        ai = step["arithmetic_intensity"]
        lines.append(
            f"step span {step['span']!r}: {step['count']} x "
            f"mean {step['mean_ms']:.3f} ms (p99 {step['p99_ms']:.3f}) | "
            f"{_human(float(step['flops_per_step']))}flop "
            f"{_human(float(step['bytes_per_step']))}B per step | "
            f"achieved {_human(step['achieved_flops_per_sec'])}FLOP/s "
            f"{_human(step['achieved_bytes_per_sec'])}B/s | "
            f"intensity {'-' if ai is None else round(ai, 2)} flop/B")
    ops = prof.get("ops") or []
    if ops:
        cols = ("#", "op", "count", "flops", "bytes", "ms",
                "FLOP/s", "B/s", "share")
        table = [cols]
        for o in ops:
            table.append((str(o["rank"]), o["op"], str(o["count"]),
                          _human(float(o["flops"])),
                          _human(float(o["bytes"])),
                          f"{o['measured_ms']:.4f}",
                          _human(o["achieved_flops_per_sec"]),
                          _human(o["achieved_bytes_per_sec"]),
                          f"{o['flop_share']:.1%}"))
        widths = [max(len(r[i]) for r in table)
                  for i in range(len(cols))]
        for j, row in enumerate(table):
            lines.append("  ".join(
                c.ljust(widths[i]) if i == 1 else c.rjust(widths[i])
                for i, c in enumerate(row)))
            if j == 0:
                lines.append("  ".join("-" * w for w in widths))
    rows = prof.get("spans") or []
    if rows:
        import trace_merge
        lines.append("-- span summary --")
        lines.append(trace_merge.format_summary(rows))
    return "\n".join(lines)


def mini_train_cost(n_steps: int, trace_dir: str) -> dict:
    """Self-contained attribute input: run a traced, fixed-seed N-step
    mini train (two-layer MLP under ``TrainStep``) whose ``train.step``
    spans land in ``trace_dir``, then ``analyze()`` the same step for
    the structured PTA106 cost report.  Returns ``Report.cost``."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.framework.observability import tracer
    from paddle_tpu.jit import TrainStep

    class _MLP(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(32, 64)
            self.fc2 = nn.Linear(64, 8)

        def forward(self, x):
            return self.fc2(
                paddle.nn.functional.relu(self.fc1(x)))

    paddle.seed(0)
    rng = np.random.default_rng(0)
    net = _MLP()
    opt = paddle.optimizer.SGD(learning_rate=0.05,
                               parameters=net.parameters())
    step = TrainStep(net, lambda m, x, y: ((m(x) - y) ** 2).mean(), opt)
    x = paddle.to_tensor(rng.standard_normal((16, 32)).astype(np.float32))
    y = paddle.to_tensor(rng.standard_normal((16, 8)).astype(np.float32))
    tracer.enable(trace_dir, label="perf_report")
    try:
        for _ in range(n_steps):
            step(x, y)
    finally:
        tracer.disable()
    report = step.analyze(x, y)
    return report.cost


# ---------------------------------------------------------------------------
# compare: Detector over ledger series
# ---------------------------------------------------------------------------

#: per-signal detector shape for the scalar summary series each record
#: carries.  ``worse`` names the regression direction; the floors keep
#: replicated-baseline MAD collapse (short ledgers) from flagging
#: jitter — latency needs tens of ms or a multiple of baseline, counts
#: need a jump of several
SUMMARY_SIGNAL_CFG: Dict[str, dict] = {
    "train_step_p99_ms": {"worse": "up", "min_mad": 5.0,
                          "rel_floor": 0.5},
    "train_step_mean_ms": {"worse": "up", "min_mad": 5.0,
                           "rel_floor": 0.5},
    "ps_rpc_p99_ms": {"worse": "up", "min_mad": 5.0, "rel_floor": 0.5},
    "ps_rpc_mean_ms": {"worse": "up", "min_mad": 5.0, "rel_floor": 0.5},
    "input_stall_pct": {"worse": "up", "min_mad": 2.0,
                        "rel_floor": 0.25},
    "jit_compiles_total": {"worse": "up", "min_mad": 0.5,
                           "z_threshold": 6.0},
    "jit_recompiles_steady_total": {"worse": "up", "min_mad": 0.1,
                                    "z_threshold": 6.0},
    "health_anomalies_total": {"worse": "up", "min_mad": 0.5,
                               "z_threshold": 6.0},
    "numerics_nonfinite_steps_total": {"worse": "up", "min_mad": 0.1,
                                       "z_threshold": 6.0},
    # ZeRO collective wire bytes per step (parallel/zero.py via
    # monitor stats): deterministic byte accounting for the fused
    # reduce-scatter + all-gather pair, so a wire/codec change shows
    # up as a named byte-series move — a quantized ring run against an
    # f32 baseline prints an IMPROVEMENT here, a silently-widened wire
    # a regression.  Bytes are exact (no timing jitter): tiny floors
    "zero_collective_bytes_per_step": {"worse": "up", "min_mad": 1.0,
                                       "rel_floor": 0.02},
    # cluster-granularity series (framework/collector.py
    # CollectorServer.capture_record): the collector's cross-worker
    # view gates here — a new straggler, a step-skew jump, or RPC-p99
    # growth across runs is a named regression
    "cluster_step_p99_ms_max": {"worse": "up", "min_mad": 5.0,
                                "rel_floor": 0.5},
    "cluster_ps_rpc_p99_ms": {"worse": "up", "min_mad": 5.0,
                              "rel_floor": 0.5},
    "cluster_input_stall_pct_max": {"worse": "up", "min_mad": 2.0,
                                    "rel_floor": 0.25},
    "cluster_step_skew": {"worse": "up", "min_mad": 0.5,
                          "z_threshold": 6.0},
    "cluster_straggler_count": {"worse": "up", "min_mad": 0.4,
                                "z_threshold": 6.0},
    "cluster_anomalies_total": {"worse": "up", "min_mad": 0.5,
                                "z_threshold": 6.0},
    "cluster_report_gaps_total": {"worse": "up", "min_mad": 2.0,
                                  "rel_floor": 0.5},
    # per-step blame series (framework/blame.py via runlog.capture):
    # a run whose TOTAL step time is flat but whose blame shifted —
    # compute fell, ps_wait rose — is a bottleneck shift, flagged by
    # the category name.  Every category regresses UP (more blocked ms
    # per step is worse whatever the resource); floors keep sub-ms
    # localhost jitter quiet while an injected latency (tens of ms)
    # clears them by an order of magnitude
    "blame_compute_ms": {"worse": "up", "min_mad": 5.0,
                         "rel_floor": 0.5},
    "blame_ps_wait_ms": {"worse": "up", "min_mad": 2.0,
                         "rel_floor": 0.5},
    "blame_ingest_wait_ms": {"worse": "up", "min_mad": 2.0,
                             "rel_floor": 0.5},
    "blame_collective_ms": {"worse": "up", "min_mad": 2.0,
                            "rel_floor": 0.5},
    "blame_compile_ms": {"worse": "up", "min_mad": 10.0,
                         "rel_floor": 1.0},
    "blame_other_ms": {"worse": "up", "min_mad": 2.0,
                       "rel_floor": 0.5},
}


def leg_signal_cfg(metric: str, unit: Optional[str]) -> dict:
    """Detector shape for a bench-leg metric, inferred from its name
    and unit: throughput regresses DOWN, latency/bytes/stall UP."""
    m = metric.lower()
    u = (unit or "").lower()
    if "stall" in m or m.endswith("_pct"):
        return {"worse": "up", "min_mad": 2.0, "rel_floor": 0.25}
    if "per_sec" in m:
        return {"worse": "down", "min_mad": 1e-9, "rel_floor": 0.05,
                "z_threshold": 4.0}
    if u in ("ms", "s") or m.endswith("_ms"):
        return {"worse": "up", "min_mad": 5.0, "rel_floor": 0.5}
    if u in ("mb", "bytes") or "mb_per" in m or "bytes" in m:
        return {"worse": "up", "min_mad": 1e-9, "rel_floor": 0.05,
                "z_threshold": 4.0}
    if "agreement" in m or u == "fraction":
        return {"worse": "down", "min_mad": 0.02, "rel_floor": 0.05}
    return {"worse": "both", "min_mad": 1e-9, "rel_floor": 0.25}


def build_series(records: List[dict]) -> Dict[str, dict]:
    """Signal series over one (kind, label) record group: summary
    scalars (known shapes only) plus every bench-leg metric.  Each
    series is ``{"cfg", "points": [(record_index, value), ...]}`` —
    a record missing a signal simply contributes no point (the plane
    was off for that run, not at zero)."""
    series: Dict[str, dict] = {}

    def add(name, cfg, idx, value):
        try:
            v = float(value)
        except (TypeError, ValueError):
            return
        s = series.setdefault(name, {"cfg": cfg, "points": []})
        s["points"].append((idx, v))

    for i, rec in enumerate(records):
        for sig, v in (rec.get("summary") or {}).items():
            cfg = SUMMARY_SIGNAL_CFG.get(sig)
            if cfg is not None:
                add(sig, cfg, i, v)
        for leg in rec.get("legs") or []:
            m = leg.get("metric")
            v = leg.get("value")
            if not m or v is None:
                continue
            if "_FAILED" in m or "SKIPPED" in m or \
                    m == "device_unavailable":
                continue               # failure markers are not series
            add(f"bench:{m}", leg_signal_cfg(m, leg.get("unit")), i, v)
    return series


def detect_series(signal: str, points, cfg: dict,
                  warmup: int = 4) -> dict:
    """Score one ledger series with ``health.Detector``.  The
    pre-candidate prefix is cycled through the detector's warmup so a
    2-run ledger still gates its second run; every post-warmup
    observation is scored, each run at most once.  Deterministic: the
    injected zero clock keeps anomaly records value-only."""
    from paddle_tpu.framework.health import Detector

    cfg = dict(cfg)
    worse = cfg.pop("worse", "both")
    n = len(points)
    if n < 2:
        return {"signal": signal, "status": "insufficient", "n": n,
                "regressions": [], "improvements": []}
    warmup = max(4, int(warmup))
    det = Detector(signal, warmup=warmup, window=64,
                   max_consecutive=1 << 30, clock=lambda: 0.0, **cfg)
    base = points[:-1]
    reps = -(-warmup // len(base))     # ceil: fill the minimum baseline
    seq = []
    for _ in range(reps):
        seq.extend(base)
    seq.append(points[-1])
    seen = set()
    regressions, improvements = [], []
    for idx, v in seq:
        a = det.update(v)
        if a is None or idx in seen:
            continue
        seen.add(idx)
        nonfinite = not math.isfinite(a.value)
        up = a.value > a.median if not nonfinite else True
        item = {"signal": signal, "run_index": idx,
                "value": a.value if nonfinite else round(a.value, 6),
                "median": round(a.median, 6),
                "z": round(a.z, 3) if math.isfinite(a.z) else "inf",
                "direction": "nonfinite" if nonfinite
                else ("up" if up else "down")}
        if nonfinite:
            # a NaN/inf measurement is a regression on EVERY signal —
            # a blown-up throughput number must not route to
            # "improvements" just because its worse-direction is down
            regressions.append(item)
        elif worse == "both" or ("up" if up else "down") == worse:
            regressions.append(item)
        else:
            improvements.append(item)
    return {"signal": signal, "status": "ok", "n": n,
            "regressions": regressions, "improvements": improvements}


def _run_name(rec: dict, idx: int) -> str:
    return str(rec.get("run") or rec.get("run_id") or f"run[{idx}]")


def compare_records(records: List[dict], warmup: int = 4,
                    kind: Optional[str] = None,
                    label: Optional[str] = None) -> dict:
    """Group ledger records by (kind, label), build the signal series,
    and detect.  Returns the full verdict dict (``regressions`` is the
    gate: empty = healthy)."""
    groups: Dict[tuple, List[tuple]] = {}
    for rec in records:
        if kind is not None and rec.get("kind") != kind:
            continue
        if label is not None and rec.get("label") != label:
            continue
        key = (str(rec.get("kind")), str(rec.get("label") or ""))
        groups.setdefault(key, []).append(rec)
    out = {"schema_version": 1, "groups": [], "regressions": [],
           "improvements": [], "insufficient": []}
    for (k, lb), recs in sorted(groups.items()):
        series = build_series(recs)
        gr = {"kind": k, "label": lb, "runs": len(recs),
              "run_names": [_run_name(r, i) for i, r in enumerate(recs)],
              "signals": []}
        for sig in sorted(series):
            s = series[sig]
            res = detect_series(sig, s["points"], s["cfg"],
                                warmup=warmup)
            gr["signals"].append(res)
            gname = f"{k}/{lb}" if lb else k
            for item in res["regressions"]:
                out["regressions"].append(
                    {**item, "group": gname,
                     "run": _run_name(recs[item["run_index"]],
                                      item["run_index"])})
            for item in res["improvements"]:
                out["improvements"].append(
                    {**item, "group": gname,
                     "run": _run_name(recs[item["run_index"]],
                                      item["run_index"])})
            if res["status"] == "insufficient":
                out["insufficient"].append(
                    {"group": gname, "signal": sig, "n": res["n"]})
        out["groups"].append(gr)
    return out


def format_compare(result: dict) -> str:
    lines = ["== perf_report compare =="]
    for gr in result["groups"]:
        gname = f"{gr['kind']}/{gr['label']}" if gr["label"] \
            else gr["kind"]
        ok = sum(1 for s in gr["signals"]
                 if s["status"] == "ok" and not s["regressions"])
        lines.append(f"group {gname}: {gr['runs']} run(s), "
                     f"{len(gr['signals'])} signal(s), {ok} clean")
    for item in result["regressions"]:
        lines.append(
            f"REGRESSION {item['group']} {item['signal']}: "
            f"run {item['run']} value={item['value']} "
            f"median={item['median']} z={item['z']} "
            f"({item['direction']})")
    for item in result["improvements"]:
        lines.append(
            f"improvement {item['group']} {item['signal']}: "
            f"run {item['run']} value={item['value']} "
            f"median={item['median']} z={item['z']}")
    if result["insufficient"]:
        sigs = ", ".join(f"{i['group']}:{i['signal']}({i['n']})"
                         for i in result["insufficient"][:10])
        more = len(result["insufficient"]) - 10
        lines.append(f"insufficient data: {sigs}"
                     + (f" (+{more} more)" if more > 0 else ""))
    lines.append(f"verdict: {len(result['regressions'])} regression(s), "
                 f"{len(result['improvements'])} improvement(s)")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _cmd_attribute(a) -> int:
    import trace_merge
    tmp = None
    cost = None
    if a.mini_train is not None and a.cost_json:
        print("perf_report attribute: --mini-train and --cost-json are "
              "mutually exclusive — the mini train analyzes its own "
              "step; joining a foreign cost model against its trace "
              "would gate the wrong program", file=sys.stderr)
        return 2
    if a.mini_train is not None:
        if a.trace_dir is None:
            tmp = tempfile.TemporaryDirectory(prefix="perf_report_")
            a.trace_dir = tmp.name
        cost = mini_train_cost(a.mini_train, a.trace_dir)
    elif a.cost_json:
        with open(a.cost_json) as f:
            doc = json.load(f)
        cost = doc.get("cost", doc) if isinstance(doc, dict) else None
    if a.trace_dir is None:
        print("perf_report attribute: need --mini-train or --trace-dir",
              file=sys.stderr)
        return 2
    paths = sorted(glob.glob(os.path.join(a.trace_dir,
                                          "trace_*.jsonl")))
    if not paths:
        print(f"perf_report attribute: no trace_*.jsonl under "
              f"{a.trace_dir}", file=sys.stderr)
        return 2
    rows = trace_merge.summarize(trace_merge.merge(paths))
    prof = attribute_profile(rows, cost, step_span=a.step_span,
                             top_k=a.top_k)
    if a.json:
        with open(a.json, "w") as f:
            json.dump(prof, f, indent=1, default=str)
    print(format_attribute(prof))
    if a.check:
        bad = check_profile(prof, top_k=a.top_k)
        if bad:
            for b in bad:
                print(f"CHECK FAILED: {b}", file=sys.stderr)
            return 1
        print(f"check ok: {len(prof.get('ops') or [])} op row(s) with "
              "measured ms and finite achieved FLOP/s")
    return 0


def _cmd_blame(a) -> int:
    from paddle_tpu.framework import blame
    tmp = None
    if a.mini_train is not None:
        if a.trace_dir is None:
            tmp = tempfile.TemporaryDirectory(prefix="perf_blame_")
            a.trace_dir = tmp.name
        import health_check
        health_check.mini_train_ps(a.mini_train, a.trace_dir)
    if a.trace_dir is None:
        print("perf_report blame: need --mini-train or --trace-dir",
              file=sys.stderr)
        return 2
    spans = blame.load_trace_dir(a.trace_dir)
    if not spans:
        print(f"perf_report blame: no trace_*.jsonl spans under "
              f"{a.trace_dir}", file=sys.stderr)
        return 2
    result = blame.compute_blame(spans, step_span=a.step_span)
    if a.json:
        with open(a.json, "w") as f:
            json.dump(result, f, indent=1, default=str)
    print(blame.format_blame(result))
    if a.check or a.expect_top:
        # the sum/link-integrity gates arm only under --check:
        # --expect-top alone must stay usable on input-stalled traces,
        # whose cycle legitimately exceeds their step-span total
        bad = blame.check(
            result, tolerance=a.tolerance if a.check else None,
            expect_top=a.expect_top)
        if bad:
            for b in bad:
                print(f"CHECK FAILED: {b}", file=sys.stderr)
            return 1
        parts = [f"check ok: {result['n_steps']} step(s)"]
        if a.check:
            blame_sum = sum(result["totals_ms"].values())
            parts.append(f"blame sum {blame_sum:.3f} ms vs step span "
                         f"total {result['span_ms_total']:.3f} ms, "
                         "0 unresolved links")
        if a.expect_top:
            parts.append(f"top category {result['top_category']}")
        print(", ".join(parts))
    return 0


def _cmd_compare(a) -> int:
    from paddle_tpu.framework.runlog import RunLedger
    records = RunLedger(a.ledger).read()
    if not records:
        print(f"perf_report compare: no readable records in {a.ledger}",
              file=sys.stderr)
        return 2
    result = compare_records(records, warmup=a.warmup, kind=a.kind,
                             label=a.label)
    if a.json:
        with open(a.json, "w") as f:
            json.dump(result, f, indent=1, default=str)
    print(format_compare(result))
    return 1 if len(result["regressions"]) > a.max_regressions else 0


def incident_rows(records: List[dict],
                  kind: Optional[str] = None) -> List[dict]:
    """Join ``kind=incident`` ledger records (the capture plane's index)
    with ``kind=incident_replay`` verdicts (``tools/replay.py
    --ledger``) by incident id: one row per captured incident carrying
    its trigger kind, step, first bad leaf, bundle path, and the latest
    replay/bisect outcome (``unreplayed`` when none landed yet)."""
    verdicts: Dict[Any, dict] = {}
    for rec in records:
        if rec.get("kind") != "incident_replay":
            continue
        v = rec.get("replay_verdict") or {}
        if v.get("id") is not None:
            verdicts[v["id"]] = v       # latest wins (ledger order)
    rows = []
    for rec in records:
        if rec.get("kind") != "incident":
            continue
        info = rec.get("incident") or {}
        if kind and info.get("kind") != kind:
            continue
        v = verdicts.get(info.get("id"))
        if v is None:
            replay = "unreplayed"
        elif v.get("mode") == "bisect":
            replay = (f"bisect:step={v.get('divergent_step')}"
                      f",leaf={v.get('leaf')}"
                      if v.get("divergent_step") is not None
                      else "bisect:clean")
        else:
            replay = "reproduced" if v.get("reproduced") \
                else "not_reproduced"
        rows.append({"id": info.get("id"), "kind": info.get("kind"),
                     "step": info.get("step"),
                     "first_bad_leaf": info.get("first_bad_leaf"),
                     "worker": info.get("worker"),
                     "bundle": info.get("bundle"),
                     "ts": rec.get("ts"), "replay": replay,
                     "verdict": v})
    return rows


def format_incidents(rows: List[dict]) -> str:
    lines = [f"== incidents: {len(rows)} captured =="]
    hdr = (("id", 4), ("kind", 22), ("step", 6), ("first_bad_leaf", 16),
           ("replay", 26), ("bundle", 0))
    lines.append("  ".join(n.ljust(w) for n, w in hdr))
    for r in rows:
        lines.append("  ".join([
            str(r.get("id", "?")).ljust(4),
            str(r.get("kind", "?"))[:22].ljust(22),
            str(r.get("step", "-")).ljust(6),
            str(r.get("first_bad_leaf") or "-")[:16].ljust(16),
            str(r.get("replay", "?"))[:26].ljust(26),
            str(r.get("bundle") or "-")]))
    return "\n".join(lines)


def _cmd_incidents(a) -> int:
    from paddle_tpu.framework.runlog import RunLedger
    records = RunLedger(a.ledger).read()
    rows = incident_rows(records, kind=a.kind)
    if a.json:
        with open(a.json, "w") as f:
            json.dump({"incidents": rows}, f, indent=1, default=str)
    print(format_incidents(rows))
    if not rows and not records:
        print(f"perf_report incidents: no readable records in "
              f"{a.ledger}", file=sys.stderr)
        return 2
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="perf_report.py", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = ap.add_subparsers(dest="cmd", required=True)

    at = sub.add_parser("attribute",
                        help="join a merged trace with the PTA106 "
                             "cost model into a measured op-profile")
    at.add_argument("--mini-train", type=int, default=None, metavar="N",
                    help="self-contained mode: traced N-step mini "
                         "train + TrainStep.analyze() in-process")
    at.add_argument("--trace-dir", default=None,
                    help="directory of trace_*.jsonl span files")
    at.add_argument("--cost-json", default=None,
                    help="structured PTA106 cost report (Report.cost "
                         "shape, or a profile JSON carrying one under "
                         "'cost')")
    at.add_argument("--step-span", default="train.step",
                    help="span name that executes the costed program "
                         "(default: train.step)")
    at.add_argument("--top-k", type=int, default=5,
                    help="op rows to attribute (default 5)")
    at.add_argument("--json", default=None, metavar="PATH",
                    help="write the joined profile JSON here")
    at.add_argument("--check", action="store_true",
                    help="gate: every top-k op must have a positive "
                         "measured ms and finite achieved FLOP/s")

    bl = sub.add_parser("blame",
                        help="causal critical-path blame: rebuild the "
                             "per-step dependency DAG from a trace "
                             "(span links) and collapse it into "
                             "per-category blocked-time vectors")
    bl.add_argument("--trace-dir", default=None,
                    help="directory of trace_*.jsonl span files")
    bl.add_argument("--mini-train", type=int, default=None, metavar="N",
                    help="self-contained mode: run the PS-backed "
                         "traced N-step mini train "
                         "(tools/health_check.py mini_train_ps) and "
                         "blame its own trace")
    bl.add_argument("--step-span", default="train.step",
                    help="span name of the consuming step "
                         "(default: train.step)")
    bl.add_argument("--json", default=None, metavar="PATH",
                    help="write the full blame result JSON here")
    bl.add_argument("--check", action="store_true",
                    help="gate: steps found, every link resolves, "
                         "blame categories sum to within --tolerance "
                         "of the measured step span")
    bl.add_argument("--tolerance", type=float, default=0.05,
                    help="blame-sum vs step-span tolerance for "
                         "--check (default 0.05)")
    bl.add_argument("--expect-top", default=None, metavar="CATEGORY",
                    help="gate: the named category must carry the "
                         "largest blame share (the chaos leg's "
                         "ps_wait assertion)")

    cp = sub.add_parser("compare",
                        help="Detector-based cross-run regression "
                             "gate over a run ledger")
    cp.add_argument("--ledger", required=True,
                    help="run ledger JSONL (runlog.RunLedger)")
    cp.add_argument("--kind", default=None,
                    help="only compare records of this kind")
    cp.add_argument("--label", default=None,
                    help="only compare records with this label")
    cp.add_argument("--warmup", type=int, default=4,
                    help="detector warmup samples (baseline prefix is "
                         "cycled to fill it; default 4)")
    cp.add_argument("--max-regressions", type=int, default=0,
                    help="tolerated named regressions (default 0)")
    cp.add_argument("--json", default=None, metavar="PATH",
                    help="write the full verdict JSON here")

    inc = sub.add_parser("incidents",
                         help="list captured incident bundles "
                              "(kind=incident ledger records) joined "
                              "with their replay/bisect verdicts "
                              "(kind=incident_replay)")
    inc.add_argument("--ledger", required=True,
                     help="run ledger JSONL (runlog.RunLedger)")
    inc.add_argument("--kind", default=None,
                     help="only incidents triggered by this flight "
                          "kind (e.g. train.nan_skip)")
    inc.add_argument("--json", default=None, metavar="PATH",
                     help="write the joined rows JSON here")

    a = ap.parse_args(argv)
    if a.cmd == "attribute":
        return _cmd_attribute(a)
    if a.cmd == "blame":
        return _cmd_blame(a)
    if a.cmd == "compare":
        return _cmd_compare(a)
    return _cmd_incidents(a)


if __name__ == "__main__":
    sys.exit(main())

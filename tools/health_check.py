#!/usr/bin/env python
"""Health report CLI — the perf health plane's decision surface.

Renders one report (text or JSON) from a metrics snapshot and/or a
trace directory, and exits nonzero when a gate trips — so CI and a
launcher wrapper consume the same verdict the detectors produce:

* **anomalies** — ``health_anomalies_total`` (+ the per-signal
  ``health_anomaly_<signal>_total`` split) from the streaming
  detectors (framework/health.py);
* **compiles** — ``jit_compiles_total`` / ``jit_cache_hits_total`` /
  per-cause counters, the ``compile_ms`` histogram, and the
  steady-state recompile count the compile-storm detector feeds;
* **memory** — ``device_mem_live_bytes`` / ``device_mem_peak_bytes``
  and the per-tag attribution gauges;
* **numerics** — the model-numerics plane (framework/numerics.py):
  global grad/param norms, update ratio, max-abs grad, non-finite
  step + NaN-skip counts, grad-norm detector anomalies, the sampled
  per-leaf grad norms, and (mini-train ``--nan-step``) the NaN
  provenance verdict;
* **spans** — the per-span-name aggregate table
  (``tools/trace_merge.py summarize``) over ``--trace-dir``.

Inputs:

* ``--metrics FILE`` — a ``monitor.snapshot()`` JSON file, or a
  Prometheus text rendering (``MetricsReporter`` output; gauges and
  ``_total`` counters are read, histogram summaries need the JSON
  form);
* ``--trace-dir DIR`` — per-process ``trace_*.jsonl`` span files;
* ``--mini-train N`` — self-contained mode: run a traced N-step mini
  train with the default detectors armed, snapshot, and evaluate
  in-process (the CI health lane; no files needed).

Gates (any trip → exit 1): ``--max-anomalies`` (default 0),
``--max-steady-recompiles`` (default 0), ``--max-input-stall``
(percent; off by default), ``--max-grad-anomalies`` (grad-norm
detector trips; off by default), ``--max-blame category=pct``
(repeatable; blame-share ceiling per causal category from
``framework/blame.py`` — requires a trace), and — implicit with
``--nan-step`` — the NaN-provenance verdict (the seeded fault must be
attributed to the poisoned leaf).

Usage::

    python tools/health_check.py --mini-train 30
    python tools/health_check.py --mini-train 30 --numerics \\
        --max-grad-anomalies 0
    python tools/health_check.py --mini-train 30 --nan-step 20
    python tools/health_check.py --metrics snap.json --trace-dir /tmp/tr
    python tools/health_check.py --metrics metrics.prom --format json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from typing import Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# the --zero leg shards over a dp=2 mesh of CPU virtual devices; the
# flag only takes effect if it lands before jax's backend initializes
# (set here, at import, because the paddle import chain pulls jax in
# during argument validation — a no-op for non-CPU backends and for
# embedders that already initialized jax)
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8"
                               ).strip()

__all__ = ["load_metrics", "build_report", "evaluate_gates",
           "parse_max_blame", "format_report", "mini_train",
           "mini_train_ps", "mini_train_zero", "build_incident_step",
           "main"]


# ---------------------------------------------------------------------------
# the two-branch numerics net — module-level so the postmortem plane's
# replay (tools/replay.py) can rebuild the exact step surface the
# mini-train recorded an incident on
# ---------------------------------------------------------------------------

def _two_branch_net():
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn

    class _TwoBranch(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(8, 4)
            self.aux_w = self.create_parameter(
                [4], default_initializer=paddle.nn.initializer
                .Constant(0.1))

        def forward(self, x, z):
            return self.fc(x), (self.aux_w * z).sum()

    return _TwoBranch()


def _two_branch_loss(m, x, z, y):
    out, aux = m(x, z)
    return ((out - y) ** 2).mean() + 1e-3 * aux


def build_incident_step(seed: int = 0, lr: float = 0.05,
                        max_consecutive_bad: int = 3):
    """Replay builder (``incident.set_program`` ref
    ``"health_check:build_incident_step"``): the resilient-wrapped
    two-branch numerics step the mini-train records incidents on.
    Registers itself as this process's program descriptor, so any
    bundle captured off the returned step replays standalone."""
    import paddle_tpu as paddle
    from paddle_tpu.framework import incident
    from paddle_tpu.framework.resilient import ResilientTrainStep
    from paddle_tpu.jit import TrainStep
    paddle.seed(int(seed))
    net = _two_branch_net()
    opt = paddle.optimizer.SGD(learning_rate=float(lr),
                               parameters=net.parameters())
    incident.set_program("health_check:build_incident_step", seed=int(seed),
                         lr=float(lr),
                         max_consecutive_bad=int(max_consecutive_bad))
    return ResilientTrainStep(TrainStep(net, _two_branch_loss, opt),
                              max_consecutive_bad=int(max_consecutive_bad))


# ---------------------------------------------------------------------------
# input loading
# ---------------------------------------------------------------------------

def _parse_prometheus(text: str) -> dict:
    """Reduce a Prometheus text rendering to the snapshot shape: plain
    samples become stats; histogram ``_sum``/``_count`` pairs become
    minimal histogram records (no percentiles — the JSON snapshot form
    carries those)."""
    stats = {}
    for line in text.splitlines():
        if not line or line.startswith("#") or "{" in line:
            continue
        parts = line.split()
        if len(parts) < 2:
            continue
        try:
            stats[parts[0]] = float(parts[1])
        except ValueError:
            continue
    hists = {}
    for name, v in list(stats.items()):
        if name.endswith("_count") and name[:-len("_count")] + "_sum" \
                in stats:
            base = name[:-len("_count")]
            count = int(v)
            total = stats[base + "_sum"]
            hists[base] = {"count": count, "sum": total,
                           "mean": total / count if count else 0.0}
    return {"stats": stats, "histograms": hists}


def load_metrics(path: str) -> dict:
    """Load a metrics snapshot: ``monitor.snapshot()`` JSON or a
    Prometheus text file (sniffed by the leading character)."""
    with open(path) as f:
        text = f.read()
    if text.lstrip().startswith("{"):
        snap = json.loads(text)
        snap.setdefault("stats", {})
        snap.setdefault("histograms", {})
        return snap
    return _parse_prometheus(text)


# ---------------------------------------------------------------------------
# report assembly
# ---------------------------------------------------------------------------

def build_report(snap: dict, trace_dir: Optional[str] = None,
                 health_snapshot: Optional[dict] = None,
                 step_span: str = "train.step") -> dict:
    """Fold a metrics snapshot (+ optional trace dir and live health
    state) into the report dict the gates and renderers consume.
    ``step_span`` names the per-step span blame anchors on
    (``zero.step`` for the ZeRO leg)."""
    stats = snap.get("stats", {})
    hists = snap.get("histograms", {})

    anomalies = {k: int(v) for k, v in stats.items()
                 if k.startswith("health_anomaly_") and k.endswith("_total")}
    compiles = {
        "jit_compiles_total": int(stats.get("jit_compiles_total", 0)),
        "jit_cache_hits_total": int(stats.get("jit_cache_hits_total", 0)),
        "jit_recompiles_steady_total": int(
            stats.get("jit_recompiles_steady_total", 0)),
        "by_cause": {k[len("jit_compiles_"):-len("_total")]: int(v)
                     for k, v in stats.items()
                     if k.startswith("jit_compiles_") and
                     k.endswith("_total") and k != "jit_compiles_total"},
        "compile_ms": hists.get("compile_ms"),
    }
    memory = {
        "live_bytes": int(stats.get("device_mem_live_bytes", 0)),
        "peak_bytes": int(stats.get("device_mem_peak_bytes", 0)),
        "tags": {k[len("device_mem_"):-len("_bytes")]: int(v)
                 for k, v in stats.items()
                 if k.startswith("device_mem_") and k.endswith("_bytes")
                 and k not in ("device_mem_live_bytes",
                               "device_mem_peak_bytes")},
    }
    def _leaf_split(k, prefix):
        # per-leaf numerics gauges: "numerics_grad_norm[fc.weight]"
        return k[len(prefix) + 1:-1] if k.startswith(prefix + "[") \
            and k.endswith("]") else None

    numerics = {
        "grad_norm": stats.get("numerics_grad_norm"),
        "param_norm": stats.get("numerics_param_norm"),
        "update_ratio": stats.get("numerics_update_ratio"),
        "max_abs_grad": stats.get("numerics_max_abs_grad"),
        "nonfinite_steps": int(
            stats.get("numerics_nonfinite_steps_total", 0)),
        "nan_skips": int(stats.get("train_nan_skips_total", 0)),
        "observe_errors": int(
            stats.get("numerics_observe_errors_total", 0)),
        "grad_anomalies": int(
            stats.get("health_anomaly_grad_norm_total", 0)),
        "grad_norm_hist": hists.get("grad_norm"),
        "per_leaf_grad_norm": {
            leaf: v for k, v in stats.items()
            if (leaf := _leaf_split(k, "numerics_grad_norm"))
            is not None},
    }
    report = {
        "anomalies": {
            "total": int(stats.get("health_anomalies_total", 0)),
            "by_signal": anomalies,
            "observe_errors": int(
                stats.get("health_observe_errors_total", 0)),
        },
        "compiles": compiles,
        "memory": memory,
        "numerics": numerics,
        "steps": {
            "train_steps_total": int(stats.get("train_steps_total", 0)),
            "train_step_ms": hists.get("train_step_ms"),
            "input_stall_pct": stats.get("input_stall_pct"),
        },
    }
    if health_snapshot is not None:
        report["detectors"] = health_snapshot.get("signals", {})
        report["compiles"]["sites"] = health_snapshot.get("compile", {})
    if trace_dir:
        import glob

        import trace_merge
        paths = sorted(glob.glob(os.path.join(trace_dir,
                                              "trace_*.jsonl")))
        if paths:
            report["spans"] = trace_merge.summarize(
                trace_merge.merge(paths))
        from paddle_tpu.framework import blame
        spans = blame.load_trace_dir(trace_dir)
        res = blame.compute_blame(spans, step_span=step_span)
        if res["n_steps"]:
            # the FULL result (edges trimmed): evaluate_gates reads
            # shares/per_step_ms, and main() hands the same dict to
            # runlog.capture(blame_result=) so the ledger record does
            # not re-read and re-analyze the whole trace dir
            report["blame"] = {**res, "edges": res["edges"][:5]}
    return report


def parse_max_blame(specs) -> dict:
    """Parse repeated ``--max-blame category=pct`` specs into
    ``{category: pct}``; unknown categories and unparseable values are
    errors (a typo'd gate that silently never trips gates nothing)."""
    from paddle_tpu.framework.blame import CATEGORIES
    out = {}
    for spec in specs or ():
        if "=" not in spec:
            raise ValueError(
                f"--max-blame expects category=pct, got {spec!r}")
        cat, _, pct = spec.partition("=")
        cat = cat.strip()
        if cat not in CATEGORIES:
            raise ValueError(f"--max-blame: unknown category {cat!r} "
                             f"(one of {CATEGORIES})")
        out[cat] = float(pct)
    return out


def evaluate_gates(report: dict, max_anomalies: int = 0,
                   max_steady_recompiles: int = 0,
                   max_input_stall: Optional[float] = None,
                   max_grad_anomalies: Optional[int] = None,
                   max_blame: Optional[dict] = None) -> list:
    """Returns the list of tripped-gate descriptions (empty = healthy)."""
    tripped = []
    n_anom = report["anomalies"]["total"]
    if n_anom > max_anomalies:
        tripped.append(f"anomalies: {n_anom} > {max_anomalies} "
                       f"(signals: {report['anomalies']['by_signal']})")
    n_re = report["compiles"]["jit_recompiles_steady_total"]
    if n_re > max_steady_recompiles:
        tripped.append(f"steady-state recompiles: {n_re} > "
                       f"{max_steady_recompiles} "
                       f"(causes: {report['compiles']['by_cause']})")
    stall = report["steps"].get("input_stall_pct")
    if max_input_stall is not None and stall is not None and \
            stall > max_input_stall:
        tripped.append(f"input stall: {stall:.2f}% > {max_input_stall}%")
    num = report.get("numerics") or {}
    if max_grad_anomalies is not None:
        n_g = int(num.get("grad_anomalies", 0))
        if n_g > max_grad_anomalies:
            tripped.append(f"grad-norm anomalies: {n_g} > "
                           f"{max_grad_anomalies}")
    prov = num.get("provenance")
    if prov is not None and not prov.get("ok"):
        # the seeded-NaN mini train gates itself: the nan_skip flight
        # event must name the poisoned leaf
        tripped.append(
            f"NaN provenance: expected first_bad_leaf="
            f"{prov.get('expected')!r}, got {prov.get('got')!r} "
            f"(nan_skips: {prov.get('nan_skips')})")
    if max_blame:
        bl = report.get("blame")
        if bl is None:
            tripped.append("blame gate set but no blame section "
                           "(no trace dir, or no step spans traced)")
        else:
            for cat, limit in sorted(max_blame.items()):
                pct = 100.0 * float((bl.get("shares") or {})
                                    .get(cat, 0.0))
                if pct > limit:
                    tripped.append(
                        f"blame share {cat}: {pct:.2f}% > {limit}% "
                        f"({bl.get('per_step_ms', {}).get(cat)} "
                        f"ms/step)")
    return tripped


def format_report(report: dict, tripped: list) -> str:
    a, c, m, s = (report["anomalies"], report["compiles"],
                  report["memory"], report["steps"])
    lines = ["== health report =="]
    lines.append(f"anomalies: {a['total']}"
                 + (f"  by signal: {a['by_signal']}" if a["by_signal"]
                    else "")
                 + (f"  (observe errors: {a['observe_errors']})"
                    if a["observe_errors"] else ""))
    hit_line = (f"compiles: {c['jit_compiles_total']}  cache hits: "
                f"{c['jit_cache_hits_total']}  steady recompiles: "
                f"{c['jit_recompiles_steady_total']}")
    if c["by_cause"]:
        hit_line += f"  by cause: {c['by_cause']}"
    lines.append(hit_line)
    cms = c.get("compile_ms")
    if cms:
        lines.append(f"compile_ms: count={cms.get('count')} "
                     f"mean={cms.get('mean')} max={cms.get('max')}")
    if m["peak_bytes"]:
        mb = 1.0 / (1 << 20)
        tag_txt = "  ".join(f"{t}={b * mb:.2f}MB"
                            for t, b in sorted(m["tags"].items()))
        lines.append(f"device memory: live={m['live_bytes'] * mb:.2f}MB "
                     f"peak={m['peak_bytes'] * mb:.2f}MB"
                     + (f"  [{tag_txt}]" if tag_txt else ""))
    step_txt = f"steps: {s['train_steps_total']}"
    if s.get("train_step_ms"):
        h = s["train_step_ms"]
        step_txt += (f"  step_ms: mean={h.get('mean')} p99={h.get('p99')} "
                     f"max={h.get('max')}")
    if s.get("input_stall_pct") is not None:
        step_txt += f"  input_stall: {s['input_stall_pct']:.2f}%"
    lines.append(step_txt)
    n = report.get("numerics") or {}
    if n.get("grad_norm") is not None:
        num_txt = (f"numerics: grad_norm={n['grad_norm']:.4g} "
                   f"param_norm={n['param_norm']:.4g} "
                   f"update_ratio={n['update_ratio']:.4g} "
                   f"max_abs_grad={n['max_abs_grad']:.4g}")
        if n.get("nonfinite_steps") or n.get("nan_skips"):
            num_txt += (f"  nonfinite_steps={n['nonfinite_steps']} "
                        f"nan_skips={n['nan_skips']}")
        if n.get("grad_anomalies"):
            num_txt += f"  grad_anomalies={n['grad_anomalies']}"
        if n.get("observe_errors"):
            num_txt += f"  (observe errors: {n['observe_errors']})"
        lines.append(num_txt)
        prov = n.get("provenance")
        if prov is not None:
            lines.append(f"  provenance: expected={prov.get('expected')} "
                         f"got={prov.get('got')} "
                         f"ok={bool(prov.get('ok'))}")
        leaves = n.get("per_leaf_grad_norm") or {}
        if leaves:
            top = sorted(leaves.items(), key=lambda kv: -abs(kv[1]
                         if kv[1] == kv[1] else float("inf")))[:5]
            lines.append("  top leaf grad norms: "
                         + "  ".join(f"{k}={v:.4g}" for k, v in top))
    bl = report.get("blame")
    if bl:
        shares = bl.get("shares") or {}
        per = bl.get("per_step_ms") or {}
        parts = "  ".join(
            f"{c}={100.0 * shares.get(c, 0.0):.1f}%"
            f"({per.get(c, 0.0):.2f}ms)"
            for c in sorted(shares, key=lambda c: -shares[c])
            if shares.get(c, 0.0) > 0)
        lines.append(f"blame ({bl.get('n_steps')} steps, top="
                     f"{bl.get('top_category')}): {parts}")
        if bl.get("unresolved_links"):
            lines.append(
                f"  UNRESOLVED LINKS: {bl['unresolved_links']}")
    if report.get("spans"):
        import trace_merge
        lines.append("-- span summary --")
        lines.append(trace_merge.format_summary(report["spans"]))
    if tripped:
        lines.append("TRIPPED:")
        lines += [f"  - {t}" for t in tripped]
    else:
        lines.append("healthy: no gate tripped")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# self-contained mini-train mode (the CI health lane)
# ---------------------------------------------------------------------------

def mini_train(n_steps: int, trace_dir: str, numerics: bool = False,
               nan_step: Optional[int] = None):
    """Run a traced, health-armed N-step mini train and return
    ``(monitor.snapshot(), provenance-or-None)``.  Fixed seeds and
    shapes: a healthy run compiles exactly once per jit site and trips
    zero detectors — which is precisely what the CI gate asserts.

    ``numerics=True`` arms the model-numerics plane (FLAGS_numerics +
    the grad-norm drift detectors) on a two-branch model — a dense
    head plus an independent ``aux_w * z`` branch — wrapped in
    ``ResilientTrainStep``.  ``nan_step=K`` additionally NaN-poisons
    ONLY the aux branch's input at step K (chaos ``train.step_grads``
    with ``payload_index``), so exactly one leaf's gradient goes
    non-finite: the returned provenance dict records whether the
    ``train.nan_skip`` flight event named that leaf (``aux_w``), the
    run must still finish on finite losses (skip-and-restore), and the
    grad-norm detector's baseline stays clean — the CI numerics lane's
    seeded-NaN leg."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.framework import chaos, health, monitor
    from paddle_tpu.framework import numerics as numerics_mod
    from paddle_tpu.framework.flags import get_flags, set_flags
    from paddle_tpu.framework.observability import flight, tracer
    from paddle_tpu.jit import TrainStep

    for signal, kw in health.DEFAULT_SIGNALS.items():
        health.watch(signal, **dict(kw))
    saved_flags = get_flags("numerics")
    provenance = None
    tracer.enable(trace_dir, label="health_check")
    try:
        paddle.seed(0)
        rng = np.random.default_rng(0)
        if not numerics:
            net = nn.Linear(8, 4)
            opt = paddle.optimizer.SGD(learning_rate=0.05,
                                       parameters=net.parameters())
            step = TrainStep(net,
                             lambda m, x, y: ((m(x) - y) ** 2).mean(),
                             opt)
            x = paddle.to_tensor(rng.standard_normal((16, 8))
                                 .astype(np.float32))
            y = paddle.to_tensor(rng.standard_normal((16, 4))
                                 .astype(np.float32))
            losses = [float(step(x, y)) for _ in range(n_steps)]
            assert all(np.isfinite(losses)), \
                f"mini train diverged: {losses}"
            params = net.parameters()
        else:
            set_flags({"numerics": True})
            # the replay builder — incidents captured off this step
            # carry the health_check:build_incident_step descriptor
            step = build_incident_step(seed=0, lr=0.05)
            net = step.step.model
            x = paddle.to_tensor(rng.standard_normal((16, 8))
                                 .astype(np.float32))
            z = paddle.to_tensor(rng.standard_normal((4,))
                                 .astype(np.float32))
            y = paddle.to_tensor(rng.standard_normal((16, 4))
                                 .astype(np.float32))
            if nan_step is not None:
                # poison ONLY the aux branch's input (payload index 1 =
                # z): the NaN reaches exactly aux_w's gradient
                chaos.arm("train.step_grads", mode="nan",
                          nth=int(nan_step), n_times=1, payload_index=1)
            losses = [float(step(x, z, y)) for _ in range(n_steps)]
            assert np.isfinite(losses[-1]), \
                f"mini train did not recover: {losses[-5:]}"
            if nan_step is not None:
                skips = flight.recent(50, kind="train.nan_skip")
                got = skips[-1]["attrs"].get("first_bad_leaf") \
                    if skips else None
                # the drift detector must fire AT the poisoned step
                # too (a non-finite grad norm is an anomaly by
                # definition — Detector's z=inf rule)
                ga = int(monitor.get_stat(
                    "health_anomaly_grad_norm_total"))
                provenance = {"expected": "aux_w", "got": got,
                              "nan_skips": len(skips),
                              "grad_anomalies": ga,
                              "ok": bool(skips) and got == "aux_w"
                              and step.skipped_steps == 1
                              and ga >= 1}
            params = net.parameters()
        health.memory.sample(tags={
            "params": sum(int(p._data.nbytes) for p in params)})
    finally:
        tracer.disable()
        if numerics:
            set_flags(saved_flags)
            chaos.disarm("train.step_grads")
            numerics_mod.reset()
    return monitor.snapshot(), provenance


def mini_train_ps(n_steps: int, trace_dir: str):
    """PS-backed mini-train leg: the same decision surface as
    :func:`mini_train`, but the embedding rows live on an in-process
    ``PsServer`` reached over localhost TCP, so the run exercises (and
    records) real ``ps.rpc`` traffic — the observatory lane injects
    ``ps.rpc`` latency into this leg via ``FLAGS_chaos_spec``.  An
    injection armed from step 0 is a LEVEL SHIFT: the in-run detector's
    warmup adopts it (this run's gates stay green), and only the
    cross-run ledger compare (``tools/perf_report.py compare``) can see
    it — which is exactly what that lane proves.  Deterministic: fixed
    seeds, fixed shapes, sync mode, no prefetch."""
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
    from paddle_tpu.framework import health, monitor
    from paddle_tpu.framework.observability import tracer

    from paddle_tpu.models import WideDeepHost

    for signal, kw in health.DEFAULT_SIGNALS.items():
        health.watch(signal, **dict(kw))
    tracer.enable(trace_dir, label="health_check_ps")
    table = HostEmbeddingTable(256, 9, optimizer="sgd",
                               learning_rate=0.05, seed=0)
    srv = PsServer({"emb": table}, port=0).start()
    cli = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype="f32",
                   backoff_base=0.01)
    try:
        paddle.seed(0)
        emb = DistributedEmbedding(
            256, 9, mode="sync",
            table=RemoteEmbeddingTable(cli, "emb", 9))
        bs = 8
        model = WideDeepHost(embedding_dim=8, num_fields=4, dense_dim=3,
                             hidden=(16,))
        opt = optimizer.Adam(learning_rate=1e-2,
                             parameters=model.parameters())

        def loss_fn(m, rows, x, y):
            return F.binary_cross_entropy_with_logits(
                m(rows, x), y).mean()

        step = PSTrainStep(model, loss_fn, opt, emb,
                           transfer_dtype="float32", prefetch_depth=0)
        rng = np.random.default_rng(3)
        ids = rng.integers(0, 256,
                           size=(n_steps, bs, 4)).astype(np.int64)
        x = paddle.to_tensor(rng.standard_normal((bs, 3))
                             .astype(np.float32))
        y = paddle.to_tensor(rng.random((bs, 1)).astype(np.float32))
        losses = [float(step(ids[n], x, y)) for n in range(n_steps)]
        assert all(np.isfinite(losses)), \
            f"PS mini train diverged: {losses[-5:]}"
        step.flush()
    finally:
        try:
            cli.bye()
        finally:
            srv.shutdown()
            tracer.disable()
    return monitor.snapshot(), None


def mini_train_zero(n_steps: int, trace_dir: str, wire: str = "f32",
                    ring: bool = False):
    """ZeRO-sharded mini-train leg: the same decision surface as
    :func:`mini_train`, but the step is the fused
    ``ShardedUpdateTrainStep`` on a dp=2 mesh of CPU virtual devices,
    so the run exercises (and records) the fused reduce-scatter /
    all-gather pair.  Per-step wire bytes land on the
    ``zero_collective_bytes_per_step`` stat (whitelisted into the
    ledger summary — the observatory's wire-byte series), and under
    the armed tracer the ``zero.reduce_scatter`` / ``zero.all_gather``
    leg spans fence the dispatch, so the fused collectives' wall time
    claims blame as ``collective``.  ``wire``/``ring`` select the
    collective codec and the chunked ring schedule (passed to the step
    directly — no flag mutation).  Deterministic: fixed seeds, fixed
    shapes."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import optimizer
    from paddle_tpu.framework import health, monitor
    from paddle_tpu.framework.observability import tracer
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.zero import ShardedUpdateTrainStep

    if len(jax.devices()) < 2:
        raise RuntimeError(
            "--zero needs >= 2 devices for a dp=2 mesh (jax "
            "initialized before the CPU virtual-device flag could be "
            "set; export XLA_FLAGS="
            "--xla_force_host_platform_device_count=8)")
    for signal, kw in health.DEFAULT_SIGNALS.items():
        health.watch(signal, **dict(kw))
    tracer.enable(trace_dir, label="health_check_zero")
    try:
        paddle.seed(0)
        rng = np.random.default_rng(0)
        mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
        model = nn.Sequential(nn.Linear(32, 64), nn.ReLU(),
                              nn.Linear(64, 32))
        opt = optimizer.Momentum(learning_rate=0.01, momentum=0.9,
                                 parameters=model.parameters())
        step = ShardedUpdateTrainStep(
            model, lambda m, x, y: ((m(x) - y) ** 2).mean(), opt,
            mesh=mesh, wire_dtype=wire, ring=ring)
        x = paddle.to_tensor(rng.standard_normal((8, 32))
                             .astype(np.float32))
        y = paddle.to_tensor(rng.standard_normal((8, 32))
                             .astype(np.float32))
        losses = [float(step(x, y)) for _ in range(n_steps)]
        assert all(np.isfinite(losses)), \
            f"ZeRO mini train diverged: {losses[-5:]}"
        health.memory.sample(tags={
            "params": sum(int(p._data.nbytes)
                          for p in model.parameters())})
    finally:
        tracer.disable()
    return monitor.snapshot(), None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="health_check.py", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--metrics", default=None,
                    help="metrics snapshot: monitor.snapshot() JSON or "
                         "Prometheus text (MetricsReporter output)")
    ap.add_argument("--trace-dir", default=None,
                    help="directory of trace_*.jsonl span files "
                         "(adds the per-span summary to the report)")
    ap.add_argument("--mini-train", type=int, default=None, metavar="N",
                    help="self-contained mode: run a traced, "
                         "health-armed N-step mini train and evaluate "
                         "its own snapshot (the CI health lane)")
    ap.add_argument("--numerics", action="store_true",
                    help="mini-train option: arm the model-numerics "
                         "plane (FLAGS_numerics + grad-norm drift "
                         "detectors) on a two-branch model under "
                         "ResilientTrainStep")
    ap.add_argument("--nan-step", type=int, default=None, metavar="K",
                    help="mini-train option (implies --numerics): NaN-"
                         "poison only the aux branch's input at step K "
                         "and gate that train.nan_skip names that "
                         "branch's leaf as first_bad_leaf (the CI "
                         "numerics lane's seeded-NaN leg)")
    ap.add_argument("--ps", action="store_true",
                    help="mini-train option: run the PS-backed leg "
                         "(in-process PsServer over localhost TCP) so "
                         "real ps.rpc traffic feeds the detectors and "
                         "the run record")
    ap.add_argument("--zero", action="store_true",
                    help="mini-train option: run the ZeRO-sharded leg "
                         "(fused reduce-scatter/all-gather on a dp=2 "
                         "mesh of CPU virtual devices) so collective "
                         "wire bytes and collective blame feed the "
                         "detectors and the run record")
    ap.add_argument("--zero-wire", default="f32",
                    choices=("f32", "bf16", "int8", "int4"),
                    help="--zero option: collective wire codec "
                         "(default f32)")
    ap.add_argument("--zero-ring", action="store_true",
                    help="--zero option: use the fused chunked-ring "
                         "collectives (parallel/ring.py) instead of "
                         "the native psum_scatter/all_gather pair")
    ap.add_argument("--ledger", default=None, metavar="PATH",
                    help="append a RunRecord (runlog.capture) for this "
                         "mini train to the run ledger at PATH — the "
                         "perf observatory's producer hook")
    ap.add_argument("--run-label", default=None,
                    help="RunRecord label (default: 'ps' or 'dense' "
                         "per the mini-train variant)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--max-anomalies", type=int, default=0,
                    help="gate: tolerated health_anomalies_total "
                         "(default 0)")
    ap.add_argument("--max-steady-recompiles", type=int, default=0,
                    help="gate: tolerated post-warmup recompiles "
                         "(default 0)")
    ap.add_argument("--max-input-stall", type=float, default=None,
                    help="gate: tolerated input_stall_pct (off by "
                         "default)")
    ap.add_argument("--max-grad-anomalies", type=int, default=None,
                    help="gate: tolerated grad-norm detector anomalies "
                         "(health_anomaly_grad_norm_total; off by "
                         "default)")
    ap.add_argument("--max-blame", action="append", default=None,
                    metavar="CATEGORY=PCT",
                    help="gate (repeatable): tolerated blame share per "
                         "category from the causal critical-path "
                         "analysis, e.g. --max-blame ps_wait=30 — "
                         "requires a trace (mini-train or "
                         "--trace-dir); categories: compute, ps_wait, "
                         "ingest_wait, collective, compile, other")
    a = ap.parse_args(argv)
    try:
        max_blame = parse_max_blame(a.max_blame)
    except ValueError as e:
        ap.error(str(e))
    if a.metrics is None and a.mini_train is None:
        ap.error("nothing to check: pass --metrics or --mini-train")
    if a.metrics is not None and a.mini_train is not None:
        ap.error("--metrics and --mini-train are mutually exclusive: "
                 "the mini train evaluates its own fresh snapshot")
    if a.nan_step is not None:
        a.numerics = True
    if a.numerics and a.mini_train is None:
        ap.error("--numerics/--nan-step are mini-train options")
    if a.ps and a.mini_train is None:
        ap.error("--ps is a mini-train option")
    if a.ps and a.numerics:
        ap.error("--ps and --numerics/--nan-step are separate "
                 "mini-train legs — run them as two invocations")
    if a.zero and a.mini_train is None:
        ap.error("--zero is a mini-train option")
    if a.zero and (a.ps or a.numerics):
        ap.error("--zero, --ps and --numerics/--nan-step are separate "
                 "mini-train legs — run them as separate invocations")
    if (a.zero_ring or a.zero_wire != "f32") and not a.zero:
        ap.error("--zero-wire/--zero-ring are --zero options")
    if a.ledger is not None and a.mini_train is None:
        ap.error("--ledger records a mini train; pass --mini-train")

    health_snapshot = None
    provenance = None
    if a.mini_train is not None:
        if a.trace_dir is None:
            tmp = tempfile.TemporaryDirectory(prefix="health_check_")
            a.trace_dir = tmp.name          # kept alive by the local ref
        if a.ps:
            snap, provenance = mini_train_ps(a.mini_train, a.trace_dir)
        elif a.zero:
            snap, provenance = mini_train_zero(
                a.mini_train, a.trace_dir, wire=a.zero_wire,
                ring=a.zero_ring)
        else:
            snap, provenance = mini_train(
                a.mini_train, a.trace_dir, numerics=a.numerics,
                nan_step=a.nan_step)
        from paddle_tpu.framework import health
        health_snapshot = health.snapshot()
    else:
        snap = load_metrics(a.metrics)

    report = build_report(snap, trace_dir=a.trace_dir,
                          health_snapshot=health_snapshot,
                          step_span="zero.step" if a.zero
                          else "train.step")
    if provenance is not None:
        report["numerics"]["provenance"] = provenance
    tripped = evaluate_gates(
        report, max_anomalies=a.max_anomalies,
        max_steady_recompiles=a.max_steady_recompiles,
        max_input_stall=a.max_input_stall,
        max_grad_anomalies=a.max_grad_anomalies,
        max_blame=max_blame)
    report["tripped"] = tripped
    if a.ledger is not None:
        # one RunRecord per mini train, appended AFTER the gates ran so
        # the verdict rides along; RunLedger.append never raises
        from paddle_tpu.framework import runlog
        label = a.run_label or ("ps" if a.ps else
                                "zero" if a.zero else
                                "numerics" if a.numerics else "dense")
        rec = runlog.capture("health_check", label=label,
                             trace_dir=a.trace_dir,
                             blame_result=report.get("blame"),
                             extra={"steps": a.mini_train,
                                    "tripped": tripped})
        runlog.RunLedger(a.ledger).append(rec)
    if a.format == "json":
        print(json.dumps(report, indent=1, default=str))
    else:
        print(format_report(report, tripped))
    return 1 if tripped else 0


if __name__ == "__main__":
    sys.exit(main())

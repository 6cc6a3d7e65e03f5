"""Perf health plane: streaming detectors (EWMA + robust MAD z-score)
under chaos, recompile-cause attribution, device-memory tracking, the
flight-recorder satellites, trace_merge --summary, and the
health_check decision surface.

Acceptance (deterministic, CPU-only): a PS mini-train with injected
``ps.rpc`` latency at step S is flagged by the RPC-latency detector
within 5 steps (anomaly in the flight recorder +
``health_anomalies_total`` incremented), while the same train without
injection reports zero anomalies and zero post-warmup recompiles
through ``tools/health_check.py``'s gates."""
import json
import os
import signal
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import optimizer
from paddle_tpu.framework import chaos, health, monitor
from paddle_tpu.framework.flags import get_flags, set_flags
from paddle_tpu.framework.observability import flight, tracer
from paddle_tpu.jit import TrainStep, to_static

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


@pytest.fixture(autouse=True)
def _fresh_plane():
    chaos.reset(0)
    health.reset()
    for s in ("health_anomalies_total", "health_observe_errors_total",
              "jit_compiles_total", "jit_cache_hits_total",
              "jit_recompiles_steady_total"):
        monitor.reset_stat(s)
    yield
    chaos.reset(0)
    health.reset()


# ---------------------------------------------------------------------------
# Detector: the streaming EWMA + MAD z-score core
# ---------------------------------------------------------------------------

class TestDetector:
    def test_warmup_never_flags(self):
        d = health.Detector("t", warmup=8)
        # wild swings inside warmup: baseline building, no judgment
        assert all(d.update(v) is None for v in [1, 100, 1, 100, 1, 100,
                                                 1, 100])

    def test_spike_flags_and_baseline_stays_clean(self):
        d = health.Detector("t", warmup=8, clock=lambda: 42.0)
        for i in range(20):
            assert d.update(1.0 + 0.01 * (i % 5)) is None
        a = d.update(50.0)
        assert a is not None and a.signal == "t" and a.ts == 42.0
        assert abs(a.z) >= d.z_threshold
        # the anomalous value did NOT enter the baseline: the next
        # normal value is normal, and a second spike still flags
        assert d.update(1.0) is None
        assert d.update(50.0) is not None
        assert d.anomalies == 2

    def test_steady_stream_no_false_positives(self):
        rng = np.random.default_rng(0)
        d = health.Detector("t", warmup=16)
        vals = 10.0 + rng.normal(0, 0.5, size=500)
        assert sum(d.update(v) is not None for v in vals) == 0

    def test_deterministic_same_sequence_same_anomalies(self):
        rng = np.random.default_rng(1)
        vals = list(10.0 + rng.normal(0, 0.3, size=100))
        vals[40] = vals[77] = 200.0

        def run():
            d = health.Detector("t", warmup=8)
            return [i for i, v in enumerate(vals)
                    if d.update(v) is not None]
        first = run()
        assert first == run() and 40 in first and 77 in first

    def test_flat_baseline_floors_absorb_jitter(self):
        d = health.Detector("t", warmup=8, rel_floor=0.25)
        for _ in range(20):
            assert d.update(100.0) is None     # MAD == 0: floors hold
        assert d.update(101.0) is None         # within the rel floor
        assert d.update(10000.0) is not None   # a real spike still trips

    def test_rebaseline_after_sustained_shift(self):
        d = health.Detector("t", warmup=4, max_consecutive=6)
        for _ in range(10):
            d.update(1.0)
        flagged = sum(d.update(100.0) is not None for _ in range(20))
        # the level shift alarms for a bounded burst, then is adopted
        assert d.rebaselines >= 1
        assert flagged <= 6 + 1
        assert d.update(100.0) is None         # the new normal

    def test_warmup_floor_enforced(self):
        with pytest.raises(ValueError, match="warmup"):
            health.Detector("t", warmup=1)

    def test_read_api_last_value_and_baseline(self):
        d = health.Detector("t", warmup=4, window=8)
        assert d.last_value() is None and d.baseline() is None
        for v in (10.0, 10.0, 12.0, 10.0, 11.0):
            d.update(v)
        assert d.last_value() == 11.0
        # robust baseline = the window median the z-score judges against
        assert d.baseline() == pytest.approx(10.0)
        # an anomalous value updates last_value but never the baseline
        for _ in range(8):
            d.update(10.0)
        a = d.update(500.0)
        assert a is not None
        assert d.last_value() == 500.0
        assert d.baseline() == pytest.approx(10.0)

    def test_reset_restores_fresh_detector(self):
        d = health.Detector("t", warmup=4, window=8)
        for v in (1.0, 1.0, 1.0, 1.0, 1.0, 100.0):
            d.update(v)
        assert d.anomalies == 1 and d.n == 6
        d.reset()
        assert d.last_value() is None and d.baseline() is None
        assert d.n == 0 and d.anomalies == 0 and d.last_z == 0.0
        # warmup restarts: a post-reset extreme is baseline, not anomaly
        # (the deliberate regime-change semantics: the knob the signal
        # measures was rewritten)
        assert d.update(1000.0) is None
        assert d.last_value() == 1000.0


# ---------------------------------------------------------------------------
# HealthMonitor: registry, counters, chaos contract
# ---------------------------------------------------------------------------

class TestHealthMonitor:
    def test_watch_idempotent_and_observe_counts(self):
        d1 = health.watch("sig", warmup=4)
        assert health.watch("sig", warmup=4) is d1
        for _ in range(10):
            health.observe("sig", 1.0)
        a = health.observe("sig", 99.0)
        assert a is not None
        assert monitor.get_stat("health_anomalies_total") == 1
        assert monitor.get_stat("health_anomaly_sig_total") == 1
        kinds = [e for e in flight.recent(10, kind="health.anomaly")]
        assert kinds and kinds[-1]["attrs"]["signal"] == "sig"

    def test_unwatched_signal_is_noop(self):
        assert health.observe("nobody_watches", 1e9) is None

    def test_injected_detector_fault_is_swallowed(self):
        """The watcher must never crash the watched: an injected
        health.detector error is absorbed and counted."""
        health.watch("sig", warmup=4)
        with chaos.inject("health.detector", mode="error", every=1):
            for _ in range(5):
                assert health.observe("sig", 1.0) is None   # no raise
        assert monitor.get_stat("health_observe_errors_total") == 5
        # detector saw nothing while faulted
        assert health.snapshot()["signals"]["sig"]["n"] == 0

    def test_flag_arming_default_set(self):
        old = get_flags("health_detectors")
        set_flags({"health_detectors": "default"})
        try:
            health.reset()
            health._monitor.arm_from_flags(force=True)
            assert set(health.DEFAULT_SIGNALS) <= \
                set(health._monitor.detectors())
        finally:
            set_flags(old)
            health.reset()

    def test_flag_arming_json_spec(self):
        old = get_flags("health_detectors")
        set_flags({"health_detectors":
                   json.dumps({"my_sig": {"warmup": 4,
                                          "z_threshold": 5.0}})})
        try:
            health.reset()
            health._monitor.arm_from_flags(force=True)
            det = health._monitor.detectors()["my_sig"]
            assert det.warmup == 4 and det.z_threshold == 5.0
        finally:
            set_flags(old)
            health.reset()


# ---------------------------------------------------------------------------
# recompile-cause attribution + compile counters/storm
# ---------------------------------------------------------------------------

class TestRecompileCause:
    def test_classifier_per_cause(self):
        sig = (("T", (4, 6), "float32"), ("A", (8,), "int64"))
        assert health.classify_recompile(sig, []) == "new_signature"
        assert health.classify_recompile(
            (("T", (8, 6), "float32"), ("A", (8,), "int64")),
            [sig]) == "shape_change"
        assert health.classify_recompile(
            (("T", (4, 6), "bfloat16"), ("A", (8,), "int64")),
            [sig]) == "dtype_change"
        assert health.classify_recompile(
            (("S", 3), ("A", (8,), "int64")),
            [(("S", 7), ("A", (8,), "int64"))]) == "static_arg_change"
        # different arity: a wholly new signature, not a mutation
        assert health.classify_recompile(
            sig + (True,), [sig]) == "new_signature"
        # a static flip that dragged shapes along: static is the cause
        assert health.classify_recompile(
            (("S", 3), ("T", (16, 6), "float32")),
            [(("S", 7), ("T", (4, 6), "float32"))]) == "static_arg_change"

    def _mk_step(self):
        paddle.seed(0)
        net = nn.Linear(4, 2)
        opt = optimizer.SGD(learning_rate=0.1,
                            parameters=net.parameters())
        return TrainStep(net, lambda m, x, y: ((m(x) - y) ** 2).mean(),
                         opt)

    def test_trainstep_shape_change_attributed(self):
        step = self._mk_step()
        rng = np.random.default_rng(0)
        x = paddle.to_tensor(rng.standard_normal((8, 4))
                             .astype(np.float32))
        y = paddle.to_tensor(rng.standard_normal((8, 2))
                             .astype(np.float32))
        for _ in range(3):
            step(x, y)
        rep = health.compile_report()["TrainStep"]
        assert rep["compiles"] == 1 and \
            rep["last_cause"] == "new_signature"
        assert monitor.get_stat("jit_cache_hits_total") == 2
        x2 = paddle.to_tensor(rng.standard_normal((16, 4))
                              .astype(np.float32))
        y2 = paddle.to_tensor(rng.standard_normal((16, 2))
                              .astype(np.float32))
        step(x2, y2)
        rep = health.compile_report()["TrainStep"]
        assert rep["compiles"] == 2 and rep["last_cause"] == "shape_change"
        assert monitor.get_stat("jit_compiles_total") == 2
        assert monitor.get_stat("jit_compiles_shape_change_total") == 1
        # compile_ms histogram recorded both
        assert monitor.get_histogram("compile_ms").count >= 2

    def test_static_function_static_arg_change(self):
        calls = []

        @to_static
        def f(x, k):
            calls.append(1)
            return x * k
        x = paddle.to_tensor(np.ones((4,), np.float32))
        f(x, 2.0)
        f(x, 2.0)
        f(x, 3.0)                      # static arg flip -> recompile
        site = "to_static:f"
        rep = health.compile_report()[site]
        assert rep["compiles"] == 2
        assert rep["causes"].get("static_arg_change") == 1

    def test_steady_recompiles_and_storm_event(self):
        old = get_flags(["health_compile_warmup_calls",
                         "health_compile_storm_k"])
        set_flags({"health_compile_warmup_calls": 2,
                   "health_compile_storm_k": 2})
        flight.clear()
        try:
            step = self._mk_step()
            rng = np.random.default_rng(0)
            for i in range(6):         # every batch a fresh shape:
                b = 4 + i              # a recompile storm by design
                x = paddle.to_tensor(rng.standard_normal((b, 4))
                                     .astype(np.float32))
                y = paddle.to_tensor(rng.standard_normal((b, 2))
                                     .astype(np.float32))
                step(x, y)
            assert monitor.get_stat("jit_recompiles_steady_total") >= 3
            storms = flight.recent(20, kind="health.compile_storm")
            assert storms and storms[0]["attrs"]["site"] == "TrainStep"
        finally:
            set_flags(old)

    def test_healthy_train_zero_steady_recompiles(self):
        flight.clear()
        step = self._mk_step()
        rng = np.random.default_rng(0)
        x = paddle.to_tensor(rng.standard_normal((8, 4))
                             .astype(np.float32))
        y = paddle.to_tensor(rng.standard_normal((8, 2))
                             .astype(np.float32))
        for _ in range(15):            # past the warmup-call window
            step(x, y)
        assert monitor.get_stat("jit_recompiles_steady_total") == 0
        assert flight.recent(20, kind="health.compile_storm") == []


# ---------------------------------------------------------------------------
# device-memory observability
# ---------------------------------------------------------------------------

class TestMemoryTracker:
    def test_sample_counts_live_arrays_and_tags(self):
        import jax.numpy as jnp
        keep = jnp.ones((256, 256), jnp.float32)       # noqa: F841
        tr = health.MemoryTracker()
        got = tr.sample(tags={"params": 1234})
        assert got["live_bytes"] >= 256 * 256 * 4
        assert got["peak_bytes"] >= got["live_bytes"]
        assert monitor.get_stat("device_mem_live_bytes") == \
            got["live_bytes"]
        assert monitor.get_stat("device_mem_params_bytes") == 1234
        assert tr.snapshot()["tags"]["params"] == 1234

    def test_watermark_flight_event_on_growth(self):
        import jax.numpy as jnp
        flight.clear()
        tr = health.MemoryTracker(watermark_frac=0.25)
        a = jnp.ones((128, 128), jnp.float32)          # noqa: F841
        tr.sample()
        first = flight.recent(10, kind="health.mem_watermark")
        assert len(first) == 1                  # first nonzero peak
        tr.sample()                             # flat: no new event
        assert len(flight.recent(10, kind="health.mem_watermark")) == 1
        b = jnp.ones((1024, 1024), jnp.float32)        # noqa: F841
        tr.sample()                             # >25% growth: event
        events = flight.recent(10, kind="health.mem_watermark")
        assert len(events) == 2
        assert events[-1]["attrs"]["peak_bytes"] > \
            events[0]["attrs"]["peak_bytes"]

    def test_track_tag_without_full_sample(self):
        tr = health.MemoryTracker()
        tr.track("ingest", 4096)
        assert monitor.get_stat("device_mem_ingest_bytes") == 4096

    def test_maybe_sample_every_n(self):
        old = get_flags("health_mem_sample_every")
        set_flags({"health_mem_sample_every": 3})
        try:
            tags_calls = []
            ran = [health.maybe_sample_memory(
                lambda: tags_calls.append(1) or {"params": 1})
                is not None for _ in range(6)]
            assert sum(ran) == 2 and len(tags_calls) == 2
        finally:
            set_flags(old)
        assert health.maybe_sample_memory(lambda: {}) is None   # off


# ---------------------------------------------------------------------------
# flight recorder satellites: filtered recent(), SIGTERM dump
# ---------------------------------------------------------------------------

class TestFlightSatellites:
    def test_recent_kind_and_severity_filters(self):
        flight.clear()
        flight.record("a.x", severity="info", i=1)
        flight.record("b.y", severity="warn", i=2)
        flight.record("a.x", severity="error", i=3)
        assert [e["attrs"]["i"] for e in flight.recent(10, kind="a.x")] \
            == [1, 3]
        assert [e["attrs"]["i"]
                for e in flight.recent(10, min_severity="warn")] == [2, 3]
        assert [e["attrs"]["i"] for e in flight.recent(
            10, kind="a.x", min_severity="warn")] == [3]
        assert flight.recent(1, min_severity="warn")[0]["attrs"]["i"] == 3
        with pytest.raises(ValueError, match="unknown severity"):
            flight.recent(10, min_severity="fatal")

    def test_sigterm_dumps_flight_file_and_chains(self, tmp_path):
        """A launcher-killed (SIGTERM) child leaves a flight file —
        the excepthook alone never sees a signal death."""
        from paddle_tpu.framework.observability import \
            install_crash_handler
        chained = []
        prev_excepthook = sys.excepthook
        prev_term = signal.signal(signal.SIGTERM,
                                  lambda s, f: chained.append(s))
        try:
            install_crash_handler(worker="wterm",
                                  flight_dir=str(tmp_path), chain=False)
            flight.record("before.kill", severity="info")
            os.kill(os.getpid(), signal.SIGTERM)
            # the handler runs synchronously on the main thread at the
            # next bytecode boundary
            for _ in range(100):
                if chained:
                    break
            assert chained == [signal.SIGTERM]
            dump = json.loads(
                (tmp_path / "flight_wterm.json").read_text())
            kinds = [e["kind"] for e in dump["events"]]
            assert "before.kill" in kinds and "sigterm" in kinds
        finally:
            sys.excepthook = prev_excepthook
            signal.signal(signal.SIGTERM, prev_term)


# ---------------------------------------------------------------------------
# elastic: measured progress deadline
# ---------------------------------------------------------------------------

class TestMeasuredHangDeadline:
    def test_arm_from_step_time_distribution(self):
        from paddle_tpu.distributed.elastic import DictStore, ElasticAgent
        h = monitor.get_histogram("test_step_ms_dist")
        h.reset()
        for _ in range(100):
            h.record(40.0)           # p99 ~ 40ms
        agent = ElasticAgent(DictStore(ttl=10.0), [],
                             hang_deadline=30.0)
        got = agent.arm_hang_deadline(histogram="test_step_ms_dist",
                                      multiplier=50.0, floor=1.0)
        assert agent.hang_deadline == got
        # 50 * p99(≈40..50ms) is a few seconds, not the 30s default
        assert 1.0 <= got <= 5.0
        assert flight.recent(5, kind="elastic.deadline_armed")

    def test_empty_histogram_raises(self):
        from paddle_tpu.distributed.elastic import DictStore, ElasticAgent
        agent = ElasticAgent(DictStore(ttl=10.0), [])
        with pytest.raises(RuntimeError, match="no samples"):
            agent.arm_hang_deadline(histogram="never_recorded_xyz")

    def test_cap_and_floor(self):
        from paddle_tpu.distributed.elastic import DictStore, ElasticAgent
        h = monitor.get_histogram("test_step_ms_dist2")
        h.reset()
        h.record(0.01)
        agent = ElasticAgent(DictStore(ttl=10.0), [])
        assert agent.arm_hang_deadline(
            histogram="test_step_ms_dist2", floor=7.0) == 7.0
        for _ in range(50):
            h.record(10000.0)
        assert agent.arm_hang_deadline(
            histogram="test_step_ms_dist2", cap=60.0) == 60.0


# ---------------------------------------------------------------------------
# trace_merge --summary
# ---------------------------------------------------------------------------

class TestTraceSummary:
    def _spanfile(self, tmp_path):
        tracer_ = __import__("paddle_tpu.framework.observability",
                             fromlist=["Tracer"]).Tracer(
            str(tmp_path), label="t0")
        with tracer_.start_span("fast"):
            pass
        for _ in range(3):
            with tracer_.start_span("slow"):
                pass
        sp = tracer_.start_span("slow", detached=True)
        sp.end(status="error")
        tracer_.disable()
        return os.path.join(str(tmp_path), "trace_t0.jsonl")

    def test_summarize_and_cli(self, tmp_path, capsys):
        from tools import trace_merge
        path = self._spanfile(tmp_path)
        rows = trace_merge.summarize(trace_merge.merge([path]))
        by_name = {r["name"]: r for r in rows}
        assert by_name["slow"]["count"] == 4
        assert by_name["slow"]["errors"] == 1
        assert by_name["fast"]["count"] == 1
        assert by_name["slow"]["p99_ms"] <= by_name["slow"]["max_ms"]
        rc = trace_merge.main(["--summary", path])
        assert rc == 0
        out = capsys.readouterr().out
        assert "slow" in out and "p99_ms" in out
        # --out still required when --summary absent
        with pytest.raises(SystemExit):
            trace_merge.main([path])


# ---------------------------------------------------------------------------
# health_check: report assembly + gates
# ---------------------------------------------------------------------------

class TestHealthCheck:
    def test_gates_trip_on_anomalies_and_recompiles(self):
        from tools import health_check
        snap = {"stats": {"health_anomalies_total": 2,
                          "health_anomaly_ps_rpc_ms_total": 2,
                          "jit_compiles_total": 5,
                          "jit_recompiles_steady_total": 3,
                          "train_steps_total": 10},
                "histograms": {}}
        report = health_check.build_report(snap)
        tripped = health_check.evaluate_gates(report)
        assert len(tripped) == 2
        assert health_check.evaluate_gates(
            report, max_anomalies=2, max_steady_recompiles=3) == []
        text = health_check.format_report(report, tripped)
        assert "TRIPPED" in text and "ps_rpc_ms" in text

    def test_prometheus_text_input(self, tmp_path):
        from tools import health_check
        monitor.stat_set("health_anomalies_total", 0)
        monitor.observe("train_step_ms", 5.0)
        p = tmp_path / "metrics.prom"
        p.write_text(monitor.export_prometheus())
        snap = health_check.load_metrics(str(p))
        assert "train_step_ms" in snap["histograms"]
        report = health_check.build_report(snap)
        assert health_check.evaluate_gates(report) == []

    def test_json_snapshot_roundtrip(self, tmp_path):
        from tools import health_check
        monitor.observe("train_step_ms", 5.0)
        p = tmp_path / "snap.json"
        p.write_text(json.dumps(monitor.snapshot()))
        snap = health_check.load_metrics(str(p))
        assert snap["histograms"]["train_step_ms"]["count"] >= 1

    @pytest.mark.slow
    def test_mini_train_mode_healthy(self, tmp_path):
        """The CI health lane end-to-end: traced mini train, report,
        zero anomalies, zero steady recompiles, rc 0."""
        from tools import health_check
        rc = health_check.main(["--mini-train", "20",
                                "--trace-dir", str(tmp_path),
                                "--format", "json"])
        assert rc == 0


# ---------------------------------------------------------------------------
# run metadata
# ---------------------------------------------------------------------------

class TestRunMeta:
    def test_run_meta_stamped(self):
        from paddle_tpu.framework import runlog
        old = get_flags("health_z_threshold")
        set_flags({"health_z_threshold": 99.0})
        try:
            meta = runlog.run_meta(refresh=True)
            assert meta["host"] and meta["python"]
            assert meta["git_sha"] is None or len(meta["git_sha"]) == 40
            assert meta["flags_overrides"]["health_z_threshold"] == 99.0
        finally:
            set_flags(old)


# ---------------------------------------------------------------------------
# acceptance: PS mini-train, detector under injected RPC latency
# ---------------------------------------------------------------------------

def _ps_mini_train(n_steps, inject_at=None, latency=0.15, seed=0,
                   warmup=8):
    """A deterministic PS mini-train over an in-process server.  Arms
    the RPC-latency detector; ``inject_at`` turns on a ``ps.rpc``
    latency fault from that step on.  The detector floors (8 ms MAD
    floor vs a 150 ms injection) keep the verdict deterministic on a
    loaded CI host: OS-jitter of whole milliseconds on sub-ms
    localhost RPCs stays under the threshold by an order of
    magnitude, the injected fault exceeds it by one.  Returns
    (step index of the first anomaly or None, stats snapshot)."""
    from paddle_tpu.distributed.ps import (DistributedEmbedding,
                                           HostEmbeddingTable,
                                           PSTrainStep)
    from paddle_tpu.distributed.ps.service import (PsClient, PsServer,
                                                   RemoteEmbeddingTable)
    from paddle_tpu.models import WideDeepHost

    health.watch("ps_rpc_ms", warmup=warmup, rel_floor=0.25,
                 min_mad=8.0)
    health.watch("train_step_ms", rel_floor=0.25, min_mad=50.0)
    table = HostEmbeddingTable(256, 9, optimizer="sgd",
                               learning_rate=0.05, seed=0)
    srv = PsServer({"emb": table}, port=0).start()
    cli = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype="f32",
                   backoff_base=0.01)
    paddle.seed(seed)
    emb = DistributedEmbedding(256, 9, mode="sync",
                               table=RemoteEmbeddingTable(cli, "emb", 9))
    model = WideDeepHost(embedding_dim=8, num_fields=4, dense_dim=3,
                         hidden=(16,))
    opt = optimizer.Adam(learning_rate=1e-2,
                         parameters=model.parameters())

    def loss_fn(m, rows, x, y):
        return F.binary_cross_entropy_with_logits(m(rows, x), y).mean()

    step = PSTrainStep(model, loss_fn, opt, emb,
                       transfer_dtype="float32", prefetch_depth=0)
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 256, size=(n_steps, 8, 4)).astype(np.int64)
    x = paddle.to_tensor(rng.standard_normal((8, 3)).astype(np.float32))
    y = paddle.to_tensor(rng.random((8, 1)).astype(np.float32))
    flagged_at = None
    try:
        for n in range(n_steps):
            if inject_at is not None and n == inject_at:
                chaos.arm("ps.rpc", mode="latency", latency=latency,
                          every=1)
            before = monitor.get_stat("health_anomalies_total")
            step(ids[n], x, y)
            if flagged_at is None and \
                    monitor.get_stat("health_anomalies_total") > before:
                flagged_at = n
    finally:
        step.flush()
        cli.bye()
        srv.shutdown()
        chaos.disarm("ps.rpc")
    return flagged_at, monitor.snapshot()


class TestRpcLatencyAcceptance:
    def test_injected_latency_flagged_within_5_steps(self):
        """Injected ps.rpc latency at step S trips the RPC-latency
        detector within 5 steps: anomaly in the flight recorder AND
        health_anomalies_total incremented."""
        flight.clear()
        inject_at = 8
        flagged_at, snap = _ps_mini_train(16, inject_at=inject_at)
        assert flagged_at is not None, "latency storm never flagged"
        assert inject_at <= flagged_at < inject_at + 5
        assert snap["stats"]["health_anomalies_total"] >= 1
        assert snap["stats"]["health_anomaly_ps_rpc_ms_total"] >= 1
        anomalies = flight.recent(50, kind="health.anomaly")
        assert any(e["attrs"]["signal"] == "ps_rpc_ms"
                   for e in anomalies)

    def test_clean_train_zero_anomalies_zero_recompiles_via_gates(self):
        """False-positive guard, through the same decision surface CI
        uses: no injection -> zero anomalies, zero post-warmup
        recompiles, health_check gates pass."""
        from tools import health_check
        flagged_at, snap = _ps_mini_train(16, inject_at=None)
        assert flagged_at is None
        assert snap["stats"].get("health_anomalies_total", 0) == 0
        report = health_check.build_report(
            snap, health_snapshot=health.snapshot())
        assert health_check.evaluate_gates(report) == []
        assert report["compiles"]["jit_recompiles_steady_total"] == 0
        # the PS stat op surfaces the same detector state to peers
        # (spot-your-straggler): check the snapshot shape
        hs = health.snapshot()
        assert "ps_rpc_ms" in hs["signals"]
        assert hs["anomalies_total"] == 0


class TestStatOpCarriesHealth:
    def test_stat_reply_has_health_field(self):
        from paddle_tpu.distributed.ps import HostEmbeddingTable
        from paddle_tpu.distributed.ps.service import PsClient, PsServer
        health.watch("ps_rpc_ms", warmup=8)
        srv = PsServer({"emb": HostEmbeddingTable(16, 4)}, port=0).start()
        try:
            cli = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype="f32")
            stat = cli.stat()
            assert "health" in stat
            assert "signals" in stat["health"]
            assert "compile" in stat["health"]
            cli.bye()
        finally:
            srv.shutdown()

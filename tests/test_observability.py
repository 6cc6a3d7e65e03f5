"""Observability tier: profiler (fluid/profiler.py + tools/timeline.py
roles), monitor counters (platform/monitor.h), NaN/Inf watcher
(framework/details/nan_inf_utils.h via FLAGS_check_nan_inf), and the
unified plane (framework/observability.py): distributed tracing over
the PS transport, the flight recorder, the Prometheus export plane,
and tools/trace_merge.py."""
import json
import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import chaos, monitor, observability
from paddle_tpu.framework.observability import (FlightRecorder,
                                                MetricsReporter, Tracer,
                                                flight,
                                                install_crash_handler,
                                                validate_prometheus)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
from tools import trace_merge  # noqa: E402


def _read_spans(path):
    """Span records of one tracer JSONL file, in write order."""
    out = []
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("kind") == "span":
                out.append(rec)
    return out


def _mk_ps(tmp_path, wire="f32", **client_kw):
    """One in-process PS server + client, each with its own tracer file
    (the per-process files an out-of-process run would produce)."""
    from paddle_tpu.distributed.ps import HostEmbeddingTable
    from paddle_tpu.distributed.ps.service import PsClient, PsServer
    tdir = str(tmp_path / "traces")
    srv_tr = Tracer(tdir, label="server")
    table = HostEmbeddingTable(128, 8, optimizer="sgd", seed=0)
    srv = PsServer({"emb": table}, tracer=srv_tr).start()
    cli_tr = Tracer(tdir, label=client_kw.pop("label", "worker-0"))
    cli = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype=wire,
                   backoff_base=0.01, tracer=cli_tr, **client_kw)
    return srv, cli, tdir


class TestMonitor:
    def test_counters(self):
        monitor.reset_all_stats()
        monitor.stat_add("STAT_test_samples", 5)
        monitor.stat_add("STAT_test_samples", 3)
        monitor.stat_sub("STAT_test_samples", 2)
        assert monitor.get_stat("STAT_test_samples") == 6
        monitor.stat_add("STAT_test_time", 0.5)
        assert monitor.all_stats()["STAT_test_time"] == 0.5
        monitor.reset_stat("STAT_test_samples")
        assert monitor.get_stat("STAT_test_samples") == 0


class TestProfiler:
    def test_record_event_aggregation(self, capsys, tmp_path):
        prof = paddle.profiler
        path = str(tmp_path / "chrome_trace.json")
        prof.start_profiler("CPU")
        for _ in range(3):
            with prof.RecordEvent("my_span"):
                time.sleep(0.002)
        with prof.record_event("other"):
            pass
        prof.stop_profiler(sorted_key="total", profile_path=path)
        out = capsys.readouterr().out
        assert "Profiling Report" in out
        assert "my_span" in out and "other" in out
        # chrome trace written with one event per span
        with open(path) as f:
            trace = json.load(f)
        names = [e["name"] for e in trace["traceEvents"]]
        assert names.count("my_span") == 3
        assert all(e["ph"] == "X" for e in trace["traceEvents"])

    def test_context_manager_and_decorator(self, capsys, tmp_path):
        prof = paddle.profiler

        @prof.RecordEvent("decorated")
        def work():
            return 1 + 1

        with prof.profiler("CPU", "calls",
                           str(tmp_path / "t.json")):
            assert work() == 2
        assert "decorated" in capsys.readouterr().out

    def test_bad_args(self):
        with pytest.raises(ValueError):
            paddle.profiler.start_profiler("XPU")
        paddle.profiler.start_profiler("CPU")
        with pytest.raises(ValueError):
            paddle.profiler.stop_profiler(sorted_key="bogus")
        paddle.profiler._state["on"] = False

    def test_trainstep_emits_span(self, tmp_path):
        import paddle_tpu.nn as nn
        from paddle_tpu.jit import TrainStep
        net = nn.Linear(4, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        step = TrainStep(net, lambda m, x, y: ((m(x) - y) ** 2).mean(), opt)
        x = paddle.to_tensor(np.random.randn(8, 4).astype("float32"))
        y = paddle.to_tensor(np.random.randn(8, 2).astype("float32"))
        path = str(tmp_path / "ts.json")
        paddle.profiler.start_profiler("CPU")
        step(x, y)
        paddle.profiler.stop_profiler(profile_path=path)
        with open(path) as f:
            names = [e["name"] for e in json.load(f)["traceEvents"]]
        assert "TrainStep" in names


class TestNanInfWatcher:
    def setup_method(self):
        paddle.set_flags({"FLAGS_check_nan_inf": True})

    def teardown_method(self):
        paddle.set_flags({"FLAGS_check_nan_inf": False})

    def test_eager_op_raises(self):
        x = paddle.to_tensor(np.array([1.0, 0.0], np.float32))
        with pytest.raises(FloatingPointError, match="NaN/Inf"):
            _ = paddle.log(x) / x          # log(0) = -inf

    def test_eager_clean_passes(self):
        x = paddle.to_tensor(np.array([1.0, 2.0], np.float32))
        _ = (x * 2 + 1).numpy()

    def test_tracked_op_raises(self):
        x = paddle.to_tensor(np.array([0.0, 1.0], np.float32))
        x.stop_gradient = False
        with pytest.raises(FloatingPointError, match="NaN/Inf"):
            _ = paddle.log(x)

    def test_trainstep_sweep(self):
        import paddle_tpu.nn as nn
        from paddle_tpu.jit import TrainStep
        net = nn.Linear(2, 1)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        step = TrainStep(net, lambda m, x, y: ((m(x) - y) ** 2).mean(), opt)
        bad = paddle.to_tensor(
            np.array([[np.inf, 1.0]], np.float32))
        y = paddle.to_tensor(np.array([[1.0]], np.float32))
        with pytest.raises(FloatingPointError, match="non-finite"):
            step(bad, y)

    def test_flag_off_no_raise(self):
        paddle.set_flags({"FLAGS_check_nan_inf": False})
        x = paddle.to_tensor(np.array([0.0], np.float32))
        out = paddle.log(x)
        assert np.isinf(out.numpy()).all()


# ---------------------------------------------------------------------------
# distributed tracing
# ---------------------------------------------------------------------------

class TestTracer:
    def test_span_nesting_and_file(self, tmp_path):
        tr = Tracer(str(tmp_path), label="t0")
        with tr.start_span("outer", attrs={"k": 1}) as outer:
            with tr.start_span("inner") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
        spans = _read_spans(tr.path())
        assert [s["name"] for s in spans] == ["inner", "outer"]
        assert spans[0]["trace"] == spans[1]["trace"]
        assert spans[1]["parent"] is None
        assert spans[1]["attrs"] == {"k": 1}
        # meta record leads the file
        first = json.loads(open(tr.path()).readline())
        assert first["kind"] == "process" and first["label"] == "t0"

    def test_inject_extract_roundtrip(self, tmp_path):
        tr = Tracer(str(tmp_path))
        with tr.start_span("s") as sp:
            header = tr.inject({"op": "x"})
        ctx = Tracer.extract(header)
        assert ctx.trace_id == sp.trace_id and ctx.span_id == sp.span_id
        assert Tracer.extract({"op": "x"}) is None

    def test_disabled_is_noop(self, tmp_path):
        tr = Tracer()                     # no dir, env flag empty
        sp = tr.start_span("a")
        assert sp.trace_id is None
        header = {"op": "x"}
        tr.inject(header)
        assert "trace" not in header
        with sp:
            pass                          # context-manager form still works

    def test_exception_marks_error(self, tmp_path):
        tr = Tracer(str(tmp_path))
        with pytest.raises(RuntimeError):
            with tr.start_span("boom"):
                raise RuntimeError("x")
        (sp,) = _read_spans(tr.path())
        assert sp["status"] == "error"

    def test_detached_span_after_disable_is_dropped(self, tmp_path):
        tr = Tracer(str(tmp_path), label="d")
        sp = tr.start_span("x", detached=True)
        tr.disable()
        sp.end()                          # must drop, not crash

    def test_clock_offset_meta_rewritten(self, tmp_path):
        tr = Tracer(str(tmp_path), label="c")
        with tr.start_span("a"):
            pass
        tr.set_clock_offset(1.5)
        metas = [json.loads(l) for l in open(tr.path())
                 if json.loads(l).get("kind") == "process"]
        assert metas[-1]["clock_offset"] == 1.5


class TestRpcTracePropagation:
    def test_client_server_share_trace(self, tmp_path):
        srv, cli, tdir = _mk_ps(tmp_path)
        try:
            cli.push_pull("emb", np.arange(4), np.ones((4, 8), np.float32),
                          np.arange(4))
        finally:
            cli.bye()
            srv.shutdown()
        cspans = _read_spans(os.path.join(tdir, "trace_worker-0.jsonl"))
        sspans = _read_spans(os.path.join(tdir, "trace_server.jsonl"))
        cpp = [s for s in cspans if s["name"] == "ps.push_pull"]
        spp = [s for s in sspans if s["name"] == "ps.server.push_pull"]
        assert cpp and spp
        # one trace id across the wire; the server span's parent is the
        # client ATTEMPT span that carried the request
        assert spp[0]["trace"] == cpp[0]["trace"]
        attempts = [s for s in cspans if s["name"] == "ps.rpc"
                    and s["trace"] == cpp[0]["trace"]]
        assert spp[0]["parent"] in {a["span"] for a in attempts}

    def test_retry_reuses_trace_with_fresh_spans(self, tmp_path):
        """Satellite: a chaos-retried ps.rpc call keeps ONE trace id
        across the retry, with distinct span ids per attempt."""
        srv, cli, tdir = _mk_ps(tmp_path)
        try:
            with chaos.inject("ps.rpc", mode="error", nth=1, n_times=1):
                cli.pull("emb", np.arange(4))
        finally:
            cli.bye()
            srv.shutdown()
        cspans = _read_spans(os.path.join(tdir, "trace_worker-0.jsonl"))
        pull = [s for s in cspans if s["name"] == "ps.pull"][0]
        attempts = [s for s in cspans if s["name"] == "ps.rpc"
                    and s["trace"] == pull["trace"]]
        assert len(attempts) == 2
        assert attempts[0]["status"] == "error"
        assert attempts[1]["status"] == "ok"
        assert attempts[0]["span"] != attempts[1]["span"]
        assert attempts[0]["trace"] == attempts[1]["trace"]

    def test_init_clock_probe_never_marks_endpoint_dead(self, tmp_path):
        """The construction-time clock probe (tracing on, server not up
        yet) must not report the endpoint dead — that fires the elastic
        lost-peer channel for a healthy co-launching job."""
        from paddle_tpu.distributed.ps.service import PsClient
        cli = PsClient(["127.0.0.1:1"], wire_dtype="f32",
                       backoff_base=0.01,
                       tracer=Tracer(str(tmp_path), label="probe"))
        assert cli.dead_endpoints == []

    def test_sync_clock_measures_offset(self, tmp_path):
        srv, cli, tdir = _mk_ps(tmp_path)
        try:
            off = cli.sync_clock()
        finally:
            cli.bye()
            srv.shutdown()
        # same host, same clock: the measured offset is sub-second
        assert off is not None and abs(off) < 1.0
        assert cli.tracer.clock_offset == off


class TestTwoWorkerOneServerMerge:
    def test_merged_chrome_trace(self, tmp_path):
        """Acceptance: a 2-worker + 1-server in-process run produces
        per-process span files that trace_merge merges into one valid
        chrome trace where a client push/pull span and its server-side
        child share a trace id."""
        from paddle_tpu.distributed.ps.service import PsClient
        srv, c0, tdir = _mk_ps(tmp_path, label="worker-0")
        c1 = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype="f32",
                      backoff_base=0.01,
                      tracer=Tracer(tdir, label="worker-1"))
        try:
            c0.sync_clock()
            c1.sync_clock()
            for c in (c0, c1):
                c.push_pull("emb", np.arange(6), np.ones((6, 8),
                                                         np.float32),
                            np.arange(6, 12))
        finally:
            c0.bye()
            c1.bye()
            srv.shutdown()
        out = str(tmp_path / "merged.json")
        rc = trace_merge.main(["--dir", tdir, "--out", out])
        assert rc == 0
        with open(out) as f:
            trace = json.load(f)              # valid traceEvents JSON
        trace_merge.validate_chrome_trace(trace)
        evs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        # three lanes (one per span file), labeled
        assert {e["pid"] for e in evs} == {0, 1, 2}
        names = {e["args"]["name"]
                 for e in trace["traceEvents"] if e["ph"] == "M"}
        assert any("server" in n for n in names)
        assert any("worker-0" in n for n in names)
        # a client push_pull span and a server-side child in one trace
        cpp = [e for e in evs if e["name"] == "ps.push_pull"]
        spp = [e for e in evs if e["name"] == "ps.server.push_pull"]
        assert cpp and spp
        assert {e["args"]["trace"] for e in spp} <= \
            {e["args"]["trace"] for e in cpp}


class TestPrefetchSpans:
    def _step(self, tmp_path, prefetch_depth=1):
        import paddle_tpu.nn.functional as F
        from paddle_tpu import optimizer
        from paddle_tpu.distributed.ps import (DistributedEmbedding,
                                               PSTrainStep)
        from paddle_tpu.distributed.ps.service import RemoteEmbeddingTable
        from paddle_tpu.models import WideDeepHost
        srv, cli, tdir = _mk_ps(tmp_path)
        paddle.seed(0)
        emb = DistributedEmbedding(
            128, 9, mode="sync", table=RemoteEmbeddingTable(cli, "emb", 9))
        model = WideDeepHost(embedding_dim=8, num_fields=4, dense_dim=3,
                             hidden=(16,))
        opt = optimizer.Adam(learning_rate=1e-2,
                             parameters=model.parameters())

        def loss_fn(m, rows, x, y):
            return F.binary_cross_entropy_with_logits(m(rows, x), y).mean()

        step = PSTrainStep(model, loss_fn, opt, emb,
                           transfer_dtype="float32",
                           prefetch_depth=prefetch_depth)
        rng = np.random.default_rng(0)
        batches = [rng.integers(0, 128, (8, 4)).astype(np.int64)
                   for _ in range(4)]
        x = paddle.to_tensor(rng.standard_normal((8, 3)).astype(np.float32))
        y = paddle.to_tensor(rng.integers(0, 2, (8, 1)).astype(np.float32))
        return srv, cli, tdir, step, batches, x, y

    def test_reform_discarded_prefetch_closes_span_with_error(
            self, tmp_path):
        """Satellite: a ``reform()``-discarded prefetch (epoch bump
        between issue and consume) must close its span with an error
        status naming the staleness."""
        srv, cli, tdir, step, batches, x, y = self._step(tmp_path)
        try:
            cli.set_epoch(1, fence_servers=True)
            step.prefetch(batches[0])
            step.prefetch(batches[1])
            step(batches[0], x, y)                 # issues prefetch(b1)
            assert step._inflight
            step._inflight[0]["future"].result()   # deterministic wait
            cli.set_epoch(2, fence_servers=True)   # reform mid-flight
            step(batches[1], x, y)                 # discards stale rows
            step.flush()
        finally:
            cli.bye()
            srv.shutdown()
        spans = _read_spans(os.path.join(tdir, "trace_worker-0.jsonl"))
        pf = [s for s in spans if s["name"] == "ps.prefetch"]
        assert pf, "no prefetch spans recorded"
        stale = [s for s in pf if s["status"] == "error"
                 and s["attrs"].get("reason") == "stale_epoch"]
        assert stale, f"no stale-epoch prefetch span in {pf}"
        # and the discard was counted as a pipeline miss
        assert monitor.get_stat("ps_prefetch_misses_total") >= 1

    def test_prefetch_hit_counted_and_span_ok(self, tmp_path):
        srv, cli, tdir, step, batches, x, y = self._step(tmp_path)
        monitor.reset_stat("ps_prefetch_hits_total")
        try:
            step.prefetch(batches[0])
            for n, ids in enumerate(batches):
                if n + 1 < len(batches):
                    step.prefetch(batches[n + 1])
                step(ids, x, y)
            step.flush()
        finally:
            cli.bye()
            srv.shutdown()
        assert monitor.get_stat("ps_prefetch_hits_total") >= 1
        spans = _read_spans(os.path.join(tdir, "trace_worker-0.jsonl"))
        assert any(s["name"] == "ps.prefetch" and s["status"] == "ok"
                   for s in spans)
        # the step root span exists and the prefetch rode the pipeline
        assert any(s["name"] == "train.step" for s in spans)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_bounded_and_ordered(self):
        fr = FlightRecorder(capacity=4)
        for i in range(10):
            fr.record("k", i=i)
        recent = fr.recent(10)
        assert len(recent) == 4
        assert [e["attrs"]["i"] for e in recent] == [6, 7, 8, 9]
        assert fr.dropped == 6
        assert len(fr.recent(2)) == 2
        fr.clear()
        assert fr.recent(10) == [] and fr.dropped == 0

    def test_severity_normalized(self):
        fr = FlightRecorder(capacity=4)
        ev = fr.record("k", severity="bogus")
        assert ev["severity"] == "info"

    def test_injected_rpc_crash_dump(self, tmp_path):
        """Acceptance: after an injected ps.rpc crash, the
        flight_<worker>.json dump holds the fault event and the
        retry/mark_dead events, in order."""
        from paddle_tpu.distributed.ps import HostEmbeddingTable
        from paddle_tpu.distributed.ps.service import PsClient, PsServer
        flight.clear()
        table = HostEmbeddingTable(64, 8, optimizer="sgd", seed=0)
        srv = PsServer({"emb": table}).start()
        cli = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype="f32",
                       max_retries=1, backoff_base=0.01)
        hook = install_crash_handler(worker="w0",
                                     flight_dir=str(tmp_path),
                                     chain=False)
        try:
            with chaos.inject("ps.rpc", mode="error", every=1):
                with pytest.raises(ConnectionError) as ei:
                    cli.pull("emb", np.arange(4))
                hook(ConnectionError, ei.value, None)   # uncaught-crash path
        finally:
            import sys as _sys
            _sys.excepthook = _sys.__excepthook__
            cli.bye()
            srv.shutdown()
        dump_path = tmp_path / "flight_w0.json"
        assert dump_path.exists()
        dump = json.loads(dump_path.read_text())
        kinds = [e["kind"] for e in dump["events"]]
        # fault first, then the retries it caused, then the death report
        assert "chaos.trip" in kinds and "ps.retry" in kinds \
            and "ps.mark_dead" in kinds
        assert kinds.index("chaos.trip") < kinds.index("ps.retry") \
            < kinds.index("ps.mark_dead")
        assert kinds[-1] == "crash"

    def test_stat_op_carries_flight(self, tmp_path):
        srv, cli, _ = _mk_ps(tmp_path)
        flight.record("test.marker", note="here")
        try:
            stat = cli.stat()
        finally:
            cli.bye()
            srv.shutdown()
        kinds = [e["kind"] for e in stat["flight"]]
        assert "test.marker" in kinds

    def test_resilient_step_events_and_counters(self):
        import paddle_tpu.nn as nn
        from paddle_tpu.jit import ResilientTrainStep, TrainStep
        flight.clear()
        monitor.reset_stat("train_nan_skips_total")
        monitor.reset_stat("train_restores_total")
        paddle.seed(0)
        net = nn.Linear(2, 1)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        step = ResilientTrainStep(TrainStep(
            net, lambda m, x, y: ((m(x) - y) ** 2).mean(), opt))
        x = paddle.to_tensor(np.ones((4, 2), np.float32))
        y = paddle.to_tensor(np.ones((4, 1), np.float32))
        step(x, y)                                     # good step
        bad = paddle.to_tensor(np.full((4, 2), np.nan, np.float32))
        step(bad, y)                                   # skipped + restored
        assert step.last_step_skipped
        assert monitor.get_stat("train_nan_skips_total") == 1
        assert monitor.get_stat("train_restores_total") == 1
        kinds = [e["kind"] for e in flight.recent(10)]
        assert "train.nan_skip" in kinds and "train.restore" in kinds

    def test_launch_supervisor_dumps_on_terminal_failure(self, tmp_path):
        import sys as _sys

        from paddle_tpu.distributed.launch import _Child, _supervise
        flight.clear()
        log = str(tmp_path / "workerlog.0")
        c = _Child("w0", [_sys.executable, "-c", "import sys; sys.exit(3)"],
                   {}, log)
        rc = _supervise([c], elastic_retries=0, poll_interval=0.05)
        assert rc == 3
        dump = json.loads((tmp_path / "flight_w0.json").read_text())
        kinds = [e["kind"] for e in dump["events"]]
        assert "launch.child_failed" in kinds

    def test_elastic_agent_events_recorded(self):
        from paddle_tpu.distributed.elastic import (DictStore,
                                                    ElasticAgent,
                                                    LocalHandle)
        flight.clear()
        clk = [0.0]
        store = DictStore(ttl=10.0, clock=lambda: clk[0])
        done = {"n": 0}

        def work(stop):
            done["n"] += 1

        h = LocalHandle("w0", work).start()
        h._thread.join(timeout=2.0)
        store.register("w0")
        agent = ElasticAgent(store, [h], clock=lambda: clk[0])
        events = agent.poll_once()
        assert any(ev[0] in ("done", "left") for ev in events)
        kinds = [e["kind"] for e in flight.recent(10)]
        assert any(k.startswith("elastic.") for k in kinds)


# ---------------------------------------------------------------------------
# metrics export plane
# ---------------------------------------------------------------------------

class TestPrometheusExport:
    def test_export_round_trips_grammar(self):
        monitor.stat_add("STAT_prom_check", 3)
        monitor.observe("prom_check_ms", 0.4)
        monitor.observe("prom_check_ms", 7.0)
        monitor.observe("prom_check_ms", 50000.0)      # overflow bucket
        text = monitor.export_prometheus()
        n = validate_prometheus(text)
        assert n > 0
        assert "# TYPE STAT_prom_check gauge" in text
        assert "# TYPE prom_check_ms histogram" in text
        assert 'prom_check_ms_bucket{le="+Inf"} 3' in text
        assert "prom_check_ms_count 3" in text

    def test_name_sanitization(self):
        monitor.observe("ps_client_rpc_ms_push-pull?", 1.0)
        text = monitor.export_prometheus()
        validate_prometheus(text)
        assert "ps_client_rpc_ms_push_pull_" in text

    def test_validator_rejects_garbage(self):
        with pytest.raises(ValueError):
            validate_prometheus("not a metric line!\n")
        with pytest.raises(ValueError):
            # non-cumulative buckets
            validate_prometheus(
                "# TYPE h histogram\n"
                'h_bucket{le="1"} 5\nh_bucket{le="+Inf"} 3\n'
                "h_sum 1\nh_count 3\n")

    def test_metrics_reporter_atomic_file(self, tmp_path):
        monitor.stat_add("STAT_reporter_check", 1)
        path = str(tmp_path / "metrics" / "train.prom")
        rep = MetricsReporter(path, interval=0.05)
        rep.start()
        try:
            time.sleep(0.15)
        finally:
            rep.stop()
        assert rep.writes >= 2
        text = open(path).read()
        validate_prometheus(text)
        assert "STAT_reporter_check" in text
        # no torn tmp files left behind
        assert all(not f.startswith("train.prom.tmp")
                   for f in os.listdir(tmp_path / "metrics"))

    def test_trainstep_instrumentation(self):
        import paddle_tpu.nn as nn
        from paddle_tpu.jit import TrainStep
        monitor.reset_stat("train_steps_total")
        monitor.get_histogram("train_step_ms").reset()
        paddle.seed(0)
        net = nn.Linear(4, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        step = TrainStep(net, lambda m, x, y: ((m(x) - y) ** 2).mean(),
                         opt)
        x = paddle.to_tensor(np.ones((8, 4), np.float32))
        y = paddle.to_tensor(np.ones((8, 2), np.float32))
        for _ in range(3):
            step(x, y)
        assert monitor.get_stat("train_steps_total") == 3
        # a step time is one call's start to the next's: two of them
        assert monitor.all_histograms()["train_step_ms"]["count"] == 2


class TestHistogramSatellites:
    def test_reset_all_in_place_keeps_live_refs(self):
        """Satellite: reset_all_histograms must reset IN PLACE — live
        Histogram references (TransportStats et al) keep recording into
        the registered object."""
        h = monitor.get_histogram("reset_check_ms")
        h.record(5.0)
        monitor.reset_all_histograms()
        assert monitor.all_histograms()["reset_check_ms"]["count"] == 0
        h.record(1.0)                      # the live ref must still land
        assert monitor.all_histograms()["reset_check_ms"]["count"] == 1

    def test_percentile_interpolates_within_bucket(self):
        """Satellite: percentile() now interpolates linearly inside the
        bucket instead of returning the upper bound."""
        h = monitor.Histogram("interp")
        for _ in range(100):
            h.record(0.15)                 # all in the (0.1, 0.2] bucket
        # upper-bound behavior would return exactly 0.2 for every p;
        # interpolation spreads across the bucket
        assert 0.1 < h.percentile(0.25) < h.percentile(0.75) <= 0.2
        assert h.percentile(0.5) == pytest.approx(0.15, abs=0.01)

    def test_percentile_overflow_returns_max(self):
        h = monitor.Histogram("over")
        h.record(123456.0)
        assert h.percentile(0.99) == 123456.0
        assert monitor.Histogram("empty").percentile(0.5) == 0.0


class TestProfilerSpanCap:
    def test_span_cap_drops_and_reports(self, tmp_path, capsys):
        """Satellite: long profiling runs must not grow _spans without
        bound — the flag caps the timeline, the drop count lands in the
        report and the chrome-trace metadata, and the aggregate table
        still counts every call."""
        prof = paddle.profiler
        old = paddle.get_flags("FLAGS_profiler_max_spans")[
            "FLAGS_profiler_max_spans"]
        paddle.set_flags({"FLAGS_profiler_max_spans": 5})
        path = str(tmp_path / "capped.json")
        try:
            prof.start_profiler("CPU")
            for _ in range(12):
                with prof.RecordEvent("tiny"):
                    pass
            prof.stop_profiler(profile_path=path)
        finally:
            paddle.set_flags({"FLAGS_profiler_max_spans": old})
        out = capsys.readouterr().out
        assert "dropped" in out and "12" in out      # report: calls=12
        with open(path) as f:
            trace = json.load(f)
        assert len(trace["traceEvents"]) == 5
        assert trace["metadata"]["dropped_spans"] == 7


class TestTraceMergeTool:
    def _fake_file(self, path, label, offset, spans):
        with open(path, "w") as f:
            f.write(json.dumps({"kind": "process", "label": label,
                                "pid": 42, "clock_offset": offset}) + "\n")
            for sp in spans:
                f.write(json.dumps(dict({"kind": "span", "status": "ok",
                                         "tid": 1, "dur": 10.0,
                                         "parent": None,
                                         "attrs": {}}, **sp)) + "\n")

    def test_clock_offset_applied_per_lane(self, tmp_path):
        a = str(tmp_path / "trace_a.jsonl")
        b = str(tmp_path / "trace_b.jsonl")
        self._fake_file(a, "a", 0.0,
                        [{"name": "x", "trace": "t1", "span": "s1",
                          "ts": 1000.0}])
        self._fake_file(b, "b", 2.0,              # 2 s behind reference
                        [{"name": "y", "trace": "t1", "span": "s2",
                          "parent": "s1", "ts": 1000.0}])
        trace = trace_merge.merge([a, b])
        trace_merge.validate_chrome_trace(trace)
        evs = {e["name"]: e for e in trace["traceEvents"]
               if e["ph"] == "X"}
        assert evs["x"]["ts"] == 1000.0
        assert evs["y"]["ts"] == 1000.0 + 2e6     # shifted onto reference
        assert evs["x"]["pid"] != evs["y"]["pid"]
        assert evs["y"]["args"]["parent"] == "s1"

    def test_torn_file_skipped_not_fatal(self, tmp_path):
        p = str(tmp_path / "trace_torn.jsonl")
        self._fake_file(p, "torn", 0.0,
                        [{"name": "x", "trace": "t", "span": "s",
                          "ts": 1.0}])
        with open(p, "a") as f:
            f.write('{"kind": "span", "name": "half')   # crash mid-write
        meta, spans = trace_merge.load_span_file(p)
        assert len(spans) == 1 and meta["label"] == "torn"

    def test_validator_rejects_bad_events(self):
        with pytest.raises(ValueError):
            trace_merge.validate_chrome_trace({"traceEvents": [{}]})
        with pytest.raises(ValueError):
            trace_merge.validate_chrome_trace(
                {"traceEvents": [{"name": "x", "ph": "X", "pid": 0,
                                  "tid": 0, "ts": -5.0, "dur": 1.0}]})
        with pytest.raises(ValueError):
            trace_merge.validate_chrome_trace([])


# ---------------------------------------------------------------------------
# flight incident-storm guard
# ---------------------------------------------------------------------------

class TestFlightStormGuard:
    """k identical (kind, attrs) events in the window keep the ring
    readable; lifetime kind totals stay truthful; anything differing in
    any attr is a different incident and never dedups."""

    def _flags(self, window, k):
        from paddle_tpu.framework.flags import get_flags, set_flags
        saved = get_flags(["flight_storm_window", "flight_storm_k"])
        set_flags({"flight_storm_window": window, "flight_storm_k": k})
        return lambda: set_flags(saved)

    def test_identical_storm_suppressed_totals_truthful(self):
        restore = self._flags(60.0, 3)
        try:
            monitor.reset_stat("flight_suppressed_total")
            fr = FlightRecorder(capacity=64)
            for _ in range(8):
                fr.record("ps.retry", op="pull")
            ring = [e for e in fr.recent(64) if e["kind"] == "ps.retry"]
            assert len(ring) == 3                  # k kept, rest culled
            assert fr.suppressed == 5
            assert fr.kind_totals()["ps.retry"] == 8   # lifetime truth
            assert monitor.get_stat("flight_suppressed_total") == 5
        finally:
            restore()

    def test_distinct_attrs_never_dedup(self):
        restore = self._flags(60.0, 2)
        try:
            fr = FlightRecorder(capacity=64)
            for i in range(6):
                fr.record("ps.retry", op="pull", attempt=i)
            assert len(fr.recent(64)) == 6 and fr.suppressed == 0
        finally:
            restore()

    def test_clear_resets_storm_state(self):
        restore = self._flags(60.0, 2)
        try:
            fr = FlightRecorder(capacity=64)
            for _ in range(5):
                fr.record("k", a=1)
            assert fr.suppressed == 3
            fr.clear()
            assert fr.suppressed == 0
            for _ in range(2):
                fr.record("k", a=1)
            assert len(fr.recent(64)) == 2         # fresh window
        finally:
            restore()

    def test_guard_off_when_disabled(self):
        restore = self._flags(0.0, 0)
        try:
            fr = FlightRecorder(capacity=64)
            for _ in range(20):
                fr.record("k", a=1)
            assert fr.suppressed == 0
        finally:
            restore()

"""Continuous-perf observatory: run-ledger lifecycle (concurrent
appends, torn-write recovery, schema skew, chaos), RunRecord capture,
the span<->cost attribution join, and Detector-over-ledger cross-run
regression detection (tools/perf_report.py).

Acceptance (deterministic, CPU-only): a ledger of seeded run records
compares clean; the same ledger plus one record whose latency summary
jumped is flagged with a NAMED signal and a nonzero-exit verdict,
identically across repeated invocations."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle  # noqa: F401 — backend pinned by conftest
from paddle_tpu.framework import chaos, health, monitor, runlog
from paddle_tpu.framework.flags import get_flags, set_flags
from paddle_tpu.framework.observability import flight, tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools import perf_report, trace_merge  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_plane():
    chaos.reset(0)
    health.reset()
    for s in ("runlog_write_errors_total", "runlog_skipped_records_total",
              "runlog_records_written_total", "health_anomalies_total"):
        monitor.reset_stat(s)
    flight.clear()
    yield
    chaos.reset(0)
    health.reset()


def _ledger(tmp_path, name="ledger.jsonl"):
    return runlog.RunLedger(str(tmp_path / name))


# ---------------------------------------------------------------------------
# ledger lifecycle
# ---------------------------------------------------------------------------

class TestLedgerLifecycle:
    def test_append_read_roundtrip(self, tmp_path):
        led = _ledger(tmp_path)
        for i in range(3):
            assert led.append({"schema_version": runlog.SCHEMA_VERSION,
                               "kind": "health_check", "label": "dense",
                               "i": i})
        recs = led.read()
        assert [r["i"] for r in recs] == [0, 1, 2]
        assert len(led.records(kind="health_check")) == 3
        assert led.records(kind="bench") == []
        assert led.records(label="dense")[0]["label"] == "dense"

    def test_missing_file_reads_empty(self, tmp_path):
        assert _ledger(tmp_path, "nope.jsonl").read() == []

    def test_concurrent_appends_two_processes(self, tmp_path):
        """Two independently-launched processes share one ledger via
        the fcntl-lock + tmp+rename discipline: every record from both
        writers survives, no torn lines."""
        path = str(tmp_path / "ledger.jsonl")
        n = 12
        script = (
            "import sys\n"
            "from paddle_tpu.framework.runlog import RunLedger\n"
            "led = RunLedger(sys.argv[1])\n"
            "for i in range(int(sys.argv[3])):\n"
            "    assert led.append({'kind': 'bench',"
            " 'writer': sys.argv[2], 'i': i})\n")
        procs = [subprocess.Popen(
            [sys.executable, "-c", script, path, w, str(n)],
            cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"})
            for w in ("a", "b")]
        for p in procs:
            assert p.wait(timeout=240) == 0
        recs = runlog.RunLedger(path).read()
        assert len(recs) == 2 * n
        for w in ("a", "b"):
            seq = [r["i"] for r in recs if r["writer"] == w]
            assert seq == list(range(n))   # per-writer order preserved

    def test_torn_write_recovery(self, tmp_path):
        """A record truncated mid-line (hard kill, torn disk) is
        skipped and counted by the next reader; the next append
        isolates the bad tail instead of merging into it."""
        led = _ledger(tmp_path)
        assert led.append({"kind": "bench", "i": 0})
        with open(led.path, "a") as f:
            f.write('{"kind": "bench", "i": 1, "torn": tru')   # no \n
        recs = led.read()
        assert [r["i"] for r in recs] == [0]
        assert monitor.get_stat("runlog_skipped_records_total") == 1
        assert led.append({"kind": "bench", "i": 2})
        recs = led.read()
        assert [r["i"] for r in recs] == [0, 2]
        # the torn line stays skipped but is NOT re-counted: the
        # counter tracks corruption, not read frequency
        assert monitor.get_stat("runlog_skipped_records_total") == 1

    def test_torn_multibyte_tail_recovered(self, tmp_path):
        """A tail torn INSIDE a multi-byte UTF-8 character must not
        crash the reader (undecodable bytes degrade to replacement
        chars -> malformed JSON -> skipped) nor wedge future appends."""
        led = _ledger(tmp_path)
        assert led.append({"kind": "bench", "i": 0})
        full = json.dumps({"kind": "bench", "host": "héllo"},
                          ensure_ascii=False).encode("utf-8")
        with open(led.path, "ab") as f:
            f.write(full[:-4])          # cut inside the record, and the
            # é multi-byte sequence stays whole but the line is torn;
            # now also tear mid-character:
            f.write("é".encode("utf-8")[:1])
        recs = led.read()
        assert [r["i"] for r in recs] == [0]
        assert monitor.get_stat("runlog_skipped_records_total") >= 1
        assert led.append({"kind": "bench", "i": 1})
        assert [r["i"] for r in led.read()] == [0, 1]

    def test_schema_version_skew_degrades(self, tmp_path):
        """An old reader meeting a NEWER record keeps the known fields
        and ignores the rest — and the compare consumer scores what it
        understands instead of crashing."""
        led = _ledger(tmp_path)
        base = {"schema_version": runlog.SCHEMA_VERSION,
                "kind": "health_check", "label": "x",
                "summary": {"train_step_p99_ms": 10.0}}
        assert led.append(base)
        future = {"schema_version": 99, "kind": "health_check",
                  "label": "x",
                  "summary": {"train_step_p99_ms": 10.5,
                              "a_signal_from_the_future": 1.0},
                  "hologram": {"unknown": ["structure"]}}
        assert led.append(future)
        recs = led.read()
        assert len(recs) == 2 and recs[1]["schema_version"] == 99
        result = perf_report.compare_records(recs)
        assert result["regressions"] == []
        sigs = {s["signal"] for g in result["groups"]
                for s in g["signals"]}
        assert "train_step_p99_ms" in sigs
        assert "a_signal_from_the_future" not in sigs  # unknown: ignored

    def test_chaos_fault_never_crashes_append(self, tmp_path):
        """runlog.observe error: swallowed, counted, flight-recorded —
        the run being recorded survives its recorder; the ledger holds
        exactly the committed records."""
        led = _ledger(tmp_path)
        with chaos.inject("runlog.observe", mode="error", nth=2,
                          n_times=1):
            assert led.append({"kind": "bench", "i": 0}) is True
            assert led.append({"kind": "bench", "i": 1}) is False
            assert led.append({"kind": "bench", "i": 2}) is True
        assert [r["i"] for r in led.read()] == [0, 2]
        assert monitor.get_stat("runlog_write_errors_total") == 1
        evs = flight.recent(10, kind="runlog.write_error")
        assert evs and evs[-1]["attrs"]["path"] == led.path

    def test_chaos_latency_absorbed(self, tmp_path):
        led = _ledger(tmp_path)
        with chaos.inject("runlog.observe", mode="latency",
                          latency=0.01, every=1):
            assert led.append({"kind": "bench"})
        assert len(led.read()) == 1

    def test_os_error_swallowed(self, tmp_path):
        led = runlog.RunLedger(
            str(tmp_path / "f.jsonl" / "cannot" / "nest"))
        # parent "f.jsonl" created as a FILE blocks the dir creation
        (tmp_path / "f.jsonl").write_text("x")
        assert led.append({"kind": "bench"}) is False
        assert monitor.get_stat("runlog_write_errors_total") == 1


# ---------------------------------------------------------------------------
# RunRecord capture + monitor.snapshot satellites
# ---------------------------------------------------------------------------

class TestCapture:
    def test_snapshot_labels_filter(self):
        monitor.stat_set("obsv_a", 1)
        monitor.stat_set("other_b", 2)
        monitor.observe("obsv_ms", 3.0)
        monitor.observe("other_ms", 4.0)
        snap = monitor.snapshot(labels=["obsv_"])
        assert "obsv_a" in snap["stats"]
        assert "other_b" not in snap["stats"]
        assert "obsv_ms" in snap["histograms"]
        assert "other_ms" not in snap["histograms"]
        # an EMPTY labels iterable means "no filter", not "drop all"
        snap = monitor.snapshot(labels=[])
        assert "obsv_a" in snap["stats"] and "other_b" in snap["stats"]
        # a bare string is one prefix, not a per-character filter
        snap = monitor.snapshot(labels="obsv_")
        assert "obsv_a" in snap["stats"]
        assert "other_b" not in snap["stats"]

    def test_snapshot_carries_flight_kind_totals(self):
        cap = int(get_flags("flight_capacity")["flight_capacity"])
        for _ in range(cap + 5):
            flight.record("obsv.test_kind")
        snap = monitor.snapshot()
        # lifetime totals, NOT ring-bounded
        assert snap["flight_events"]["obsv.test_kind"] == cap + 5

    def test_capture_summary_and_meta(self):
        monitor.reset_all_stats()
        monitor.reset_all_histograms()
        for v in (10.0, 12.0, 11.0):
            monitor.observe("train_step_ms", v)
        monitor.stat_set("input_stall_pct", 3.5)
        monitor.stat_set("jit_compiles_total", 4)
        flight.record("health.anomaly", severity="warn")
        rec = runlog.capture("health_check", label="dense",
                             legs=[{"metric": "m", "value": 1.0,
                                    "unit": "x"}])
        assert rec["schema_version"] == runlog.SCHEMA_VERSION
        assert rec["kind"] == "health_check"
        s = rec["summary"]
        assert s["train_step_p99_ms"] > 0
        assert s["input_stall_pct"] == 3.5
        assert s["jit_compiles_total"] == 4.0
        assert rec["flight_events"].get("health.anomaly", 0) >= 1
        assert rec["legs"][0]["metric"] == "m"
        meta = rec["meta"]
        assert meta["host"] and meta["python"]
        assert "git_sha" in meta and "flags_overrides" in meta
        # the whole record is JSON-able (the ledger's contract)
        json.dumps(rec, default=str)

    def test_capture_trace_summary(self, tmp_path):
        tr = tracer.enable(str(tmp_path), label="cap")
        with tr.start_span("obsv.work"):
            pass
        tr.disable()
        rec = runlog.capture("health_check", trace_dir=str(tmp_path))
        names = {r["name"] for r in rec["trace_summary"]}
        assert "obsv.work" in names

    def test_span_summary_matches_trace_merge_rows(self, tmp_path):
        """The in-framework span reader (observability.span_summary —
        what RunRecord capture uses, no tools/ dependency) aggregates
        the same rows trace_merge.summarize derives from the merged
        chrome-trace."""
        from paddle_tpu.framework.observability import span_summary
        _write_span_file(str(tmp_path / "trace_a.jsonl"), "a",
                         [("x", 0.0, 1000.0), ("x", 10.0, 3000.0),
                          ("y", 0.0, 500.0)])
        rows = span_summary(str(tmp_path))
        merged = trace_merge.summarize(trace_merge.merge(
            [str(tmp_path / "trace_a.jsonl")]))
        assert rows == merged

    def test_train_epoch_range_appends_when_armed(self, tmp_path):
        from paddle_tpu.framework.auto_checkpoint import TrainEpochRange
        saved = get_flags("runlog_dir")
        set_flags({"runlog_dir": str(tmp_path)})
        try:
            ckpt = str(tmp_path / "acp")
            for _ in TrainEpochRange(2, "obsv_job",
                                     checkpoint_dir=ckpt):
                pass
            recs = runlog.RunLedger(
                str(tmp_path / runlog.LEDGER_NAME)).read()
            assert len(recs) == 1
            assert recs[0]["kind"] == "train_epoch"
            assert recs[0]["label"] == "obsv_job"
            assert recs[0]["epochs"]["end"] == 1
        finally:
            set_flags(saved)

    def test_train_epoch_range_off_without_flag(self, tmp_path):
        from paddle_tpu.framework.auto_checkpoint import TrainEpochRange
        assert str(get_flags("runlog_dir")["runlog_dir"]) == ""
        for _ in TrainEpochRange(1, "obsv_off",
                                 checkpoint_dir=str(tmp_path / "acp")):
            pass
        assert not os.path.exists(str(tmp_path / runlog.LEDGER_NAME))


# ---------------------------------------------------------------------------
# trace_merge satellites
# ---------------------------------------------------------------------------

def _write_span_file(path, label, spans):
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "process", "label": label,
                            "pid": 1, "clock_offset": 0.0}) + "\n")
        for name, ts, dur in spans:
            f.write(json.dumps({"kind": "span", "name": name,
                                "trace": "t", "span": "s",
                                "parent": None, "ts": ts, "dur": dur,
                                "status": "ok", "tid": 0,
                                "attrs": {}}) + "\n")


class TestTraceMergeSatellites:
    def test_summary_json_output(self, tmp_path, capsys):
        _write_span_file(str(tmp_path / "trace_a.jsonl"), "a",
                         [("x", 0.0, 1000.0), ("x", 2000.0, 3000.0)])
        out = str(tmp_path / "summary.json")
        rc = trace_merge.main(["--dir", str(tmp_path),
                               "--summary-json", out])
        assert rc == 0
        with open(out) as f:
            doc = json.load(f)
        assert doc["schema_version"] == 1
        rows = {r["name"]: r for r in doc["rows"]}
        assert rows["x"]["count"] == 2
        assert rows["x"]["mean_ms"] == pytest.approx(2.0)

    def test_dir_with_zero_span_files_errors(self, tmp_path, capsys):
        rc = trace_merge.main(["--dir", str(tmp_path), "--out",
                               str(tmp_path / "merged.json")])
        assert rc == 1
        assert not os.path.exists(str(tmp_path / "merged.json"))
        assert "no trace_*.jsonl" in capsys.readouterr().err

    def test_empty_dir_with_explicit_inputs_still_merges(self, tmp_path,
                                                         capsys):
        """--dir matching nothing must not reject a run that ALSO
        passed explicit span files — those merge on their own."""
        span = str(tmp_path / "trace_a.jsonl")
        _write_span_file(span, "a", [("x", 0.0, 1000.0)])
        cold = tmp_path / "cold"
        cold.mkdir()
        out = str(tmp_path / "merged.json")
        rc = trace_merge.main([span, "--dir", str(cold), "--out", out])
        assert rc == 0 and os.path.exists(out)


# ---------------------------------------------------------------------------
# perf_report attribute: the span <-> cost-model join
# ---------------------------------------------------------------------------

class TestAttribute:
    # 5 spans; the 12 ms max is the compile-carrying first dispatch —
    # the steady mean over the other four is exactly (52-12)/4 = 10 ms
    ROWS = [{"name": "train.step", "count": 5, "total_ms": 52.0,
             "mean_ms": 10.4, "p99_ms": 12.0, "max_ms": 12.0,
             "errors": 0},
            {"name": "jit.compile", "count": 1, "total_ms": 9.0,
             "mean_ms": 9.0, "p99_ms": 9.0, "max_ms": 9.0,
             "errors": 0}]
    COST = {"name": "TrainStep", "total_flops": 1_000_000,
            "total_bytes": 500_000, "n_eqns": 10,
            "by_op": [
                {"op": "dot_general", "flops": 900_000,
                 "bytes": 300_000, "count": 3},
                {"op": "add", "flops": 100_000, "bytes": 150_000,
                 "count": 4},
                {"op": "transpose", "flops": 0, "bytes": 50_000,
                 "count": 2}]}

    def test_join_attributes_ms_by_flop_share(self):
        prof = perf_report.attribute_profile(self.ROWS, self.COST)
        step = prof["step"]
        # the attribution base is the STEADY mean (compile span
        # dropped): 10 ms, not the raw 10.4 ms mean
        assert step["mean_ms"] == pytest.approx(10.0)
        assert step["mean_ms_with_compile"] == pytest.approx(10.4)
        assert step["achieved_flops_per_sec"] == pytest.approx(1e8)
        assert step["achieved_bytes_per_sec"] == pytest.approx(5e7)
        ops = {o["op"]: o for o in prof["ops"]}
        assert ops["dot_general"]["measured_ms"] == pytest.approx(9.0)
        assert ops["add"]["measured_ms"] == pytest.approx(1.0)
        assert "transpose" not in ops          # 0-flop: not attributable
        assert perf_report.check_profile(prof) == []

    def test_cli_rejects_mini_train_plus_cost_json(self, tmp_path,
                                                   capsys):
        cost = tmp_path / "cost.json"
        cost.write_text("{}")
        rc = perf_report.main(["attribute", "--mini-train", "1",
                               "--cost-json", str(cost)])
        assert rc == 2
        assert "mutually exclusive" in capsys.readouterr().err

    def test_check_fails_without_step_span(self):
        prof = perf_report.attribute_profile(
            [r for r in self.ROWS if r["name"] != "train.step"],
            self.COST)
        assert perf_report.check_profile(prof)

    def test_analyze_cost_attachment_structured(self):
        """TrainStep.analyze().cost carries the per-primitive PTA106
        aggregates the join consumes (no message-string parsing)."""
        import paddle_tpu.nn as nn
        from paddle_tpu.jit import TrainStep
        paddle.seed(0)
        net = nn.Linear(8, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        step = TrainStep(net, lambda m, x, y: ((m(x) - y) ** 2).mean(),
                         opt)
        rng = np.random.default_rng(0)
        x = paddle.to_tensor(rng.standard_normal((4, 8))
                             .astype(np.float32))
        y = paddle.to_tensor(rng.standard_normal((4, 4))
                             .astype(np.float32))
        cost = step.analyze(x, y).cost
        assert cost["total_flops"] > 0 and cost["total_bytes"] > 0
        ops = {o["op"] for o in cost["by_op"]}
        assert "dot_general" in ops
        flops = [o["flops"] for o in cost["by_op"]]
        assert flops == sorted(flops, reverse=True)
        assert sum(flops) == cost["total_flops"]

    def test_mini_train_e2e_top5_measured_and_finite(self, tmp_path):
        """The acceptance criterion end-to-end: a traced 3-step mini
        train joins into a profile where every top-5 PTA106 op has a
        measured ms and a finite achieved FLOP/s."""
        cost = perf_report.mini_train_cost(3, str(tmp_path))
        paths = sorted(
            str(p) for p in tmp_path.glob("trace_*.jsonl"))
        rows = trace_merge.summarize(trace_merge.merge(paths))
        prof = perf_report.attribute_profile(rows, cost)
        assert perf_report.check_profile(prof, top_k=5) == []
        assert len(prof["ops"]) == 5
        for o in prof["ops"]:
            assert o["measured_ms"] > 0
            assert np.isfinite(o["achieved_flops_per_sec"])
        # and it renders
        text = perf_report.format_attribute(prof)
        assert "train.step" in text and "dot_general" in text


# ---------------------------------------------------------------------------
# perf_report compare: Detector over ledger series
# ---------------------------------------------------------------------------

def _mk_record(i, kind="health_check", label="ps", summary=None,
               legs=None):
    return {"schema_version": runlog.SCHEMA_VERSION, "kind": kind,
            "label": label, "run_id": f"r{i}", "summary": summary or {},
            "legs": legs or []}


class TestCompare:
    def test_clean_pair_no_regressions(self):
        recs = [_mk_record(0, summary={"train_step_p99_ms": 10.0,
                                       "ps_rpc_p99_ms": 0.9}),
                _mk_record(1, summary={"train_step_p99_ms": 10.4,
                                       "ps_rpc_p99_ms": 1.1})]
        res = perf_report.compare_records(recs)
        assert res["regressions"] == [] and res["improvements"] == []

    def test_seeded_latency_regression_named_and_deterministic(self):
        """The ledger-series twin of the acceptance test: two clean
        runs, then one whose RPC p99 jumped two orders of magnitude —
        flagged under the signal's NAME, byte-identical verdict across
        invocations (Detector is value-driven; compare injects a zero
        clock)."""
        recs = [_mk_record(i, summary={"train_step_p99_ms": 10.0 + i,
                                       "ps_rpc_p99_ms": 0.9 + 0.1 * i})
                for i in range(2)]
        recs.append(_mk_record(2, summary={"train_step_p99_ms": 11.0,
                                           "ps_rpc_p99_ms": 150.0}))
        r1 = perf_report.compare_records(recs)
        r2 = perf_report.compare_records(recs)
        assert r1 == r2
        assert len(r1["regressions"]) == 1
        reg = r1["regressions"][0]
        assert reg["signal"] == "ps_rpc_p99_ms"
        assert reg["run"] == "r2" and reg["direction"] == "up"
        # a NAMED regression reaches the text verdict too
        text = perf_report.format_compare(r1)
        assert "REGRESSION" in text and "ps_rpc_p99_ms" in text

    def test_throughput_drop_is_regression_gain_is_improvement(self):
        base = [{"metric": "widget_examples_per_sec", "value": 1000.0,
                 "unit": "examples/s", "vs_baseline": 1.0}]
        recs = [_mk_record(i, kind="bench", label="bench",
                           legs=[dict(base[0])]) for i in range(3)]
        slow = dict(base[0], value=400.0)
        res = perf_report.compare_records(
            recs + [_mk_record(3, kind="bench", label="bench",
                               legs=[slow])])
        assert [r["signal"] for r in res["regressions"]] == \
            ["bench:widget_examples_per_sec"]
        fast = dict(base[0], value=2500.0)
        res = perf_report.compare_records(
            recs + [_mk_record(3, kind="bench", label="bench",
                               legs=[fast])])
        assert res["regressions"] == []
        assert [r["signal"] for r in res["improvements"]] == \
            ["bench:widget_examples_per_sec"]

    def test_nonfinite_measurement_is_always_a_regression(self):
        """A NaN throughput leg must gate (Detector's z=inf rule) even
        though the signal's worse-direction is DOWN — a blown-up
        measurement must never read as an improvement."""
        recs = [_mk_record(i, kind="bench", label="bench", legs=[
            {"metric": "w_examples_per_sec", "value": 1000.0,
             "unit": "examples/s"}]) for i in range(2)]
        recs.append(_mk_record(2, kind="bench", label="bench", legs=[
            {"metric": "w_examples_per_sec", "value": float("nan"),
             "unit": "examples/s"}]))
        res = perf_report.compare_records(recs)
        assert res["improvements"] == []
        assert [r["signal"] for r in res["regressions"]] == \
            ["bench:w_examples_per_sec"]
        assert res["regressions"][0]["direction"] == "nonfinite"

    def test_wire_bytes_growth_flagged(self):
        recs = [_mk_record(i, kind="bench", label="bench", legs=[
            {"metric": "x_wire_mb_per_step", "value": 10.0,
             "unit": "MB"}]) for i in range(2)]
        recs.append(_mk_record(2, kind="bench", label="bench", legs=[
            {"metric": "x_wire_mb_per_step", "value": 18.0,
             "unit": "MB"}]))
        res = perf_report.compare_records(recs)
        assert [r["signal"] for r in res["regressions"]] == \
            ["bench:x_wire_mb_per_step"]

    def test_single_run_series_insufficient_not_regression(self):
        recs = [_mk_record(0, summary={"train_step_p99_ms": 10.0}),
                _mk_record(1, summary={})]
        res = perf_report.compare_records(recs)
        assert res["regressions"] == []
        assert any(i["signal"] == "train_step_p99_ms"
                   for i in res["insufficient"])

    def test_groups_do_not_cross_contaminate(self):
        """A dense group's step time must not enter the ps group's
        baseline: same signal name, separate (kind, label) series."""
        recs = [_mk_record(i, label="dense",
                           summary={"train_step_p99_ms": 5.0})
                for i in range(2)]
        recs += [_mk_record(i, label="ps",
                            summary={"train_step_p99_ms": 500.0})
                 for i in range(2)]
        res = perf_report.compare_records(recs)
        assert res["regressions"] == []

    def test_compile_count_jump_flagged(self):
        recs = [_mk_record(i, summary={"jit_compiles_total": 4.0})
                for i in range(3)]
        recs.append(_mk_record(3, summary={"jit_compiles_total": 14.0}))
        res = perf_report.compare_records(recs)
        assert [r["signal"] for r in res["regressions"]] == \
            ["jit_compiles_total"]

    def test_failed_and_skipped_legs_are_not_series(self):
        recs = [_mk_record(i, kind="bench", label="bench", legs=[
            {"metric": "bench_gpt2_FAILED", "value": 0.0, "unit": "x"},
            {"metric": "gpt2_zero_dp2_SKIPPED_single_device",
             "value": 0.0, "unit": "n/a"},
            {"metric": "device_unavailable", "value": 0.0,
             "unit": "x"}]) for i in range(3)]
        res = perf_report.compare_records(recs)
        assert res["groups"][0]["signals"] == []

    def test_ledger_to_verdict_cli_roundtrip(self, tmp_path):
        led = _ledger(tmp_path)
        for i in range(2):
            assert led.append(_mk_record(
                i, summary={"ps_rpc_p99_ms": 1.0}))
        assert perf_report.main(["compare", "--ledger", led.path]) == 0
        assert led.append(_mk_record(
            2, summary={"ps_rpc_p99_ms": 120.0}))
        out = str(tmp_path / "verdict.json")
        rc = perf_report.main(["compare", "--ledger", led.path,
                               "--json", out])
        assert rc == 1
        with open(out) as f:
            verdict = json.load(f)
        assert verdict["regressions"][0]["signal"] == "ps_rpc_p99_ms"
        # --max-regressions tolerance path
        assert perf_report.main(["compare", "--ledger", led.path,
                                 "--max-regressions", "1"]) == 0


# ---------------------------------------------------------------------------
# health_check --ledger producer hook
# ---------------------------------------------------------------------------

class TestHealthCheckLedger:
    def test_mini_train_appends_run_record(self, tmp_path, capsys):
        from tools import health_check
        led = str(tmp_path / "ledger.jsonl")
        rc = health_check.main(["--mini-train", "5", "--ledger", led,
                                "--trace-dir",
                                str(tmp_path / "traces")])
        assert rc == 0
        recs = runlog.RunLedger(led).read()
        assert len(recs) == 1
        rec = recs[0]
        assert rec["kind"] == "health_check" and rec["label"] == "dense"
        assert rec["steps"] == 5 and rec["tripped"] == []
        assert rec["summary"]["train_step_p99_ms"] > 0
        names = {r["name"] for r in rec["trace_summary"]}
        assert "train.step" in names

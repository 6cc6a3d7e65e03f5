#!/usr/bin/env bash
# CI gate (reference roles: paddle/scripts/paddle_build.sh test stages,
# tools/test_op_benchmark.sh, tools/check_api_compatible.py).
#
#   tools/ci.sh            # full gate: tests + API freeze + op-bench check
#   tools/ci.sh quick      # tests only
#
# The op-benchmark regression stage only runs when a baseline exists
# (tools/op_bench_baseline.json — record one on your hardware with
# `python tools/op_bench.py --save tools/op_bench_baseline.json`).
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== pytest =="
# slow-marked tests (e.g. the SIGKILL-mid-save chaos test) run once, in
# the chaos lane below — not here
python -m pytest tests/ -q -m "not slow"

if [ "${1:-}" = "quick" ]; then exit 0; fi

echo "== chaos fault-injection lane (fixed seed, incl. slow) =="
# re-runs the fault-injection suite with the registry seeded through the
# ENV path (FLAGS_chaos_seed), proving the launcher-side arming channel
# end-to-end and pinning determinism
JAX_PLATFORMS=cpu FLAGS_chaos_seed=1234 \
    python -m pytest tests/test_chaos.py -q

echo "== elastic membership/re-form lane (fixed seed, incl. slow) =="
# the job-level recovery tier: lease-expiry shrink to loss parity,
# hang-watchdog kill+replace, SIGKILL-a-worker-mid-epoch multi-process
# re-form — deterministic (fake clock + fixed chaos seed)
JAX_PLATFORMS=cpu FLAGS_chaos_seed=1234 \
    python -m pytest tests/test_elastic.py -q

echo "== observability lane (traced mini train -> trace_merge -> schema; prometheus grammar; cluster collector) =="
# 3-step mini train with tracing armed, per-process span file merged by
# tools/trace_merge.py into a chrome trace that must pass the schema
# check; monitor.export_prometheus() must round-trip through the
# Prometheus text-format grammar (incl. cumulative-bucket invariants
# and the # HELP-per-metric scraper contract).  The collector leg then
# gates the cluster telemetry plane: with collector.rpc faults injected
# the training trajectory is bit-identical to a collector-less run
# (drops counted, nothing blocks), and in a clean mini cluster
# (2 workers + 1 PS server + collector) the rank with injected step
# latency is named in the straggler report, the cluster_top view
# (schema-validated), and the cluster-level ledger record perf_report
# compare consumes
JAX_PLATFORMS=cpu python tools/obs_check.py

echo "== ingest lane (JPEG corpus -> full pipeline; stall + cache gates) =="
# a small on-disk JPEG corpus through the streaming ingest plane
# (decode -> uint8 augment -> batch collate -> double-buffered device
# transfer): asserts op_bench-style thresholds on the cache-epoch
# speedup and the overlapped input_stall_pct, and that the gauge,
# per-stage histograms and cache counters export via
# monitor.export_prometheus(); parity/chaos/fault behavior is covered
# by tests/test_ingest_pipeline.py in the pytest lane above
JAX_PLATFORMS=cpu python tools/ingest_check.py

echo "== perf health lane (traced mini train -> health_check; zero anomalies, zero steady recompiles) =="
# the health plane's decision surface end-to-end: a fixed-seed,
# fixed-shape mini train with the default detectors armed must compile
# once per jit site and trip nothing — a steady-state recompile or a
# detector anomaly on a healthy run fails here (the same gate the
# acceptance test drives with injected ps.rpc latency, inverted)
JAX_PLATFORMS=cpu python tools/health_check.py --mini-train 30 \
    --max-anomalies 0 --max-steady-recompiles 0

echo "== model numerics lane (in-jit stats; zero grad anomalies, NaN provenance) =="
# the model-signal twin of the health lane: (1) a clean mini train with
# the numerics plane armed must trip zero grad-norm anomalies and zero
# steady recompiles (arming must not churn the jit cache); (2) a run
# with ONE layer's gradient NaN-poisoned at step 20 must skip-and-
# restore, name that leaf as first_bad_leaf in the train.nan_skip
# flight event AND fire the grad-norm detector at the poisoned step
# (both gated by the implicit --nan-step provenance verdict), with
# exactly that one anomaly per drift signal and a clean baseline after
JAX_PLATFORMS=cpu python tools/health_check.py --mini-train 30 --numerics \
    --max-anomalies 0 --max-grad-anomalies 0 --max-steady-recompiles 0
JAX_PLATFORMS=cpu python tools/health_check.py --mini-train 30 \
    --nan-step 20 --max-anomalies 3 --max-grad-anomalies 1

echo "== perf observatory lane (run ledger -> span/cost join -> cross-run regression gate) =="
# (1) span<->cost attribution: a traced 3-step mini train joined with
# the PTA106 analytic cost model must yield an op-profile where every
# top-5 op has a measured ms and a finite achieved FLOP/s (--check).
# (2) two seeded PS mini-train runs appended to a fresh ledger must
# compare clean; a third run with ps.rpc latency injected from step 0
# — a level shift the in-run detector's warmup absorbs, so that run's
# own gates stay green — MUST be flagged by the cross-run compare
# (named signal, nonzero exit).
OBSV=$(mktemp -d /tmp/pt_observatory.XXXXXX)
JAX_PLATFORMS=cpu python tools/perf_report.py attribute --mini-train 3 \
    --json "$OBSV/profile.json" --check
JAX_PLATFORMS=cpu python tools/health_check.py --mini-train 15 --ps \
    --ledger "$OBSV/ledger.jsonl" --max-anomalies 0
JAX_PLATFORMS=cpu python tools/health_check.py --mini-train 15 --ps \
    --ledger "$OBSV/ledger.jsonl" --max-anomalies 0
JAX_PLATFORMS=cpu python tools/perf_report.py compare \
    --ledger "$OBSV/ledger.jsonl"
JAX_PLATFORMS=cpu FLAGS_chaos_seed=1234 \
    FLAGS_chaos_spec='{"ps.rpc": {"mode": "latency", "latency": 0.1, "every": 1}}' \
    python tools/health_check.py --mini-train 15 --ps \
    --ledger "$OBSV/ledger.jsonl" --max-anomalies 0
# the gate demands BOTH the nonzero exit AND a named REGRESSION line in
# the verdict — a comparator that crashed (tracebacks also exit 1)
# cannot fake a flag
rc=0
JAX_PLATFORMS=cpu python tools/perf_report.py compare \
    --ledger "$OBSV/ledger.jsonl" | tee "$OBSV/verdict.txt" || rc=$?
if [ "$rc" != 1 ] || ! grep -q "^REGRESSION .*ps_rpc" "$OBSV/verdict.txt"; then
  echo "observatory lane FAILED: injected ps.rpc latency run not flagged (rc=$rc)" >&2
  exit 1
fi
rm -rf "$OBSV"

echo "== causal blame lane (span links -> critical path -> bottleneck-shift gate) =="
# (1) clean traced PS mini-train: the per-step blame DAG must
# reconstruct with ZERO unresolved links and its categories must sum
# to within 5% of the measured step span (--check — the partition-
# exactness acceptance).  (2) chaos leg: ps.rpc latency injected from
# step 0 must make ps_wait the named TOP blame category (--expect-top
# — "98% input stall vs PS wait" is now a computed verdict, not a
# human reading merged traces).  (3) cross-run: two clean ledgered
# runs + the latency run — each green on its OWN gates (the level
# shift hides in warmup) — must be flagged by perf_report compare on
# the blame_ps_wait_ms series BY NAME with rc 1 (a crashed comparator
# also exits 1, hence the grep)
BLAME=$(mktemp -d /tmp/pt_blame.XXXXXX)
JAX_PLATFORMS=cpu python tools/perf_report.py blame --mini-train 12 \
    --json "$BLAME/blame.json" --check
JAX_PLATFORMS=cpu FLAGS_chaos_seed=1234 \
    FLAGS_chaos_spec='{"ps.rpc": {"mode": "latency", "latency": 0.1, "every": 1}}' \
    python tools/perf_report.py blame --mini-train 12 --check \
    --expect-top ps_wait
JAX_PLATFORMS=cpu python tools/health_check.py --mini-train 12 --ps \
    --ledger "$BLAME/ledger.jsonl" --max-anomalies 0
JAX_PLATFORMS=cpu python tools/health_check.py --mini-train 12 --ps \
    --ledger "$BLAME/ledger.jsonl" --max-anomalies 0
JAX_PLATFORMS=cpu FLAGS_chaos_seed=1234 \
    FLAGS_chaos_spec='{"ps.rpc": {"mode": "latency", "latency": 0.1, "every": 1}}' \
    python tools/health_check.py --mini-train 12 --ps \
    --ledger "$BLAME/ledger.jsonl" --max-anomalies 0
rc=0
JAX_PLATFORMS=cpu python tools/perf_report.py compare \
    --ledger "$BLAME/ledger.jsonl" | tee "$BLAME/verdict.txt" || rc=$?
if [ "$rc" != 1 ] || ! grep -q "^REGRESSION .*blame_ps_wait" "$BLAME/verdict.txt"; then
  echo "blame lane FAILED: bottleneck shift to ps_wait not named (rc=$rc)" >&2
  exit 1
fi
rm -rf "$BLAME"

echo "== concurrency lint + lock watchdog lane (PTA4xx static; runtime cycle naming) =="
# static half: the in-tree sources must be PTA4xx-clean (zero errors AND
# zero warnings — every accepted pattern carries an audited pragma), the
# rule table must match the README rows, and the committed two-lock
# inversion fixture MUST be flagged (a pass suite that can't see the
# seeded bug gates nothing)
JAX_PLATFORMS=cpu python tools/prog_lint.py --threads paddle_tpu --strict
JAX_PLATFORMS=cpu python tools/prog_lint.py --list-rules --check-docs
rc=0
JAX_PLATFORMS=cpu python tools/prog_lint.py --threads \
    tests/fixtures/lock_inversion.py --format=json \
    > /tmp/pt_threads_fixture.json || rc=$?
if [ "$rc" != 1 ] || ! grep -q '"PTA401"' /tmp/pt_threads_fixture.json; then
  echo "concurrency lane FAILED: inversion fixture not flagged (rc=$rc)" >&2
  exit 1
fi
# dynamic half: executing the SAME fixture under FLAGS_lock_watchdog
# must name the same cycle in a locks.cycle flight event while the run
# completes normally (exit 0) — the static model validated by runtime
JAX_PLATFORMS=cpu FLAGS_lock_watchdog=1 \
    python tests/fixtures/lock_inversion.py | tee /tmp/pt_watchdog.txt
if ! grep -q "LOCK_CYCLE fixture.inversion.a fixture.inversion.b" \
    /tmp/pt_watchdog.txt; then
  echo "concurrency lane FAILED: watchdog did not name the cycle" >&2
  exit 1
fi
rm -f /tmp/pt_threads_fixture.json /tmp/pt_watchdog.txt

echo "== distributed-semantics lane (PTA5xx static; runtime replica-parity probe) =="
# static half: the whole package AST-lints clean at --strict AND the
# parallel-tier zoo (zero/sharded/tp/ring traced on a virtual mesh)
# carries zero PTA5xx errors/warnings; the committed divergence fixture
# MUST be flagged PTA501 naming fixture.w2 (a pass suite that can't see
# the seeded bug gates nothing)
JAX_PLATFORMS=cpu python tools/prog_lint.py --collectives paddle_tpu \
    --zoo zero_step --zoo sharded_step --zoo tp_layers \
    --zoo ring_attention --strict --no-cost
rc=0
JAX_PLATFORMS=cpu python tools/prog_lint.py --collectives \
    tests/fixtures/replica_divergence.py --format=json \
    > /tmp/pt_collectives_fixture.json || rc=$?
if [ "$rc" != 1 ] || ! grep -q '"PTA501"' /tmp/pt_collectives_fixture.json \
    || ! grep -q 'fixture.w2' /tmp/pt_collectives_fixture.json; then
  echo "distributed lane FAILED: divergence fixture not flagged (rc=$rc)" >&2
  exit 1
fi
# dynamic half: executing the SAME fixture under FLAGS_replica_parity
# must name the IDENTICAL leaf in a parity.divergence flight event
# while the run completes normally (exit 0) — static model validated
# by runtime
JAX_PLATFORMS=cpu FLAGS_replica_parity=1 \
    python tests/fixtures/replica_divergence.py | tee /tmp/pt_parity.txt
if ! grep -q "PARITY_DIVERGENCE fixture.w2" /tmp/pt_parity.txt; then
  echo "distributed lane FAILED: probe did not name fixture.w2" >&2
  exit 1
fi
# chaos leg: an injected parity.observe error is swallowed+counted and
# the probed training trajectory stays BIT-IDENTICAL to the clean run
JAX_PLATFORMS=cpu FLAGS_chaos_seed=1234 \
    python tests/fixtures/replica_divergence.py --chaos \
    | tee /tmp/pt_parity_chaos.txt
if ! grep -q "CHAOS_PARITY_BITIDENTICAL" /tmp/pt_parity_chaos.txt; then
  echo "distributed lane FAILED: chaos leg perturbed the trajectory" >&2
  exit 1
fi
rm -f /tmp/pt_collectives_fixture.json /tmp/pt_parity.txt \
    /tmp/pt_parity_chaos.txt

echo "== pallas kernel lane (PTA6xx static; interpret-mode differential oracle) =="
# static half: every in-tree pallas_call (package sources + the kernel
# zoo traced at tail-bearing shapes) must be PTA6xx-clean at --strict —
# zero errors AND zero warnings; the committed floored-grid fixture
# MUST be flagged PTA601 + PTA603 naming fixture.out (a pass suite
# that can't see the seeded tiling bug gates nothing)
JAX_PLATFORMS=cpu python tools/prog_lint.py --pallas \
    paddle_tpu/ops/pallas paddle_tpu/parallel/ring_attention.py \
    --zoo all --strict
rc=0
JAX_PLATFORMS=cpu python tools/prog_lint.py --pallas \
    tests/fixtures/pallas_oob.py --format=json \
    > /tmp/pt_pallas_fixture.json || rc=$?
if [ "$rc" != 1 ] || ! grep -q '"PTA601"' /tmp/pt_pallas_fixture.json \
    || ! grep -q '"PTA603"' /tmp/pt_pallas_fixture.json \
    || ! grep -q 'fixture.out' /tmp/pt_pallas_fixture.json; then
  echo "pallas lane FAILED: tiling fixture not flagged (rc=$rc)" >&2
  exit 1
fi
# dynamic half: the SAME fixture under FLAGS_pallas_verify must make
# the differential oracle (interpret leg vs pure-jnp reference — the
# CPU legs) name the IDENTICAL operand in a pallas.divergence flight
# event while the run completes normally (exit 0) — the static model
# validated by runtime
JAX_PLATFORMS=cpu FLAGS_pallas_verify=1 \
    python tests/fixtures/pallas_oob.py | tee /tmp/pt_pallas.txt
if ! grep -q "PALLAS_DIVERGENCE fixture.out" /tmp/pt_pallas.txt; then
  echo "pallas lane FAILED: oracle did not name fixture.out" >&2
  exit 1
fi
# chaos leg: an injected pallas.verify error is swallowed+counted
# (pallas_verify_errors_total) and the watched computation is untouched
JAX_PLATFORMS=cpu FLAGS_chaos_seed=1234 \
    python tests/fixtures/pallas_oob.py --chaos \
    | tee /tmp/pt_pallas_chaos.txt
if ! grep -q "CHAOS_PALLAS_SWALLOWED" /tmp/pt_pallas_chaos.txt; then
  echo "pallas lane FAILED: verify fault not swallowed+counted" >&2
  exit 1
fi
rm -f /tmp/pt_pallas_fixture.json /tmp/pt_pallas.txt /tmp/pt_pallas_chaos.txt

echo "== durability lane (verified generations; SIGKILL-mid-async-save; bit-flip recovery; offline fsck) =="
# the durable-state plane end-to-end: (1) clean leg — three generations
# (sync + async + async) save, commit-after-verify, and restore
# bit-exact.  (2) corruption leg — a bit-flipped shard in the newest
# committed generation makes the walk land on the older verified one BY
# NAME, firing the named ckpt.corrupt flight event, with GC keeping the
# survivor; the offline fsck must then name the corrupt file and exit 1.
# (3) SIGKILL leg — a child killed mid-ASYNC-save leaves a torn,
# uncommitted generation the walk skips; recovery lands on the newest
# verified generation by name.  (4) chaos leg — ckpt.async armed ERROR
# under the fixed seed degrades every async save to a counted sync save
# and the trajectory is bit-identical to its replay.
DURA=$(mktemp -d /tmp/pt_durable.XXXXXX)
JAX_PLATFORMS=cpu python tests/fixtures/durable_ckpt.py clean \
    "$DURA/clean" | tee "$DURA/clean.txt"
grep -q "DURABLE_CLEAN gen=3" "$DURA/clean.txt" || {
  echo "durability lane FAILED: clean leg did not restore gen 3" >&2
  exit 1; }
JAX_PLATFORMS=cpu python tests/fixtures/durable_ckpt.py corrupt \
    "$DURA/corrupt" | tee "$DURA/corrupt.txt"
if ! grep -q "DURABLE_RECOVERED gen_00000001" "$DURA/corrupt.txt" \
    || ! grep -q "FLIGHT ckpt.corrupt" "$DURA/corrupt.txt"; then
  echo "durability lane FAILED: bit-flip recovery or ckpt.corrupt event missing" >&2
  exit 1
fi
# offline fsck: must NAME the corrupt shard and exit 1
rc=0
JAX_PLATFORMS=cpu python tools/ckpt_check.py verify "$DURA/corrupt" \
    | tee "$DURA/fsck.txt" || rc=$?
if [ "$rc" != 1 ] || ! grep -q "crc_mismatch" "$DURA/fsck.txt" \
    || ! grep -q "CORRUPT  gen_00000002" "$DURA/fsck.txt"; then
  echo "durability lane FAILED: fsck did not name the corrupt file (rc=$rc)" >&2
  exit 1
fi
JAX_PLATFORMS=cpu python tests/fixtures/durable_ckpt.py sigkill-parent \
    "$DURA/sigkill" | tee "$DURA/sigkill.txt"
grep -q "DURABLE_SIGKILL_RECOVERED gen_00000001" "$DURA/sigkill.txt" || {
  echo "durability lane FAILED: SIGKILL-mid-async-save recovery" >&2
  exit 1; }
JAX_PLATFORMS=cpu FLAGS_chaos_seed=1234 \
    python tests/fixtures/durable_ckpt.py chaos "$DURA/chaos" \
    | tee "$DURA/chaos.txt"
grep -q "CKPT_CHAOS_BITIDENTICAL" "$DURA/chaos.txt" || {
  echo "durability lane FAILED: armed-chaos trajectory not bit-identical" >&2
  exit 1; }
rm -rf "$DURA"

echo "== postmortem lane (incident capture -> deterministic replay -> first-divergence bisect; torn-bundle refusal; cheap-when-off) =="
# the postmortem plane end-to-end: (1) capture leg — a seeded
# train.step_grads NaN at step 3 must AUTO-capture a committed incident
# bundle (verify_bundle-clean, flight event stamped with the id, run
# ledger indexed).  (2) replay leg — tools/replay.py must rebuild the
# step from the bundle's program descriptor, re-arm the recorded chaos
# schedule, and reproduce the recorded signal naming the SAME
# first_bad_leaf; --bisect must re-execute CLEAN and land on the
# poisoned step BY NUMBER via the recorded trajectory hashes; both
# verdicts land back in the ledger and perf_report incidents joins
# them.  (3) SIGKILL leg — a capture killed mid-write leaves a torn,
# COMMIT-less directory that verify_bundle AND replay refuse.  (4)
# clean leg — disarmed, the poisoned run captures NOTHING; armed, the
# loss trajectory is BITWISE identical to the disarmed one (the ring
# is host-only reads).
PM=$(mktemp -d /tmp/pt_postmortem.XXXXXX)
JAX_PLATFORMS=cpu python tests/fixtures/postmortem_incident.py capture \
    "$PM/cap" | tee "$PM/capture.txt"
grep -q "INCIDENT_CAPTURED" "$PM/capture.txt" || {
  echo "postmortem lane FAILED: NaN skip did not capture a bundle" >&2
  exit 1; }
BUNDLE=$(grep "^INCIDENT_CAPTURED " "$PM/capture.txt" | awk '{print $2}')
LEDGER=$(grep "^INCIDENT_LEDGER " "$PM/capture.txt" | awk '{print $2}')
JAX_PLATFORMS=cpu python tools/replay.py "$BUNDLE" --ledger "$LEDGER" \
    | tee "$PM/replay.txt"
grep -q "REPLAY_REPRODUCED kind=train.nan_skip first_bad_leaf=aux_w" \
    "$PM/replay.txt" || {
  echo "postmortem lane FAILED: replay did not reproduce the recorded leaf" >&2
  exit 1; }
JAX_PLATFORMS=cpu python tools/replay.py "$BUNDLE" --bisect \
    --ledger "$LEDGER" | tee "$PM/bisect.txt"
grep -q "BISECT_DIVERGENCE step=2 leaf=aux_w" "$PM/bisect.txt" || {
  echo "postmortem lane FAILED: bisect did not land on the poisoned step" >&2
  exit 1; }
JAX_PLATFORMS=cpu python tools/perf_report.py incidents \
    --ledger "$LEDGER" | tee "$PM/incidents.txt"
grep -q "bisect:step=2,leaf=aux_w" "$PM/incidents.txt" || {
  echo "postmortem lane FAILED: ledger join lost the replay verdict" >&2
  exit 1; }
JAX_PLATFORMS=cpu python tests/fixtures/postmortem_incident.py \
    sigkill-parent "$PM/kill" | tee "$PM/kill.txt"
grep -q "INCIDENT_SIGKILL_TORN" "$PM/kill.txt" || {
  echo "postmortem lane FAILED: torn bundle not refused" >&2
  exit 1; }
JAX_PLATFORMS=cpu python tests/fixtures/postmortem_incident.py clean \
    "$PM/clean" | tee "$PM/clean.txt"
if ! grep -q "INCIDENT_DISARMED_SILENT" "$PM/clean.txt" \
    || ! grep -q "INCIDENT_BITIDENTICAL" "$PM/clean.txt"; then
  echo "postmortem lane FAILED: cheap-when-off gate (disarmed capture or armed bitwise drift)" >&2
  exit 1
fi
rm -rf "$PM"

echo "== program lint (jaxpr IR passes + jit-safety AST lint) =="
# whole-package AST lint plus the model-zoo jaxpr passes on the cheap-
# to-trace entries — elastic_step traces the resilient train step and
# lints the chaos-threaded elastic sources, so PTA301/302 cover the
# elastic.lease / elastic.worker_hang fault points; exits nonzero on any
# error-severity finding (warnings are reported but do not gate —
# promote with --strict once the corpus has been warning-clean a while)
JAX_PLATFORMS=cpu python tools/prog_lint.py paddle_tpu \
    --zoo lenet --zoo transformer_encoder --zoo elastic_step \
    --zoo ps_transport --zoo ingest --zoo health --zoo zero_step \
    --zoo numerics_step --zoo runlog --zoo collector --zoo ckpt \
    --zoo incident --format=json --min-severity warning

echo "== API signature freeze =="
JAX_PLATFORMS=cpu python tools/print_signatures.py --check

echo "== ZeRO collective byte gate (analytic wire MB per leg/dtype, dp=2) =="
# deterministic per-replica reduce-scatter/all-gather byte counts per
# wire dtype on the sharded-update step — a change that silently
# fattens a collective (or breaks the bf16=0.5x / int8~0.25x encodings)
# fails here; the fused-step wall clock is reported but NOT gated
JAX_PLATFORMS=cpu python tools/op_bench.py --zero-collectives \
    --compare tools/op_bench_baseline.json \
    --thresholds tools/op_bench_thresholds.json

echo "== fused ring collectives lane (wire-byte gate -> ledger improvement -> collective blame) =="
# (1) analytic per-leg wire MB of the chunked ring at dp=2 per wire
# dtype, gated vs baseline AND vs the f32 leg (bf16 <= 0.51x,
# int8 <= 0.26x, int4 <= 0.14x — in-function ceiling; wall clock
# reported, not gated).  (2) two clean f32 ZeRO mini-trains plus one
# int4-ring run appended to a fresh ledger: the cross-run compare MUST
# print the zero_collective_bytes_per_step series as a named
# IMPROVEMENT (bytes fell ~8x) with zero regressions — the observatory
# seeing the ring pay off.  (3) blame --check over the ring run's
# trace: the per-step DAG reconstructs (categories sum to the step
# span) and the fused path's fenced wait lands in the `collective`
# category — the same ms that ledgers as blame_collective_ms
RING=$(mktemp -d /tmp/pt_ring.XXXXXX)
JAX_PLATFORMS=cpu python tools/op_bench.py --ring-collectives \
    --compare tools/op_bench_baseline.json \
    --thresholds tools/op_bench_thresholds.json
JAX_PLATFORMS=cpu python tools/health_check.py --mini-train 12 --zero \
    --ledger "$RING/ledger.jsonl" --max-anomalies 0
JAX_PLATFORMS=cpu python tools/health_check.py --mini-train 12 --zero \
    --ledger "$RING/ledger.jsonl" --max-anomalies 0
JAX_PLATFORMS=cpu python tools/health_check.py --mini-train 12 --zero \
    --zero-wire int4 --zero-ring --trace-dir "$RING/trace" \
    --ledger "$RING/ledger.jsonl" --max-anomalies 0
rc=0
JAX_PLATFORMS=cpu python tools/perf_report.py compare \
    --ledger "$RING/ledger.jsonl" | tee "$RING/verdict.txt" || rc=$?
if [ "$rc" != 0 ] || \
   ! grep -q "^improvement .*zero_collective_bytes_per_step" "$RING/verdict.txt"; then
  echo "ring lane FAILED: int4 ring run not flagged as a wire-byte improvement (rc=$rc)" >&2
  exit 1
fi
JAX_PLATFORMS=cpu python tools/perf_report.py blame \
    --trace-dir "$RING/trace" --step-span zero.step --check \
    | tee "$RING/blame.txt"
if ! grep -q "zero.reduce_scatter \[child -> collective\]" "$RING/blame.txt"; then
  echo "ring lane FAILED: fused reduce-scatter wait not blamed as collective" >&2
  exit 1
fi
rm -rf "$RING"

echo "== replica-parity probe overhead gate (armed <= 2% step, disarmed exactly zero) =="
# armed: the probe's amortized cost at the default cadence must stay
# under 2% of the mlp1m step (in-function gate) and its analytic hash
# wire bytes are deterministic (compare gate); disarmed: zero probe
# invocations, zero compiled probe programs, step cache untouched
# (in-function gate — "exactly zero", not "small")
JAX_PLATFORMS=cpu python tools/op_bench.py --parity-probe \
    --compare tools/op_bench_baseline.json \
    --thresholds tools/op_bench_thresholds.json

echo "== PS transport byte gate (measured wire MB per op, host-side) =="
# deterministic byte counts per wire dtype — holds the line on
# transport bytes (a change that silently fattens the wire fails here);
# localhost wall-clock is reported but NOT gated
JAX_PLATFORMS=cpu python tools/op_bench.py --ps-transport \
    --compare tools/op_bench_baseline.json \
    --thresholds tools/op_bench_thresholds.json

if [ -f tools/op_bench_baseline.json ]; then
  echo "== op benchmark regression gate =="
  if [ -f tools/op_bench_thresholds.json ]; then
    # per-op thresholds sized from the measured run-to-run distribution
    # (max(0.15, 6×CV)); the gate is verified to
    # catch a planted 1.3x regression (tests/test_op_bench_gate.py)
    python tools/op_bench.py --compare tools/op_bench_baseline.json \
        --thresholds tools/op_bench_thresholds.json --iters 20
  else
    # no measured distribution yet: a blanket fallback wide enough for
    # run-to-run jitter
    python tools/op_bench.py --compare tools/op_bench_baseline.json \
        --threshold 1.0 --iters 20
  fi
else
  echo "== op benchmark gate skipped (no tools/op_bench_baseline.json) =="
fi
echo "CI gate passed."

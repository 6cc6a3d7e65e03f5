"""Typed global flag registry.

One config system replacing the reference's gflags (126 DEFINE_* across
platform/flags.cc etc.) + env-var bootstrap (python/paddle/fluid/__init__.py
__bootstrap__) + runtime get/set (pybind/global_value_getter_setter.cc:330,
surfaced as paddle.set_flags/get_flags).  Flags here are typed, env-seeded
(FLAGS_<name>), and readable/writable at runtime.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Dict

_registry: Dict[str, Any] = {}
_defaults: Dict[str, Any] = {}
_lock = threading.Lock()


def define_flag(name: str, default, help_str: str = ""):
    env = os.environ.get("FLAGS_" + name)
    value = default
    if env is not None:
        if isinstance(default, bool):
            value = env.lower() in ("1", "true", "yes")
        elif isinstance(default, int):
            value = int(env)
        elif isinstance(default, float):
            value = float(env)
        else:
            value = env
    with _lock:
        _registry[name] = value
        _defaults[name] = default
    return value


def get_flags(names):
    if isinstance(names, str):
        names = [names]
    out = {}
    for n in names:
        key = n[6:] if n.startswith("FLAGS_") else n
        if key not in _registry:
            raise ValueError(f"unknown flag {n}")
        out[n] = _registry[key]
    return out


def set_flags(flags: dict):
    for n, v in flags.items():
        key = n[6:] if n.startswith("FLAGS_") else n
        if key not in _registry:
            raise ValueError(f"unknown flag {n}")
        with _lock:
            _registry[key] = v


def flag(name: str):
    return _registry[name]


def overrides() -> Dict[str, Any]:
    """Every flag whose current value differs from its registered
    default — whether env-seeded (FLAGS_<name>) or set at runtime
    (set_flags).  This is what runlog.run_meta stamps into a RunRecord
    so a regression is attributable to the configuration that produced
    it."""
    with _lock:
        return {n: v for n, v in _registry.items()
                if n in _defaults and v != _defaults[n]}


# the flags the reference exposes that still mean something on TPU
define_flag("check_nan_inf", False,
            "per-op NaN/Inf watcher (ref: FLAGS_check_nan_inf, "
            "framework/details/nan_inf_utils.h)")
define_flag("benchmark", False, "sync + time every op")
define_flag("paddle_num_threads", 1, "host threads for data feeding")
define_flag("use_bf16_matmul", True,
            "allow bf16 matmul accumulation on MXU where AMP is active")
define_flag("cudnn_deterministic", False,
            "accepted for compat; XLA on TPU is deterministic by default")
define_flag("max_inplace_grad_add", 0, "compat no-op")
define_flag("gpt_fused_ce", False,
            "route gpt_loss through the blockwise Pallas linear+softmax-CE "
            "kernel (ops/pallas/fused_ce.py): trades nothing vs XLA on "
            "step time (XLA runs the unfused head at ~MXU peak on v5e) "
            "but eliminates the (B,S,V) f32 logits buffer — enable when "
            "HBM is the binding constraint")
define_flag("eager_op_jit_cache", True,
            "compiled (fwd, vjp) fast path for eager op dispatch, keyed on "
            "op semantics — plays the reference's generated core.ops role "
            "(pybind/op_function_generator.cc).  Cached fns must be pure in "
            "(args, kwargs, closure, defaults): mutable module-level state "
            "read inside an op is frozen at first call.  Disable for impure "
            "custom ops.")
define_flag("conv_workspace_size_limit", 512, "compat no-op")

# fault-tolerance tier (framework/chaos.py + ps/service.py retries):
define_flag("chaos_spec", "",
            "JSON {fault_point: schedule} armed into framework.chaos at "
            "first use — e.g. '{\"ps.rpc\": {\"mode\": \"error\", "
            "\"every\": 3, \"n_times\": 2}}'.  Env form lets the "
            "launcher arm a whole child-process tree; empty = chaos off")
define_flag("chaos_seed", 0,
            "seed for chaos probability schedules (deterministic suites "
            "pin this; the CI chaos lane runs with a fixed seed)")
define_flag("ps_rpc_timeout", 30.0,
            "socket timeout (s) per PS RPC (brpc_ps_client's "
            "rpc_timeout_ms role)")
define_flag("ps_rpc_max_retries", 3,
            "bounded retries per PS RPC before the endpoint is reported "
            "dead to the heartbeat monitor")
define_flag("ps_rpc_backoff_base", 0.05,
            "exponential backoff base (s): sleep base*2^attempt between "
            "PS RPC retries")
define_flag("download_retries", 3,
            "fetch attempts in utils.download before giving up")
define_flag("download_backoff_base", 0.1,
            "exponential backoff base (s) between download fetch retries")

# PS transport tier (ps/service.py wire format + PSTrainStep pipeline):
define_flag("ps_wire_dtype", "bf16",
            "wire encoding for PS pull rows / push grads: 'bf16' "
            "(default, half the f32 bytes, ~3 significant digits), "
            "'int8' (quarter the bytes, per-row scale), 'int4' "
            "(eighth the bytes, two nibbles per byte + per-row "
            "scale), or 'f32' (exact-parity fallback).  Negotiated "
            "per peer: bf16/int8 pulls decode whatever the reply "
            "header declares, int4 pulls and all quantized pushes "
            "engage only after a hello handshake confirms the server "
            "lists the dtype — old/new peers always interoperate at "
            "f32")
define_flag("zero_wire_dtype", "bf16",
            "wire encoding for the ZeRO sharded-update collectives "
            "(parallel/zero.py ShardedUpdateTrainStep reduce-scatter / "
            "all-gather legs): 'bf16' (default, half the f32 bytes), "
            "'int8' (quarter the bytes + one f32 scale per chunk), "
            "'int4' (eighth the bytes, packed nibbles + per-chunk "
            "scale), or 'f32' (exact fallback — trajectory-parity "
            "with the replicated TrainStep, pinned by tests).  "
            "Per-step override via ShardedUpdateTrainStep(wire_dtype=...)")
define_flag("zero_ring_collectives", False,
            "route the dp collective legs through the fused "
            "quantized ring (parallel/ring.py): quant/dequant "
            "overlapped with the neighbor ppermute, per-chunk scales "
            "on the wire.  Applies to ShardedUpdateTrainStep and "
            "CompressedAllReduceTrainStep; the f32 wire stays on the "
            "native XLA collectives (exact leg, bitwise-stable).  "
            "Per-step override via ring=True/False")
define_flag("ps_prefetch_depth", 1,
            "max in-flight prefetched pulls in PSTrainStep's pipeline "
            "(PSTrainStep.prefetch): 0 disables the pipeline, 1 is the "
            "classic double buffer — the next batch's shard fan-out "
            "rides a background executor while the chip runs the "
            "current step, coalesced with the previous step's push "
            "into one RPC round-trip per shard")

# ingest tier (io/pipeline.py streaming data plane):
define_flag("ingest_prefetch_depth", 1,
            "max in-flight batches in IngestPipeline's double buffer "
            "(decode+collate pulled from the loader and device-put on a "
            "background executor while the chip runs the current step); "
            "0 disables the overlap (synchronous fetch+transfer), 1 is "
            "the classic double buffer")
define_flag("ingest_cache_mode", "",
            "decoded-sample cache for epoch >= 2: '' (off), 'memory' "
            "(bounded in-RAM dict), or 'disk' (one crash-safe tmp+rename "
            "file per sample under FLAGS_ingest_cache_dir).  Epoch 1 "
            "records decoded tensors at cache granularity; later epochs "
            "skip JPEG decode entirely on a hit")
define_flag("ingest_cache_dir", "",
            "directory for the disk-backed decoded-sample cache "
            "(ingest_cache_mode='disk'); empty = a 'ingest_cache' dir "
            "under the current directory")
define_flag("ingest_cache_bytes", 1 << 30,
            "byte bound on the decoded-sample cache (memory or disk): "
            "inserts stop once the recorded payload bytes reach the "
            "bound, so a cache can never eat the host")

# observability tier (framework/observability.py + profiler):
define_flag("trace_dir", "",
            "directory for distributed-tracing span files; non-empty "
            "enables the process-wide Tracer, which appends finished "
            "spans to trace_<label>.jsonl there (label from "
            "PADDLE_TRACE_LABEL, set per child by the launcher).  Merge "
            "the per-process files with tools/trace_merge.py")
define_flag("trace_max_mb", 0.0,
            "size cap (MB) per tracer span-file segment: past it the "
            "segment rotates to trace_<label>.jsonl.1 (exactly one "
            "previous segment is kept — a week-long traced run costs "
            "at most 2x the cap on disk) and a fresh segment opens "
            "with a re-emitted process meta record.  Rotations count "
            "into trace_rotations_total, spans lost with an "
            "overwritten .1 segment into trace_spans_dropped_total; "
            "the cluster collector's incremental span cursor detects "
            "the segment change (inode/size) and resets without "
            "double-counting.  0 (default) = unbounded")
define_flag("flight_capacity", 512,
            "flight recorder ring size: the last N structured events "
            "(chaos trips, PS retries, NaN rollbacks, elastic "
            "membership changes) kept for crash dumps and the PS stat "
            "op's 'flight' field")
define_flag("flight_dir", "",
            "directory for flight_<worker>.json crash dumps "
            "(install_crash_handler); empty = current directory")
define_flag("metrics_export_interval", 30.0,
            "seconds between MetricsReporter writes of "
            "monitor.export_prometheus() to its textfile (atomic "
            "tmp+rename, scraper-safe)")
# cluster telemetry tier (framework/collector.py central collector +
# tools/cluster_top.py):
define_flag("collector_endpoint", "",
            "host:port of the central telemetry collector "
            "(framework/collector.py CollectorServer).  Non-empty arms "
            "collector.auto_reporter(): the process pushes periodic "
            "monitor.snapshot() deltas + flight-event deltas over the "
            "PS RPC framing, fire-and-forget (bounded queue, drop "
            "counter, collector.rpc chaos point) — collector loss can "
            "never slow or crash the pushing process.  The launcher "
            "exports it to every child (server AND trainer roles) as "
            "PADDLE_COLLECTOR_ENDPOINT, which takes precedence")
define_flag("collector_interval", 5.0,
            "seconds between telemetry pushes to the collector "
            "(MetricsReporter push mode / collector.auto_reporter)")
define_flag("collector_queue_capacity", 64,
            "bound on the collector push queue: a payload enqueued "
            "while the queue is full is DROPPED and counted "
            "(collector_dropped_total) — the pushing process never "
            "blocks on a slow or dead collector")
define_flag("collector_timeout", 2.0,
            "socket timeout (s) per collector push attempt; a timed-out "
            "push is a drop, never a retry storm")
define_flag("collector_straggler_ratio", 2.0,
            "straggler flag threshold: a worker whose per-interval step "
            "mean exceeds this multiple of the cluster median is named "
            "a straggler in the collector's view / cluster ledger "
            "record (and reported to ElasticAgent.note_stragglers)")
define_flag("ps_hot_row_k", 0,
            "bounded top-k hot-row sketch per host embedding table "
            "(space-saving counters over pulled ids, "
            "device_table.HotRowSketch): the PS stat op and the "
            "collector's cluster view report the k hottest rows per "
            "table — the telemetry a serving/online-learning row cache "
            "needs.  0 (default) disables the sketch: it costs an "
            "np.unique + bounded counter pass on EVERY pull, and "
            "per-step observability work is opt-in in this repo "
            "(FLAGS_numerics precedent); 32 is the recommended "
            "serving-telemetry setting")
# concurrency tier (framework/locks.py runtime lock-order watchdog):
define_flag("lock_watchdog", False,
            "arm the runtime lock-order watchdog: every tracked lock "
            "(locks.lock/locks.rlock — adopted by the PS service, "
            "cluster collector, ingest pipeline, and elastic agent) "
            "records per-thread acquisition order into a global "
            "held-before graph; a cycle fires a locks.cycle flight "
            "event naming the cycle, a hold past "
            "FLAGS_lock_hold_warn_ms fires locks.long_hold, and "
            "lock_waits_total/lock_hold_ms metrics export.  The "
            "watchdog NEVER raises (locks.observe chaos point + "
            "swallow-and-count guard).  Off (default): one flag "
            "lookup per acquire on top of the plain primitive")
define_flag("lock_hold_warn_ms", 1000.0,
            "hold time (ms) past which an armed lock watchdog fires a "
            "locks.long_hold flight event on release; 0 disables the "
            "long-hold check (the hold histogram still records)")
# perf health tier (framework/health.py detectors + compile/memory
# observability):
define_flag("health_detectors", "",
            "streaming anomaly detectors (framework/health.py): "
            "'' = off, 'default' arms the built-in signal set "
            "(train_step_ms, ps_rpc_ms, input_stall_pct, "
            "ps_prefetch_miss), or a JSON object "
            "'{\"signal\": {detector kwargs}}' for a custom set.  Env "
            "form lets a launcher arm a whole child-process tree")
define_flag("health_warmup", 16,
            "baseline samples a health.Detector collects before it "
            "starts scoring (per signal; the warmup absorbs compile "
            "steps and cold caches)")
define_flag("health_z_threshold", 8.0,
            "robust MAD z-score at which a health.Detector flags an "
            "anomaly (per-signal override via the detector spec)")
define_flag("health_compile_warmup_calls", 10,
            "calls per jit site within which recompiles count as "
            "warmup (shape bucketing, lazy first use); a recompile "
            "past this window is steady-state "
            "(jit_recompiles_steady_total) and feeds the "
            "compile-storm detector")
define_flag("health_compile_storm_k", 3,
            "post-warmup recompiles at one jit site that constitute a "
            "compile storm (health.compile_storm flight event)")
define_flag("health_mem_sample_every", 0,
            "sample jax.live_arrays() into device_mem_* gauges every "
            "N train steps (health.MemoryTracker); 0 disables the "
            "per-step hook (sample() stays callable directly)")
# model-numerics tier (framework/numerics.py in-jit tensor stats):
define_flag("numerics", False,
            "arm the model-numerics plane: TrainStep/PSTrainStep/"
            "ShardedUpdateTrainStep compute per-leaf + global grad/param "
            "norms, update ratios, max-abs and non-finite counts INSIDE "
            "the jitted step and publish them as monitor gauges/"
            "histograms + health-detector signals; ResilientTrainStep "
            "switches its finite check to the in-jit aux and stamps "
            "first_bad_leaf into train.nan_skip.  Off (default): the "
            "step traces exactly the disarmed computation — no extra "
            "outputs, no recompile")
define_flag("numerics_sample_every", 10,
            "per-leaf numerics export cadence: the numerics_*[<leaf>] "
            "attribution gauges refresh every Nth published step, and "
            "(when the cadence is > 0) on every non-finite step — the "
            "post-mortem wants the leaf split exactly then.  0 is a "
            "HARD off for the per-leaf export (the metric-cardinality "
            "cap on huge models; NaN provenance still reaches the "
            "flight event), global gauges/histograms still publish "
            "every step")
define_flag("numerics_scale_collapse_k", 4,
            "consecutive GradScaler downscales that constitute a loss-"
            "scale collapse: the amp.GradScaler update path exports its "
            "current scale as the amp_loss_scale gauge and records a "
            "numerics.scale_collapse flight event every K consecutive "
            "decreases (a scale halving K times without an intervening "
            "good streak is a systematic overflow, not a transient)")
# distributed-semantics tier (parallel/parity.py replica-parity probe):
define_flag("replica_parity", False,
            "arm the runtime replica-parity probe: the train-step "
            "classes (TrainStep and its sharded/dp variants) fold a "
            "per-leaf bitwise hash of every fully-replicated multi-"
            "device param/opt-state leaf through a psum-based "
            "agreement check every FLAGS_replica_parity_every steps; "
            "a divergent leaf fires a parity.divergence flight event "
            "naming the first divergent leaf (the same leaf a static "
            "PTA501 finding names) and counts "
            "parity_divergence_total.  The probe NEVER raises "
            "(parity.observe chaos point + swallow-and-count).  Off "
            "(default): one flag lookup per step — the step's own "
            "compiled computation and signature-cache keys are "
            "byte-identical to the probe-less seed")
define_flag("replica_parity_every", 16,
            "replica-parity probe cadence: hash-compare replicated "
            "state every Nth step of each armed train-step object "
            "(the probe is one tiny fused shard_map program; at the "
            "default cadence its cost amortizes below the op_bench "
            "--parity-probe 2% step-time gate)")
# pallas kernel verification tier (ops/pallas/verify.py differential
# oracle — the runtime half of the PTA6xx static passes):
define_flag("pallas_verify", False,
            "arm the Pallas differential oracle: verify_call() runs a "
            "kernel in interpret=True mode against its compiled form "
            "and against the pure-jnp reference on the call's shapes "
            "(flash_autotune additionally sweeps the boundary-shape "
            "corpus per tiling candidate before timing it); a "
            "disagreeing output fires a pallas.divergence flight "
            "event naming the first divergent operand with the SAME "
            "<name>.<operand> label the static PTA6xx pass uses and "
            "counts pallas_divergence_total.  The oracle NEVER raises "
            "(pallas.verify chaos point + swallow-and-count, "
            "pallas_verify_errors_total).  Off (default): one flag "
            "lookup — the kernel callables are not even invoked")
define_flag("pallas_vmem_budget_kb", 16384,
            "analytic VMEM budget (KB) for the static PTA605 pass: "
            "2x the double-buffered in/out block footprints plus "
            "scratch must fit; the 16 MB default is the v5e/v6e "
            "per-core VMEM.  <=0 disables the check")
# continuous-perf observatory (framework/runlog.py + tools/perf_report.py):
define_flag("runlog_dir", "",
            "directory of the persistent run ledger "
            "(<runlog_dir>/ledger.jsonl, append-only JSONL).  Non-empty "
            "arms the implicit producers — TrainEpochRange appends a "
            "RunRecord when an epoch range completes; the tool CLIs "
            "(--ledger) take an explicit path and work either way.  "
            "Empty = implicit run recording off")
# flight-recorder incident-storm guard (framework/observability.py):
define_flag("flight_storm_window", 1.0,
            "seconds within which identical (kind, attrs) flight "
            "events are deduplicated once flight_storm_k of them "
            "landed — a flapping signal during an incident cannot "
            "wash the bounded ring of its root cause.  Suppressed "
            "events still count into kind_totals and "
            "flight_suppressed_total.  0 disables the guard")
define_flag("flight_storm_k", 8,
            "identical (kind, attrs) flight events tolerated per "
            "flight_storm_window before further identical events are "
            "suppressed (ring skipped, counters still bumped)")
# postmortem tier (framework/incident.py IncidentRecorder +
# tools/replay.py):
define_flag("incident", False,
            "arm the postmortem plane: ResilientTrainStep/PSTrainStep "
            "keep a small host-side ring of recent step inputs (batch "
            "arrays or pulled-row ids, rng state, pre-step training "
            "state, chaos schedule) and a subscribed flight kind "
            "(FLAGS_incident_kinds) firing assembles a crash-safe "
            "incident bundle under FLAGS_incident_dir — checkpoint "
            "generation ref or inline state, the input ring, flags "
            "overrides, monitor snapshot, flight tail — that "
            "tools/replay.py re-executes standalone.  Capture NEVER "
            "raises (incident.capture chaos point + swallow-and-count) "
            "and never perturbs the trajectory (host-only reads).  Off "
            "(default): one flag lookup per step, signature-cache keys "
            "byte-identical to the seed")
define_flag("incident_kinds", "",
            "comma-separated flight kinds that trigger incident "
            "capture; empty = the built-in subscription "
            "(train.nan_skip, health.anomaly, numerics.scale_collapse, "
            "parity.divergence, pallas.divergence)")
define_flag("incident_dir", "",
            "directory incident bundles land under "
            "(incident_<NNNNNN>/ per capture, monotonic id from a "
            "directory scan); empty = 'incidents' under the current "
            "directory")
define_flag("incident_ring", 4,
            "steps of input history the armed IncidentRecorder keeps "
            "(host copies of step inputs + rng state + pre-step "
            "training state); the bundle replays exactly this window "
            "and --bisect walks it for the first divergent step")
define_flag("incident_state_cap_mb", 64.0,
            "inline-state size cap (MB) per incident bundle: below it "
            "the ring's oldest pre-step params/opt-state snapshot is "
            "embedded in the bundle (standalone replay, no checkpoint "
            "root needed); above it the bundle records a {root, "
            "generation} ref to the newest verified checkpoint "
            "generation instead.  0 forces the generation-ref path")
# durable-state tier (distributed/durable.py CheckpointManager +
# checkpoint.py async save + the SIGTERM emergency-save contract):
define_flag("ckpt_keep_last", 2,
            "checkpoint generations the GC always keeps (newest-first); "
            "the newest VERIFIED commit is kept unconditionally on top "
            "of this, so a bounded retention policy can never delete "
            "the only restorable state")
define_flag("ckpt_keep_every", 0,
            "additionally keep every Nth generation (by generation "
            "number) as a long-horizon archive — 0 disables; e.g. 100 "
            "keeps gen 0, 100, 200, ... forever while ckpt_keep_last "
            "bounds the rest")
define_flag("ckpt_emergency_deadline", 10.0,
            "seconds the SIGTERM emergency save may spend before the "
            "handler gives up and proceeds with the crash dump — the "
            "preemption contract: the save must fit the platform's "
            "grace window, a hung save must not eat it")
define_flag("profiler_max_spans", 100000,
            "cap on retained chrome-trace spans per profiling session; "
            "beyond it spans are dropped (counted — the Profiling "
            "Report and chrome-trace metadata report the drop count) "
            "while the aggregate report keeps counting every event")

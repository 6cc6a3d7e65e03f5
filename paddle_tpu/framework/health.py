"""Perf health plane: compile & device-memory observability with
streaming anomaly detection.

PR 5 gave the repo raw telemetry — spans, histograms, a flight
recorder — but nothing *watches* it: a recompile storm, an HBM creep,
or a step-time regression stayed invisible until a human read a
chrome-trace.  This module closes measurement into detection (the GDP
loop's missing middle: measure → **detect** → decide), three parts on
one design center (deterministic, clock-injectable, cheap when off):

* **Compile observability** — ``jit.StaticFunction`` / ``TrainStep`` /
  ``PSTrainStep`` report every signature-cache lookup here.  A miss is
  an XLA compile: :func:`note_compile` classifies the *recompile
  cause* by diffing the new signature against the cached ones
  (``new_signature`` / ``shape_change`` / ``dtype_change`` /
  ``static_arg_change``), bumps ``jit_compiles_total`` (+ a per-cause
  counter; a program jax compiles under a signature the site's own cache
  HIT, its retrace for an input's new sharding, is counted too, cause
  ``jax_retrace`` — scoped to the dispatch alone, ``TrainStep.launch``),
  records ``compile_ms`` (trace + lower + backend of that compile, as
  jax reports them), and counts ``jit_recompiles_steady_total`` when a
  site that already compiled recompiles past its warmup calls.  ≥K
  post-warmup compiles at one site is a **compile storm**: a
  ``health.compile_storm`` flight-recorder event fires so the
  post-mortem shows it next to the step-time anomalies it caused.
  Cache hits land in ``jit_cache_hits_total``.

  One listener takes every compile event jax emits: the three phases as
  time spans (jaxpr trace, lowering to StableHLO, backend compile) and
  the persistent cache's hits and misses.  Each phase is booked to the
  step call open on its thread (:func:`step_call`: a ``TrainStep`` call
  from the start of ``prepare`` to the end of ``commit``, so the first
  call's optimizer-state init and eager compiles count too), per site in
  :func:`compile_report` (``traces``, ``trace_s``, ``lower_s``,
  ``backend_s``, ``cold_compile_s``) and process-wide in
  ``jit_traces_total`` and
  ``jit_{trace,lower,backend,cold_compile}_seconds_total``.
  A nested interval counts once, for the innermost phase, so the
  phases' seconds never overlap; a backend compile is cold
  unless the persistent cache reported a hit inside it.  Compiles
  outside any step call are booked to no step site.  While the profiler
  is on, each phase is also a ``jit.trace`` / ``jit.lower`` /
  ``jit.backend_compile`` row on the profiler's clock.

* **Device-memory observability** — :class:`MemoryTracker` samples
  ``jax.live_arrays()`` into ``device_mem_live_bytes`` /
  ``device_mem_peak_bytes`` gauges with per-tag attribution gauges
  (``device_mem_<tag>_bytes``: params / opt state from the
  ``TrainStep`` hook, ingest buffers from ``IngestPipeline``), plus a
  ``health.mem_watermark`` flight event whenever the peak grows by
  ``watermark_frac``.  ``profile(path)`` writes a pprof
  ``device_memory_profile`` when jax provides one.

* **Streaming anomaly detection** — :class:`Detector`: EWMA plus a
  robust MAD z-score over a sliding window, over any monitor stat or
  histogram-fed signal (step time, ``input_stall_pct``, PS RPC
  latency, prefetch miss rate).  Purely value-driven (deterministic —
  the injectable ``clock`` stamps anomalies, it never gates them);
  warmup samples build the baseline, anomalous samples are kept OUT of
  it (a storm must not teach the detector that storms are normal), and
  ``max_consecutive`` anomalies force a re-baseline so a genuine level
  shift is eventually adopted instead of alarming forever.  Anomalies
  feed the FlightRecorder (``health.anomaly``), export as
  ``health_anomalies_total`` / ``health_anomaly_<signal>_total``, and
  ride the PS ``stat`` op (``health`` field) so a worker set can spot
  its straggler.  :meth:`ElasticAgent.arm_hang_deadline
  <paddle_tpu.distributed.elastic.ElasticAgent.arm_hang_deadline>`
  arms the progress watchdog from the measured step-time distribution
  instead of a hardcoded budget.

Arming: ``watch(signal)`` explicitly, or ``FLAGS_health_detectors`` —
``"default"`` arms the built-in signal set (:data:`DEFAULT_SIGNALS`),
a JSON object ``{"signal": {detector kwargs}}`` arms a custom one; the
env form lets a launcher arm a whole child tree.  When nothing is
armed, :func:`observe` is a dict check.  The ``health.detector`` chaos
fault point fires at the head of every observation; an injected error
is swallowed and counted (``health_observe_errors_total``) — detection
must never crash the training loop it watches.

``tools/health_check.py`` renders all of this (plus a trace summary)
as a health report and exits nonzero on tripped detectors — the
decision surface CI gates on.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np

from paddle_tpu.framework import chaos, monitor
from paddle_tpu.framework.flags import flag
from paddle_tpu.framework.observability import flight, tracer

__all__ = ["Anomaly", "Detector", "HealthMonitor", "MemoryTracker",
           "memory", "watch", "observe", "enabled", "snapshot", "reset",
           "classify_recompile", "note_compile", "note_cache_hit",
           "compile_report", "step_call", "maybe_sample_memory",
           "DEFAULT_SIGNALS", "RECOMPILE_CAUSES"]


# ---------------------------------------------------------------------------
# streaming anomaly detection
# ---------------------------------------------------------------------------

class Anomaly:
    """One flagged observation: the value, its robust z-score, and the
    baseline (window median / MAD scale) it was judged against."""

    __slots__ = ("signal", "value", "z", "median", "scale", "index", "ts")

    def __init__(self, signal: str, value: float, z: float, median: float,
                 scale: float, index: int, ts: float):
        self.signal = signal
        self.value = value
        self.z = z
        self.median = median
        self.scale = scale
        self.index = index
        self.ts = ts

    def to_dict(self) -> dict:
        return {"signal": self.signal, "value": round(self.value, 6),
                "z": round(self.z, 3), "median": round(self.median, 6),
                "scale": round(self.scale, 6), "index": self.index,
                "ts": self.ts}

    def __repr__(self):
        return (f"Anomaly({self.signal}: value={self.value:.4g} "
                f"z={self.z:.1f} median={self.median:.4g})")


class Detector:
    """EWMA + robust MAD z-score over one scalar signal stream.

    Each :meth:`update` folds the value into an EWMA (trend readout)
    and — once ``warmup`` baseline samples exist — scores it against
    the sliding window's median with a MAD scale:
    ``z = 0.6745 * (v - median) / max(MAD, min_mad,
    rel_floor * |median|)``.  The floors keep a near-constant baseline
    (MAD → 0) from flagging benign jitter: on a dead-flat stream only
    a deviation larger than ``rel_floor`` of the level (or ``min_mad``
    absolutely) can trip.  ``|z| >= z_threshold`` flags an
    :class:`Anomaly`.

    Anomalous values never enter the baseline window — a latency storm
    must not teach the detector that storms are normal — but
    ``max_consecutive`` consecutive anomalies force a **re-baseline**
    (window cleared, fresh warmup): a genuine level shift is adopted
    after a bounded alarm burst instead of alarming forever.

    Deterministic: behavior depends only on the value sequence.  The
    injectable ``clock`` (``elastic.DictStore`` discipline) stamps
    anomaly timestamps and never gates detection.
    """

    def __init__(self, signal: str, warmup: Optional[int] = None,
                 window: int = 64, z_threshold: Optional[float] = None,
                 ewma_alpha: float = 0.2, min_mad: float = 1e-9,
                 rel_floor: float = 0.05, max_consecutive: int = 64,
                 clock=None):
        self.signal = signal
        self.warmup = int(flag("health_warmup")) if warmup is None \
            else int(warmup)
        if self.warmup < 4:
            raise ValueError("Detector warmup must be >= 4 samples "
                             "(a 1-sample baseline flags everything)")
        self.window = int(window)
        self.z_threshold = float(flag("health_z_threshold")) \
            if z_threshold is None else float(z_threshold)
        self.ewma_alpha = float(ewma_alpha)
        self.min_mad = float(min_mad)
        self.rel_floor = float(rel_floor)
        self.max_consecutive = int(max_consecutive)
        self.clock = clock or time.time
        self._values: deque = deque(maxlen=self.window)
        self._lock = threading.Lock()    # PS fan-out threads share the
        self._warm_left = self.warmup    # ps_rpc_ms detector
        self.n = 0
        self.anomalies = 0
        self.consecutive = 0
        self.rebaselines = 0
        self.ewma: Optional[float] = None
        self.last: Optional[float] = None
        self.last_z = 0.0

    def update(self, value) -> Optional[Anomaly]:
        """Score one observation; returns the :class:`Anomaly` when it
        trips, else None.  Thread-safe: concurrent feeders (the PS
        client's RPC fan-out threads) serialize on the detector.

        A NON-FINITE observation (a NaN grad norm on a blown-up step)
        is an anomaly by definition — flagged immediately, even during
        warmup, with ``z=inf`` — and never folds into the EWMA or the
        baseline window (one NaN would otherwise poison both
        forever)."""
        v = float(value)
        with self._lock:
            self.n += 1
            self.last = v
            if not np.isfinite(v):
                self.anomalies += 1
                self.consecutive += 1
                # median BEFORE any rebaseline clear: the anomaly must
                # report the baseline it was judged against
                med = float(np.median(np.asarray(self._values,
                                                 np.float64))) \
                    if self._values else 0.0
                if self.consecutive >= self.max_consecutive:
                    self._values.clear()
                    self._warm_left = self.warmup
                    self.consecutive = 0
                    self.rebaselines += 1
                self.last_z = float("inf")
                return Anomaly(self.signal, v, float("inf"), med, 0.0,
                               self.n, self.clock())
            self.ewma = v if self.ewma is None else \
                self.ewma_alpha * v + (1.0 - self.ewma_alpha) * self.ewma
            if self._warm_left > 0:
                self._warm_left -= 1
                self._values.append(v)
                # a clean sample breaks any non-finite anomaly streak
                # even during warmup (the z=inf rule can flag here):
                # isolated NaNs must not ratchet toward a rebaseline
                self.consecutive = 0
                return None
            vals = np.asarray(self._values, np.float64)
            med = float(np.median(vals))
            mad = float(np.median(np.abs(vals - med)))
            scale = max(mad, self.min_mad, self.rel_floor * abs(med))
            z = 0.6745 * (v - med) / scale
            self.last_z = z
            if abs(z) < self.z_threshold:
                self.consecutive = 0
                self._values.append(v)
                return None
            self.anomalies += 1
            self.consecutive += 1
            if self.consecutive >= self.max_consecutive:
                # a sustained shift is the new normal: re-baseline
                # instead of alarming forever (bounded alarm burst by
                # design)
                self._values.clear()
                self._warm_left = self.warmup
                self.consecutive = 0
                self.rebaselines += 1
            return Anomaly(self.signal, v, z, med, scale, self.n,
                           self.clock())

    def last_value(self) -> Optional[float]:
        """Most recent observed value, or ``None`` before the first
        :meth:`observe` — the read half consumers use instead of
        reaching into detector internals."""
        with self._lock:
            return self.last

    def baseline(self) -> Optional[float]:
        """Current robust baseline: the rolling-window median the z
        score is computed against, or the EWMA while still warming
        (too few samples for a median), or ``None`` before any data."""
        with self._lock:
            if self._values:
                return float(np.median(np.asarray(self._values,
                                                  np.float64)))
            return self.ewma

    def reset(self) -> None:
        """Forget everything: window, EWMA, warmup progress, and all
        counters — equivalent to a freshly constructed detector.
        Distinct from the automatic rebaseline (which keeps lifetime
        counters); callers use this at deliberate regime changes, e.g.
        after the knob the signal measures was rewritten."""
        with self._lock:
            self._values.clear()
            self._warm_left = self.warmup
            self.ewma = None
            self.last = None
            self.last_z = 0.0
            self.n = 0
            self.anomalies = 0
            self.consecutive = 0
            self.rebaselines = 0

    def snapshot(self) -> dict:
        with self._lock:
            return {"n": self.n, "anomalies": self.anomalies,
                    "consecutive": self.consecutive,
                    "rebaselines": self.rebaselines,
                    "warming": self._warm_left > 0,
                    "ewma": None if self.ewma is None
                    else round(self.ewma, 6),
                    "last": None if self.last is None
                    else round(self.last, 6),
                    "last_z": round(self.last_z, 3),
                    "z_threshold": self.z_threshold}


#: the built-in signal set FLAGS_health_detectors="default" arms —
#: exactly the streams the train/transport/ingest tiers feed
DEFAULT_SIGNALS: Dict[str, dict] = {
    # step time, one call's start to the next's on the same step object
    # (TrainStep / PSTrainStep / ShardedUpdateTrainStep).  The wide
    # relative floor absorbs host-side dispatch jitter on real (tens
    # of ms+) steps; the absolute ms floor keeps sub-ms CPU baselines
    # from flagging scheduler noise — only a multiple-of-baseline /
    # tens-of-ms step (recompile, stall, storm) trips
    "train_step_ms": {"rel_floor": 0.25, "min_mad": 5.0},
    # client-side PS RPC latency, every op (TransportStats.record);
    # same floor rationale — localhost RPCs are sub-ms and jitter by
    # whole ms under load, a real latency fault is tens of ms
    "ps_rpc_ms": {"rel_floor": 0.25, "min_mad": 5.0},
    # ingest plane consumer stall share (IngestPipeline._note_wait)
    "input_stall_pct": {"min_mad": 1.0},
    # 0/1 stream per consumed prefetch (PSTrainStep._consume_prefetch);
    # the floors make a single post-warmup miss a detectable event on
    # an all-hit baseline without alarming a mixed one
    "ps_prefetch_miss": {"min_mad": 0.05, "z_threshold": 10.0},
    # model-numerics drift signals (framework/numerics.py publish, fed
    # only when FLAGS_numerics arms the in-jit stats; a non-finite
    # value flags instantly via the z=inf rule, and provenance names
    # the leaf).  The wide relative floor absorbs the natural decay of
    # grad norms over a healthy run; a multiple-of-baseline spike (10x
    # grad blow-up, lr accident, loss-scale overflow) trips the step
    # it lands
    "grad_norm": {"rel_floor": 0.5, "min_mad": 1e-9},
    "update_ratio": {"rel_floor": 0.5, "min_mad": 1e-9},
}


class HealthMonitor:
    """Registry of named-signal detectors — the plane's front door.

    ``watch(signal)`` arms a detector (idempotent); ``observe(signal,
    value)`` scores an observation.  Unwatched signals cost a dict
    lookup.  Every anomaly feeds the flight recorder
    (``health.anomaly``) and the monitor counters
    (``health_anomalies_total`` + ``health_anomaly_<signal>_total``).

    The ``health.detector`` chaos fault point fires at the head of
    every observation; an injected error is swallowed and counted
    (``health_observe_errors_total``) — the watcher must never crash
    the training loop it watches (``mode="latency"`` models a slow
    detector the loop simply absorbs).
    """

    def __init__(self, clock=None):
        self.clock = clock
        self._detectors: Dict[str, Detector] = {}
        self._lock = threading.Lock()
        self._checked_flags = False

    # -- arming -------------------------------------------------------------
    def watch(self, signal: str, **detector_kwargs) -> Detector:
        """Arm a detector for ``signal`` (idempotent: an existing
        detector is returned unchanged — re-watching must not wipe a
        live baseline)."""
        with self._lock:
            det = self._detectors.get(signal)
            if det is None:
                if "clock" not in detector_kwargs and \
                        self.clock is not None:
                    detector_kwargs["clock"] = self.clock
                det = self._detectors[signal] = Detector(
                    signal, **detector_kwargs)
            return det

    def arm_from_flags(self, force: bool = False):
        """Arm from ``FLAGS_health_detectors`` (lazy, chaos-style, so a
        launcher arms a whole child tree via the environment):
        ``"default"``/``"1"``/``"auto"`` arms :data:`DEFAULT_SIGNALS`,
        a JSON object ``{"signal": {kwargs}}`` arms a custom set,
        empty leaves the plane off.

        A malformed value (typo'd JSON, unknown detector kwarg) must
        not crash the caller: the arming is lazy, so the first
        :meth:`observe` runs from inside a train step — the
        watcher-never-crashes-watched contract covers config typos
        too.  The error is recorded (``health_config_errors_total`` +
        a ``health.config_error`` flight event) and the plane stays
        off."""
        if self._checked_flags and not force:
            return
        self._checked_flags = True
        raw = str(flag("health_detectors") or "").strip()
        if not raw:
            return
        try:
            if raw.lower() in ("default", "auto", "1", "true"):
                spec: Dict[str, dict] = DEFAULT_SIGNALS
            else:
                spec = json.loads(raw)
            for signal, kw in spec.items():
                self.watch(signal, **dict(kw))
        except Exception as e:          # noqa: BLE001 — config, not code
            monitor.stat_add("health_config_errors_total")
            flight.record("health.config_error", severity="error",
                          flag="health_detectors", value=raw[:200],
                          error=repr(e))

    def detectors(self) -> Dict[str, Detector]:
        with self._lock:
            return dict(self._detectors)

    @property
    def enabled(self) -> bool:
        if not self._checked_flags:
            self.arm_from_flags()
        return bool(self._detectors)

    # -- observation --------------------------------------------------------
    def observe(self, signal: str, value) -> Optional[Anomaly]:
        """Score ``value`` against the ``signal`` detector; no-op (None)
        when the signal is unwatched."""
        if not self._checked_flags:
            self.arm_from_flags()
        try:
            chaos.fault_point("health.detector",
                              meta={"signal": signal})
        except chaos.InjectedFault:
            # the watcher must never crash the watched: swallow, count
            monitor.stat_add("health_observe_errors_total")
            return None
        det = self._detectors.get(signal)
        if det is None:
            return None
        anomaly = det.update(value)
        if anomaly is not None:
            monitor.stat_add("health_anomalies_total")
            monitor.stat_add(f"health_anomaly_{signal}_total")
            flight.record("health.anomaly", severity="warn",
                          signal=signal, value=round(anomaly.value, 6),
                          z=round(anomaly.z, 3),
                          median=round(anomaly.median, 6),
                          index=anomaly.index)
        return anomaly

    # -- introspection ------------------------------------------------------
    def snapshot(self) -> dict:
        """JSON-able state of every detector plus the compile sites —
        what the PS ``stat`` op's ``health`` field and
        ``tools/health_check.py`` render."""
        dets = self.detectors()
        return {"signals": {s: d.snapshot() for s, d in dets.items()},
                "anomalies_total": sum(d.anomalies for d in dets.values()),
                "compile": compile_report()}

    def reset(self):
        """Drop every detector and pin flag arming off until the next
        explicit :meth:`arm_from_flags` — each test starts here."""
        with self._lock:
            self._detectors.clear()
            self._checked_flags = True


_monitor = HealthMonitor()


def watch(signal: str, **detector_kwargs) -> Detector:
    """Arm a detector on the process-wide health monitor."""
    return _monitor.watch(signal, **detector_kwargs)


def observe(signal: str, value) -> Optional[Anomaly]:
    """Feed one observation to the process-wide health monitor."""
    return _monitor.observe(signal, value)


def enabled() -> bool:
    """True when any detector is armed (flag arming counted)."""
    return _monitor.enabled


def snapshot() -> dict:
    """Process-wide health state (detectors + compile sites)."""
    return _monitor.snapshot()


def reset():
    """Reset detectors, compile sites, and the memory tracker — the
    per-test clean slate (counters in the monitor registry are owned by
    ``monitor.reset_all_stats`` as usual)."""
    _monitor.reset()
    with _sites_lock:
        _sites.clear()
    memory.reset()


# ---------------------------------------------------------------------------
# compile observability
# ---------------------------------------------------------------------------

RECOMPILE_CAUSES = ("new_signature", "shape_change", "dtype_change",
                    "static_arg_change", "jax_retrace")
# what jax hands to the backend compiler, a persistent-cache read included
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_DTYPE_NAMES = ("float", "bfloat", "int", "uint", "bool", "complex")


def _is_dtype_str(v) -> bool:
    if not isinstance(v, str):
        return False
    return v.rstrip("0123456789") in _DTYPE_NAMES


def _sig_diff(a, b, kinds: set) -> bool:
    """Walk two signature trees in parallel, collecting difference
    kinds into ``kinds`` ({"shape", "dtype", "static"}).  Returns False
    when the trees are structurally incomparable (different arity or
    leaf classes) — that is a wholly new signature, not a mutation."""
    if isinstance(a, tuple) and isinstance(b, tuple):
        # a (tag/name, value) pair with equal string heads is a STATIC
        # leaf — ("S", v) from _sig_of, or a to_static (kwarg, value)
        # pair: any value difference, even a tuple of ints that would
        # otherwise read as a shape (e.g. stride=(2,2) -> (2,3)), is a
        # static-arg change, never a phantom shape change
        if len(a) == 2 and len(b) == 2 and isinstance(a[0], str) \
                and isinstance(b[0], str):
            if a[0] != b[0]:
                return False
            if a[1] != b[1]:
                kinds.add("static")
            return True
        # a tuple of ints is a shape; compare it as ONE leaf
        if a != b and all(isinstance(x, int) for x in a) \
                and all(isinstance(x, int) for x in b):
            kinds.add("shape")
            return True
        if len(a) != len(b):
            return False
        return all(_sig_diff(x, y, kinds) for x, y in zip(a, b))
    if isinstance(a, tuple) or isinstance(b, tuple):
        return False
    if a == b:
        return True
    if _is_dtype_str(a) and _is_dtype_str(b):
        kinds.add("dtype")
        return True
    kinds.add("static")
    return True


def classify_recompile(sig, cached_sigs) -> str:
    """Attribute a signature-cache miss to its cause by diffing ``sig``
    against every cached signature and keeping the closest comparable
    one: ``static_arg_change`` > ``dtype_change`` > ``shape_change``
    (a static-arg flip is reported even when it dragged shapes along —
    it is the actionable cause); no comparable cached signature (or an
    empty cache) is a ``new_signature``."""
    best: Optional[set] = None
    for cached in cached_sigs:
        kinds: set = set()
        if not _sig_diff(sig, cached, kinds) or not kinds:
            continue
        if best is None or len(kinds) < len(best):
            best = kinds
    if best is None:
        return "new_signature"
    if "static" in best:
        return "static_arg_change"
    if "dtype" in best:
        return "dtype_change"
    return "shape_change"


class _CompileSite:
    __slots__ = ("name", "calls", "compiles", "steady_recompiles",
                 "causes", "last_cause", "compile_ms_total", "traces",
                 "trace_s", "lower_s", "backend_s", "cold_compile_s")

    def __init__(self, name: str):
        self.name = name
        self.calls = 0
        self.compiles = 0
        self.steady_recompiles = 0
        self.causes: Dict[str, int] = {}
        self.last_cause: Optional[str] = None
        self.compile_ms_total = 0.0
        # the phases of every compile booked to the site's step calls
        self.traces = 0
        self.trace_s = 0.0
        self.lower_s = 0.0
        self.backend_s = 0.0
        self.cold_compile_s = 0.0


_sites: Dict[str, _CompileSite] = {}
_sites_lock = threading.Lock()


def _site(name: str) -> _CompileSite:
    with _sites_lock:
        s = _sites.get(name)
        if s is None:
            s = _sites[name] = _CompileSite(name)
        return s


def note_cache_hit(site: str):
    """A signature-cache hit at ``site`` (one per non-compiling call)."""
    s = _site(site)
    s.calls += 1
    monitor.stat_add("jit_cache_hits_total")


def note_compile(site: str, cause: str, compile_ms: float):
    """A signature-cache miss at ``site``: count the compile under its
    ``cause``, record ``compile_ms``, and run the storm/steady-state
    bookkeeping.  Call sites classify the cause with
    :func:`classify_recompile` BEFORE inserting the new signature."""
    s = _site(site)
    s.calls += 1
    _count_compile(s, cause, compile_ms)


def _count_compile(s: _CompileSite, cause: str, compile_ms: float):
    if cause not in RECOMPILE_CAUSES:
        cause = "new_signature"
    s.compiles += 1
    s.causes[cause] = s.causes.get(cause, 0) + 1
    s.last_cause = cause
    s.compile_ms_total += float(compile_ms)
    monitor.stat_add("jit_compiles_total")
    monitor.stat_add(f"jit_compiles_{cause}_total")
    monitor.observe("compile_ms", float(compile_ms))
    warmup_calls = int(flag("health_compile_warmup_calls"))
    if s.calls > warmup_calls and s.compiles > 1:
        # a RE-compile past the warmup window: the signature cache was
        # supposed to be settled — count it, and K of them is a storm
        s.steady_recompiles += 1
        monitor.stat_add("jit_recompiles_steady_total")
        storm_k = int(flag("health_compile_storm_k"))
        if s.steady_recompiles >= storm_k and \
                s.steady_recompiles % storm_k == 0:
            flight.record("health.compile_storm", severity="warn",
                          site=s.name,
                          post_warmup_compiles=s.steady_recompiles,
                          causes=dict(s.causes))


def compile_report() -> Dict[str, dict]:
    """Per-site compile bookkeeping (JSON-able): calls, compiles,
    steady-state recompiles, per-cause counts, total compile ms, and the
    phases booked to the site's step calls (:func:`step_call`):
    ``traces`` (jaxpr traces, nested ones included), the seconds of
    ``trace_s``, ``lower_s`` and ``backend_s`` (persistent-cache reads
    included), which never overlap (:class:`_Union`), and
    ``cold_compile_s`` (the part of ``backend_s`` no persistent-cache hit
    served)."""
    with _sites_lock:
        sites = list(_sites.values())
    return {s.name: {"calls": s.calls, "compiles": s.compiles,
                     "steady_recompiles": s.steady_recompiles,
                     "causes": dict(s.causes),
                     "last_cause": s.last_cause,
                     "compile_ms_total": round(s.compile_ms_total, 3),
                     "traces": s.traces, "trace_s": round(s.trace_s, 6),
                     "lower_s": round(s.lower_s, 6),
                     "backend_s": round(s.backend_s, 6),
                     "cold_compile_s": round(s.cold_compile_s, 6)}
            for s in sites}


# what jax emits while it compiles, on the compiling thread: three phases
# as time spans (``time.time()`` stamps, a ``fun_name`` each) and, inside
# a backend compile's span, whether the persistent cache served it
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_PHASES = {TRACE_EVENT: "trace", LOWER_EVENT: "lower",
           BACKEND_COMPILE_EVENT: "backend"}
_PROFILER_ROWS = {"trace": "jit.trace", "lower": "jit.lower",
                  "backend": "jit.backend_compile"}

monitor.describe("jit_traces_total",
                 "jaxpr traces booked to step calls, nested ones included")
monitor.describe("jit_trace_seconds_total",
                 "seconds of jaxpr tracing booked to step calls")
monitor.describe("jit_lower_seconds_total",
                 "seconds of lowering to StableHLO booked to step calls")
monitor.describe("jit_backend_seconds_total",
                 "seconds of backend compiles booked to step calls, "
                 "persistent-cache reads included")
monitor.describe("jit_cold_compile_seconds_total",
                 "seconds of backend compiles booked to step calls that "
                 "the persistent cache did not serve")
monitor.describe("compile_ms", "trace + lower + backend ms of one compile "
                 "at a jit site (a signature-cache miss or jax's retrace)")


class _Union:
    """The compile phases booked to one open scope (a step call, a
    launch), as the seconds their intervals cover together: ``add``
    returns what a new interval covers that no earlier one did.  So a
    nested interval counts once, for the phase that ended first, the
    innermost (a jit's trace inside its caller's, an eager op's compile
    inside a trace), and the phases' seconds never overlap.  jax reports
    a phase when it ends, so on one thread a new interval ends after
    every earlier one and can only overlap the last few."""

    __slots__ = ("spans", "seconds")

    def __init__(self):
        self.spans: List[tuple] = []
        self.seconds = 0.0

    def add(self, a: float, b: float) -> float:
        covered = 0.0
        while self.spans and self.spans[-1][1] >= a \
                and self.spans[-1][0] <= b:
            x, y = self.spans.pop()
            covered += y - x
            a, b = min(a, x), max(b, y)
        self.spans.append((a, b))
        new = (b - a) - covered
        self.seconds += new
        return new


class _StepCall:
    """One call of a step, from the start of its ``prepare`` to the end
    of its ``commit``: every compile phase jax reports on this thread
    meanwhile is booked to ``site`` — the jitted step's, and the eager
    programs of the first call (optimizer-state init, the RNG split)."""

    __slots__ = ("site", "_union", "_outer")

    def __init__(self, site: str):
        self.site = site
        self._union = None
        self._outer = None

    def __enter__(self):
        _listen()
        self._outer = getattr(_open, "call", None)
        _open.call = self
        return self

    def __exit__(self, *exc):
        _open.call = self._outer
        return False

    def book(self, phase: str, a: float, b: float, cold: bool):
        if self._union is None:
            self._union = _Union()
        new = self._union.add(a, b)
        s = _site(self.site)
        if phase == "trace":
            s.traces += 1
            s.trace_s += new
            monitor.stat_add("jit_traces_total")
            monitor.stat_add("jit_trace_seconds_total", new)
        elif phase == "lower":
            s.lower_s += new
            monitor.stat_add("jit_lower_seconds_total", new)
        else:
            s.backend_s += new
            monitor.stat_add("jit_backend_seconds_total", new)
            if cold:
                s.cold_compile_s += new
                monitor.stat_add("jit_cold_compile_seconds_total", new)


def step_call(site: str):
    """See :class:`_StepCall` — ``TrainStep.__call__`` and
    ``TrainStep.multi_step`` wrap each call in one.  Compiles outside any
    step call (a reference, a solve, an eager op between steps) are
    booked to no step site."""
    return _StepCall(site)


class _TimedCompile:
    """Context manager the jit tiers wrap a dispatch in (a step's
    ``launch``).  On a signature-cache miss (``cause`` set): a
    ``jit.compile`` tracer span carrying site + cause, and on exit
    :func:`note_compile` with the trace + lower + backend ms jax reported
    meanwhile.  On a hit nothing is counted, but while it is open the site
    owns whatever jax hands to the backend compiler (:func:`_on_phase`):
    jax's own retrace, cause ``jax_retrace``."""

    __slots__ = ("site", "cause", "_span", "_outer", "_union", "_since")

    def __init__(self, site: str, cause: Optional[str]):
        self.site = site
        self.cause = cause
        self._span = None
        self._outer = None
        self._union = None
        self._since = 0.0      # trace + lower seconds since the last backend

    def __enter__(self):
        _listen()
        self._outer = getattr(_open, "launch", None)
        _open.launch = self
        if self.cause is not None:
            self._span = tracer.start_span(
                "jit.compile",
                attrs={"site": self.site, "cause": self.cause})
            self._span.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        _open.launch = self._outer
        if self._span is not None:
            self._span.__exit__(exc_type, exc, tb)
            if exc_type is None:
                note_compile(self.site, self.cause, 0.0 if self._union
                             is None else self._union.seconds * 1e3)
        return False

    def book(self, phase: str, a: float, b: float):
        if self._union is None:
            self._union = _Union()
        new = self._union.add(a, b)
        if phase != "backend":
            self._since += new
        elif self.cause is None:
            # under a hit of our signature cache this is jax's own retrace
            # (an input's sharding changed: the second call of every
            # process, whose parameters carry the first call's output
            # sharding), which no signature of ours can see
            _count_compile(_site(self.site), "jax_retrace",
                           (self._since + new) * 1e3)
            self._since = 0.0


def timed_compile(site: str, cause: Optional[str]):
    """See :class:`_TimedCompile` — the one-liner the jit tiers use.
    ``cause=None`` is a hit of the site's signature cache, so a call
    site wraps its dispatch unconditionally instead of duplicating the
    dispatch expression across a compile/hit branch pair."""
    return _TimedCompile(site, cause)


# .call: the innermost open step call; .launch: the innermost open
# dispatch; .cache_hit: the persistent cache served the backend compile
# now in progress
_open = threading.local()
_listening = threading.Event()


def _listen():
    """Register :func:`_on_phase` and :func:`_on_cache_event` with jax,
    once a process: the program's one compile listener."""
    if not _listening.is_set():
        with _sites_lock:
            if not _listening.is_set():
                import jax
                jax.monitoring.register_event_time_span_listener(_on_phase)
                jax.monitoring.register_event_listener(_on_cache_event)
                _listening.set()


def _on_cache_event(event: str, **_):
    if event == CACHE_HIT_EVENT:
        _open.cache_hit = True
    elif event == CACHE_MISS_EVENT:
        _open.cache_hit = False


def _on_phase(event: str, start: float, end: float, **kwargs):
    """jax finished one phase of a compile on this thread: book it to the
    open step call and the open launch, and, while the profiler is on,
    give it a row on the profiler's clock."""
    phase = _PHASES.get(event)
    if phase is None:
        return
    cold = False
    if phase == "backend":
        # the hit or miss jax reported inside this compile's span
        cold = not getattr(_open, "cache_hit", False)
        _open.cache_hit = False
    call = getattr(_open, "call", None)
    if call is not None:
        call.book(phase, start, end, cold)
    launch = getattr(_open, "launch", None)
    if launch is not None:
        launch.book(phase, start, end)
    from paddle_tpu import profiler
    if profiler.is_profiling():
        shift = time.perf_counter() - time.time()
        profiler.record_span(_PROFILER_ROWS[phase], start + shift,
                             end + shift,
                             fun_name=str(kwargs.get("fun_name", "")))


# ---------------------------------------------------------------------------
# device-memory observability
# ---------------------------------------------------------------------------

class MemoryTracker:
    """Live/peak device-byte gauges over ``jax.live_arrays()`` with
    per-tag attribution.

    :meth:`sample` sums every live jax array's bytes into
    ``device_mem_live_bytes`` (gauge) and tracks the high watermark in
    ``device_mem_peak_bytes``; a peak that grew by at least
    ``watermark_frac`` since the last watermark event records a
    ``health.mem_watermark`` flight event (first nonzero peak counts).
    ``tags`` (e.g. ``{"params": nbytes, "opt_state": nbytes}``) become
    ``device_mem_<tag>_bytes`` gauges — the TrainStep hook attributes
    params/opt state/buffers, the ingest plane its in-flight device
    batches (:meth:`track`).  :meth:`profile` writes jax's pprof
    ``device_memory_profile`` when the installed jax provides one.
    """

    def __init__(self, watermark_frac: float = 0.25, clock=None):
        self.watermark_frac = float(watermark_frac)
        self.clock = clock or time.time
        self.live_bytes = 0
        self.peak_bytes = 0
        self.samples = 0
        self.tags: Dict[str, int] = {}
        self._watermark = 0
        self._lock = threading.Lock()

    def sample(self, tags: Optional[Dict[str, int]] = None) -> dict:
        """One measurement pass; returns ``{"live_bytes", "peak_bytes",
        "tags"}``.  O(#live arrays) metadata walk — no device sync."""
        import jax
        live = 0
        try:
            for a in jax.live_arrays():
                live += int(getattr(a, "nbytes", 0) or 0)
        except Exception:        # noqa: BLE001 — backend without support
            live = 0
        with self._lock:
            self.samples += 1
            self.live_bytes = live
            if live > self.peak_bytes:
                self.peak_bytes = live
            new_watermark = self.peak_bytes > 0 and (
                self._watermark == 0 or self.peak_bytes >=
                self._watermark * (1.0 + self.watermark_frac))
            prev = self._watermark
            if new_watermark:
                self._watermark = self.peak_bytes
            if tags:
                self.tags.update({t: int(b) for t, b in tags.items()})
            tag_snapshot = dict(self.tags)
        monitor.stat_set("device_mem_live_bytes", live)
        monitor.stat_set("device_mem_peak_bytes", self.peak_bytes)
        for t, b in (tags or {}).items():
            monitor.stat_set(f"device_mem_{t}_bytes", int(b))
        if new_watermark:
            flight.record("health.mem_watermark", severity="info",
                          peak_bytes=self.peak_bytes, prev_watermark=prev,
                          tags=tag_snapshot, ts=self.clock())
        return {"live_bytes": live, "peak_bytes": self.peak_bytes,
                "tags": tag_snapshot}

    def track(self, tag: str, nbytes: int):
        """Attribute ``nbytes`` to ``tag`` without a full sample (the
        ingest plane's per-batch hook: metadata-cheap, every batch)."""
        with self._lock:
            self.tags[tag] = int(nbytes)
        monitor.stat_set(f"device_mem_{tag}_bytes", int(nbytes))

    def profile(self, path: str) -> str:
        """Write jax's pprof device-memory profile to ``path``."""
        from jax.profiler import device_memory_profile
        blob = device_memory_profile()
        with open(path, "wb") as f:
            f.write(blob)
        return path

    def snapshot(self) -> dict:
        with self._lock:
            return {"live_bytes": self.live_bytes,
                    "peak_bytes": self.peak_bytes,
                    "samples": self.samples, "tags": dict(self.tags)}

    def reset(self):
        with self._lock:
            self.live_bytes = 0
            self.peak_bytes = 0
            self.samples = 0
            self.tags.clear()
            self._watermark = 0


#: process-wide device-memory tracker (TrainStep / ingest hooks feed it)
memory = MemoryTracker()

_mem_calls = 0
_mem_lock = threading.Lock()


def maybe_sample_memory(tags_fn=None) -> Optional[dict]:
    """The TrainStep hook: sample device memory every
    ``FLAGS_health_mem_sample_every`` calls (0 = off — the default, so
    the per-step cost is one flag read).  ``tags_fn`` is invoked only
    when a sample actually runs."""
    every = int(flag("health_mem_sample_every"))
    if every <= 0:
        return None
    global _mem_calls
    with _mem_lock:
        _mem_calls += 1
        due = _mem_calls % every == 0
    if not due:
        return None
    return memory.sample(tags=tags_fn() if tags_fn is not None else None)

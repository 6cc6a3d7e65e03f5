"""Deterministic fault-injection ("chaos") registry.

The reference treats failure handling as a first-class subsystem —
heartbeat lost-worker monitoring (operators/distributed/
heart_beat_monitor.cc), auto-checkpoint crash recovery
(fluid/incubate/checkpoint/auto_checkpoint.py TrainEpochRange), per-op
NaN/Inf watching (FLAGS_check_nan_inf) — but none of it is provable
without a way to *cause* the faults on demand.  This module is that
way: a seedable registry of named fault points threaded through the
layers that must survive them.

Fault points shipped in-tree (grep for ``fault_point(`` to audit):

=====================  ====================================================
``ps.rpc``              client side of every PS RPC (ps/service.py
                        _Conn.rpc)
``ps.pipeline``         each background prefetch task of PSTrainStep's
                        pull/compute overlap pipeline (ps/__init__.py
                        _issue_prefetch) — ``mode="error"`` is a failed
                        prefetch (the step must fall back to a
                        synchronous pull and replay the coalesced
                        push), ``mode="latency"`` a slow one the
                        consume path must simply wait out
``data.pipeline``       each background fetch+transfer task of the
                        streaming ingest plane (io/pipeline.py
                        IngestPipeline) — ``mode="error"`` is a failed
                        prefetch (the consumer must fall back to a
                        synchronous fetch+transfer of the same batch:
                        no sample lost, no duplicate), ``mode="latency"``
                        a slow decode the wait stage simply absorbs
``fs.write``            crash-safe file writes (fleet/utils/fs.py
                        atomic_write)
``ckpt.save``           per-file checkpoint writes (distributed/
                        checkpoint.py)
``ckpt.async``          async-save dispatch (distributed/checkpoint.py
                        ``save_train_state(mode="async")``) — an
                        injected fault means the background tier is
                        broken; the save degrades to a counted
                        synchronous save, never to no save
``ckpt.verify``         checkpoint integrity verification
                        (distributed/checkpoint.py verify_checkpoint)
                        — an injected fault makes the verifier itself
                        fail closed: the checkpoint is reported
                        unverifiable, save-side commit refuses, and
                        load walks back a generation
``download.fetch``      each fetch attempt (utils/download.py)
``train.step_grads``    per-step input poisoning (framework/resilient.py)
                        — ``mode="nan"`` with ``payload_index=i``
                        poisons only the i-th step input, so the NaN
                        reaches exactly the parameter leaves that input
                        feeds (the numerics plane's per-leaf provenance
                        fault)
``elastic.lease``       every lease renewal (distributed/elastic.py
                        RendezvousStore.renew) — ``mode="error"`` is a
                        lost renewal: the lease runs out, a peer's sweep
                        expires it, the membership epoch bumps
``elastic.worker_hang`` per-step worker liveness beat (elastic.py
                        ElasticWorkerContext.step_done) —
                        ``mode="latency"`` is a straggler/hung worker the
                        agent's hang deadline must catch
``health.detector``     head of every health-plane observation
                        (framework/health.py HealthMonitor.observe) —
                        ``mode="error"`` is a broken detector the
                        observe path must swallow and count (the
                        watcher must never crash the watched train
                        loop), ``mode="latency"`` a slow one the loop
                        simply absorbs
``zero.collective``     once per collective leg (reduce_scatter /
                        all_gather) at the dispatch head of the ZeRO
                        sharded update (parallel/zero.py
                        ShardedUpdateTrainStep) — ``mode="error"`` is a
                        dropped collective the step re-issues (bounded
                        pre-dispatch retry; no state was consumed, so
                        the retried trajectory is bit-identical),
                        ``mode="latency"`` a slow interconnect the
                        dispatch simply absorbs
``numerics.observe``    head of every model-numerics publish
                        (framework/numerics.py publish) —
                        ``mode="error"`` is a broken stats exporter the
                        publish path must swallow and count
                        (``numerics_observe_errors_total``): the
                        watcher must never crash the watched train
                        step; ``mode="latency"`` a slow one the step
                        simply absorbs
``runlog.observe``      head of every run-ledger append
                        (framework/runlog.py RunLedger.append) —
                        ``mode="error"`` is a broken/full ledger disk
                        the append must swallow and count
                        (``runlog_write_errors_total`` + a
                        ``runlog.write_error`` flight event): the run
                        being recorded must never crash on its
                        recorder; ``mode="latency"`` a slow disk the
                        append simply absorbs
``locks.observe``       head of every lock-watchdog observation
                        (framework/locks.py LockWatchdog.note_acquire,
                        armed via FLAGS_lock_watchdog) —
                        ``mode="error"`` is broken watchdog bookkeeping
                        the observation path must swallow and count
                        (``lock_watchdog_errors_total``): the watcher
                        must never deadlock or crash the watched lock;
                        ``mode="latency"`` a slow observation the
                        acquire simply absorbs
``collector.rpc``       head of every telemetry push the
                        fire-and-forget sender thread attempts
                        (framework/collector.py CollectorClient) —
                        ``mode="error"`` is a dead/refusing collector:
                        the payload is DROPPED and counted
                        (``collector_dropped_total``), the pushing
                        train loop is bit-identical to a collector-less
                        run; ``mode="latency"`` a slow collector the
                        sender thread absorbs off the training path
``parity.observe``      head of every replica-parity probe observation
                        (parallel/parity.py ParityProbe.observe, armed
                        via FLAGS_replica_parity) — ``mode="error"`` is
                        a broken probe the observation path must
                        swallow and count
                        (``parity_observe_errors_total``): the watcher
                        must never perturb or crash the watched train
                        step (the trajectory stays bit-identical);
                        ``mode="latency"`` a slow probe the step simply
                        absorbs
``pallas.verify``       head of every Pallas differential-oracle check
                        (ops/pallas/verify.py verify_call, armed via
                        FLAGS_pallas_verify) — ``mode="error"`` is a
                        broken oracle the verification path must
                        swallow and count
                        (``pallas_verify_errors_total``): the watcher
                        must never perturb or crash the watched kernel
                        call (its output stays bit-identical);
                        ``mode="latency"`` a slow oracle the call
                        simply absorbs
``incident.capture``    head of every incident-bundle capture
                        (framework/incident.py IncidentRecorder, armed
                        via FLAGS_incident) — ``mode="error"`` is a
                        broken/full bundle disk the capture must
                        swallow and count
                        (``incident_capture_errors_total``): the
                        postmortem recorder must never crash the run
                        it records; ``mode="latency"`` a slow disk the
                        (already off-hot-path) capture simply absorbs
=====================  ====================================================

Injection is schedule-driven and deterministic: ``nth`` (trip exactly on
the Nth call), ``every`` (trip every Nth call), ``p`` (seeded
probability), bounded by ``n_times``.  A trip applies the point's
``mode``: ``"error"`` raises :class:`InjectedFault`, ``"latency"``
sleeps ``latency`` seconds then proceeds, ``"nan"`` NaN-poisons float
arrays in the payload and returns them.

Arming paths, in precedence order:

* the :func:`inject` context manager (tests):
    ``with chaos.inject("ps.rpc", mode="error", nth=3): ...``
* env flags read once at first use (so a launcher can arm a whole
  child-process tree): ``FLAGS_chaos_spec`` is a JSON object
  ``{"<point>": {"mode": ..., "nth": ..., ...}}``, ``FLAGS_chaos_seed``
  seeds the probability stream.

When nothing is armed a fault point is one dict lookup — cheap enough
to leave in production paths.
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
from typing import Any, Dict, Optional

import numpy as np

__all__ = ["InjectedFault", "FaultSpec", "fault_point", "inject", "arm",
           "disarm", "stats", "reset", "arm_from_flags", "FAULT_POINTS",
           "register_fault_point", "known_fault_points",
           "payload_fault_points", "arm_state", "restore_state"]

FAULT_POINTS = ("ps.rpc", "ps.pipeline", "data.pipeline", "fs.write",
                "ckpt.save", "ckpt.async", "ckpt.verify",
                "download.fetch", "train.step_grads",
                "elastic.lease", "elastic.worker_hang",
                "health.detector", "zero.collective",
                "numerics.observe", "runlog.observe", "collector.rpc",
                "locks.observe", "parity.observe", "pallas.verify",
                "incident.capture")
_known_points = set(FAULT_POINTS)
# points whose fault_point() call carries a payload (the only ones where
# mode="nan" can transform anything)
_payload_points = {"train.step_grads"}


def register_fault_point(name: str, carries_payload: bool = False):
    """Declare a custom fault point so arm()/FLAGS_chaos_spec accept it.
    In-tree points are pre-registered; arming an UNDECLARED name raises —
    a typo'd spec silently injecting nothing is exactly the
    false-green-chaos-run this registry exists to prevent.  Pass
    ``carries_payload=True`` when your fault_point() call site hands in
    arrays, to unlock ``mode="nan"`` for it."""
    _known_points.add(name)
    if carries_payload:
        _payload_points.add(name)
    return name


def known_fault_points() -> frozenset:
    """Every declared fault point name — in-tree plus anything added via
    :func:`register_fault_point`.  Consumer API for the static analyzer
    (framework.analysis rules PTA301/PTA302): the linter validates
    ``fault_point("...")`` call sites against this registry and flags
    sites with no retry/backoff guard, so a chaos-armed point can never
    be a name the registry would reject nor a call path that escalates
    an injected fault straight into a crash."""
    return frozenset(_known_points)


def payload_fault_points() -> frozenset:
    """Declared points whose call sites carry a payload (the only ones
    where ``mode="nan"`` transforms anything) — see known_fault_points."""
    return frozenset(_payload_points)


class InjectedFault(ConnectionError):
    """Raised by an armed ``mode="error"`` fault point.

    Subclasses ConnectionError so transport-layer retry paths (PS RPC)
    treat an injected drop exactly like a real one; elsewhere it
    propagates like the crash it simulates."""


class FaultSpec:
    """One armed fault point's schedule + mode."""

    def __init__(self, mode: str = "error", nth: Optional[int] = None,
                 every: Optional[int] = None, p: float = 0.0,
                 latency: float = 0.0, n_times: Optional[int] = None,
                 message: str = "", payload_index: Optional[int] = None):
        if mode not in ("error", "latency", "nan"):
            raise ValueError(f"unknown chaos mode {mode!r}")
        self.mode = mode
        self.nth = nth
        self.every = every
        self.p = float(p)
        self.latency = float(latency)
        self.n_times = n_times
        self.message = message
        # mode="nan" targeting: poison only the payload_index-th element
        # of a tuple/list payload (e.g. ONE input of a train step, so a
        # NaN reaches exactly the parameter leaves that input feeds —
        # the numerics plane's per-leaf provenance is provable only
        # with a fault this surgical); None poisons every float array
        self.payload_index = payload_index
        self.calls = 0
        self.trips = 0

    def should_trip(self, rng: np.random.Generator) -> bool:
        self.calls += 1
        if self.n_times is not None and self.trips >= self.n_times:
            return False
        hit = False
        if self.nth is not None and self.calls == self.nth:
            hit = True
        if self.every is not None and self.calls % self.every == 0:
            hit = True
        if self.p > 0.0 and rng.random() < self.p:
            hit = True
        if hit:
            self.trips += 1
        return hit


class ChaosRegistry:
    def __init__(self, seed: int = 0):
        self._specs: Dict[str, FaultSpec] = {}
        self._seed = int(seed)
        self._rng = np.random.default_rng(seed)
        self._lock = threading.Lock()
        self.armed = False               # fast-path gate for fault_point

    def arm(self, name: str, **spec) -> FaultSpec:
        if name not in _known_points:
            raise ValueError(
                f"unknown fault point {name!r} — in-tree points: "
                f"{sorted(_known_points)}; declare custom sites with "
                "register_fault_point() first")
        if spec.get("mode") == "nan" and name not in _payload_points:
            raise ValueError(
                f"fault point {name!r} carries no payload — mode='nan' "
                "would inject nothing (false-green chaos); payload "
                f"points: {sorted(_payload_points)}")
        fs = FaultSpec(**spec)
        with self._lock:
            self._specs[name] = fs
            self.armed = True
        return fs

    def disarm(self, name: Optional[str] = None):
        with self._lock:
            if name is None:
                self._specs.clear()
            else:
                self._specs.pop(name, None)
            self.armed = bool(self._specs)

    def reseed(self, seed: int):
        # under the registry lock: fire() reads the generator under it,
        # and a reseed racing a fire must swap the reference atomically
        # with the schedule state (PTA403)
        with self._lock:
            self._seed = int(seed)
            self._rng = np.random.default_rng(seed)

    def export_state(self) -> Dict[str, Any]:
        """JSON-able snapshot of the whole injection state: seed, the
        probability stream's mid-sequence generator state, and every
        armed spec WITH its call/trip counters — what an incident
        bundle records so a replay resumes the exact fault schedule a
        mid-run incident saw, not the schedule from call zero."""
        with self._lock:
            specs = {}
            for name, s in self._specs.items():
                specs[name] = {
                    "mode": s.mode, "nth": s.nth, "every": s.every,
                    "p": s.p, "latency": s.latency, "n_times": s.n_times,
                    "message": s.message,
                    "payload_index": s.payload_index,
                    "calls": s.calls, "trips": s.trips}
            return {"seed": self._seed, "armed": self.armed,
                    "rng_state": self._rng.bit_generator.state,
                    "specs": specs}

    def import_state(self, state: Dict[str, Any]):
        """Reinstall an :meth:`export_state` snapshot: specs are rebuilt
        with their call/trip counters reinstated, and the probability
        stream resumes from the recorded generator state (falling back
        to a fresh seed when the snapshot predates ``rng_state``)."""
        specs = {}
        for name, kw in dict(state.get("specs") or {}).items():
            kw = dict(kw)
            calls = int(kw.pop("calls", 0))
            trips = int(kw.pop("trips", 0))
            fs = FaultSpec(**kw)
            fs.calls, fs.trips = calls, trips
            specs[name] = fs
        with self._lock:
            self._seed = int(state.get("seed", 0))
            self._rng = np.random.default_rng(self._seed)
            rng_state = state.get("rng_state")
            if rng_state is not None:
                self._rng.bit_generator.state = rng_state
            self._specs = specs
            self.armed = bool(specs)

    def fire(self, name: str, payload: Any = None, meta: dict = None):
        spec = self._specs.get(name)
        if spec is None:
            return payload
        with self._lock:
            trip = spec.should_trip(self._rng)
        if not trip:
            return payload
        # every trip lands in the flight recorder: a post-mortem dump
        # shows the injected fault right before the recovery machinery's
        # own events (retry, mark_dead, rollback, re-form)
        from paddle_tpu.framework.observability import flight
        flight.record("chaos.trip", severity="warn", point=name,
                      mode=spec.mode, call=spec.calls,
                      **({"meta": meta} if meta else {}))
        if spec.mode == "latency":
            time.sleep(spec.latency)
            return payload
        if spec.mode == "nan":
            return _poison(payload, index=spec.payload_index)
        raise InjectedFault(
            f"chaos[{name}] injected fault (call {spec.calls}"
            + (f", {meta}" if meta else "") + ")"
            + (f": {spec.message}" if spec.message else ""))

    def stats(self) -> Dict[str, Dict[str, int]]:
        with self._lock:
            return {n: {"calls": s.calls, "trips": s.trips}
                    for n, s in self._specs.items()}


def _poison(payload, index=None):
    """NaN-poison every float array in ``payload`` (first element of each
    array, enough for any finiteness sweep to trip); non-float leaves and
    non-array values pass through untouched.  ``index`` (FaultSpec
    ``payload_index``) restricts the poison to one element of a
    tuple/list payload — the targeted-gradient fault the numerics
    plane's leaf attribution is tested with."""
    if payload is None:
        return None
    if isinstance(payload, (list, tuple)):
        if index is not None:
            out = list(payload)
            if not -len(out) <= index < len(out):
                raise IndexError(
                    f"chaos payload_index {index} out of range for a "
                    f"{len(out)}-element payload")
            out[index] = _poison(out[index])
            return type(payload)(out)
        return type(payload)(_poison(p) for p in payload)
    data = getattr(payload, "_data", None)       # paddle Tensor
    if data is not None:
        poisoned = _poison_array(data)
        if poisoned is data:
            return payload
        return type(payload)(poisoned)
    return _poison_array(payload)


def _poison_array(arr):
    try:
        a = np.asarray(arr)
    except Exception:                            # noqa: BLE001
        return arr
    if not np.issubdtype(a.dtype, np.floating):
        return arr
    a = a.copy()
    a.reshape(-1)[0] = np.nan
    return a


_registry = ChaosRegistry()
_env_armed = False
_explicit_seed = False


def arm_from_flags(force: bool = False):
    """Arm the registry from FLAGS_chaos_spec / FLAGS_chaos_seed (env or
    set_flags).  Called lazily on the first fault_point hit so a launcher
    can arm an entire child-process tree via the environment.  The env
    seed is applied only when no explicit reset(seed)/reseed happened
    first — lazy env arming must never clobber a seed the caller pinned
    (unless ``force=True`` re-reads the flags deliberately)."""
    global _env_armed
    if _env_armed and not force:
        return
    _env_armed = True
    from paddle_tpu.framework.flags import flag
    if force or not _explicit_seed:
        _registry.reseed(int(flag("chaos_seed")))
    raw = flag("chaos_spec")
    if not raw:
        return
    spec = json.loads(raw) if isinstance(raw, str) else dict(raw)
    for name, kw in spec.items():
        _registry.arm(name, **kw)


def fault_point(name: str, payload: Any = None, meta: dict = None):
    """Consult the chaos registry at a named site.  Returns the payload
    (possibly NaN-poisoned), raises :class:`InjectedFault`, or sleeps,
    per the armed schedule; a no-op returning ``payload`` when nothing
    is armed for ``name``."""
    if not _env_armed:
        arm_from_flags()
    if not _registry.armed:
        return payload
    return _registry.fire(name, payload, meta)


def arm(name: str, **spec) -> FaultSpec:
    if not _env_armed:
        arm_from_flags()
    return _registry.arm(name, **spec)


def disarm(name: Optional[str] = None):
    _registry.disarm(name)


def reset(seed: int = 0):
    """Disarm everything and reseed — each chaos test starts here."""
    global _explicit_seed
    _explicit_seed = True
    _registry.disarm()
    _registry.reseed(seed)


def stats() -> Dict[str, Dict[str, int]]:
    return _registry.stats()


def arm_state() -> Dict[str, Any]:
    """JSON-able snapshot of the full chaos state — seed, mid-sequence
    rng stream, and every armed spec with its call/trip counters.
    Recorded into incident bundles so :func:`restore_state` resumes the
    exact fault schedule a mid-run incident saw (the seed alone would
    replay from call zero, a different schedule)."""
    if not _env_armed:
        arm_from_flags()
    return _registry.export_state()


def restore_state(state: Dict[str, Any]):
    """Reinstall an :func:`arm_state` snapshot (replay's arming path).

    Pins the seed as explicit (lazy env arming must not clobber a
    restored stream) and auto-registers spec names this process has not
    declared — they were valid where the snapshot was taken, and a
    replay refusing its own recorded schedule would be the
    false-green the registry exists to prevent."""
    global _env_armed, _explicit_seed
    _env_armed = True
    _explicit_seed = True
    for name in dict(state.get("specs") or {}):
        if name not in _known_points:
            register_fault_point(name, carries_payload=True)
    _registry.import_state(state)


@contextlib.contextmanager
def inject(name: str, **spec):
    """Scope one armed fault point::

        with chaos.inject("ps.rpc", mode="error", nth=2, n_times=1):
            client.pull(...)     # the 2nd RPC raises InjectedFault
    """
    fs = arm(name, **spec)
    try:
        yield fs
    finally:
        disarm(name)

"""paddle_tpu.profiler — host + device profiling.

Parity targets in the reference:
  * RecordEvent host spans       — platform/profiler.h:127 (RecordEvent),
    python surface fluid/profiler.py record_event
  * start/stop/reset_profiler    — fluid/profiler.py:109-253
  * profiler() context manager   — fluid/profiler.py:255
  * CUPTI device tracing         — platform/device_tracer.cc:57
  * chrome-trace timeline        — tools/timeline.py

TPU mapping: device-side tracing is jax.profiler (XLA's profiler — the
CUPTI analogue), which captures per-op device timelines viewable in
TensorBoard/Perfetto.  Host spans are RecordEvent context managers that
both (a) feed an in-process aggregate table (calls/total/min/max/ave —
the Profiling Report) and (b) emit jax.profiler.TraceAnnotation scopes so
the same names show up inside the device trace.  ``export_chrome_tracing``
writes the host spans in chrome://tracing JSON (timeline.py's role).

Names in a device trace (``start_profiler`` or a bare
``jax.profiler.start_trace``; one ``.xplane.pb``, one clock):

* host, the calling thread's line: ``TrainStep`` (one per call of a
  ``jit.TrainStep`` / ``ShardedTrainStep``, with ``step=<n>``) and inside
  it, in order, ``TrainStep.prepare`` (layouts, live state, signature and
  cache lookup), ``TrainStep.launch`` (the jitted call alone: trace +
  compile on a miss, enqueue on a hit) and ``TrainStep.commit`` (state
  write-back and every per-step hook); ``TrainStep.multi_step`` with the
  same three children; ``model.init`` (the parameters' draw in the
  constructor of ``models.GPT``, ``Bert``, ``NemotronH`` and
  ``BailingHybrid``); ``Predictor.run``; and, while paddle's profiler is
  on, one row per tracer span (``ps.*``, ``ingest.*``, ``jit.compile``)
  and one per phase of every compile jax runs (``jit.trace``,
  ``jit.lower``, ``jit.backend_compile``, each with its ``fun_name``;
  ``framework.health`` books them, on this clock).
* device, in each operation's ``op_name`` path (``jax.named_scope``):
  ``embed``, ``attn`` (inner ``ln``, ``qkv``, ``core``, ``out``), ``mlp``
  (inner ``ln``, ``up``, ``down``) and ``head_loss`` from ``models/gpt.py``
  and ``models/bert.py``; from ``models/nemotron_h.py`` the same ``embed``,
  ``attn`` and ``head_loss``, its expert layer under ``mlp`` (inner ``ln``,
  ``router``, ``latent_down``, ``dispatch``, ``experts``, ``combine``,
  ``latent_up``, ``shared``; ``nn/functional/moe.py``) and its Mamba-2
  layer under ``ssm`` (inner ``ln``, ``in_proj``, ``conv``, ``scan``,
  ``gate_norm``, ``out``; ``nn/functional/ssm.py``); from
  ``models/bailing_hybrid.py`` the same ``embed``, ``head_loss``, its
  latent attention under ``attn`` (``ln``, ``qkv``, ``core``, ``out``),
  its dense MLP under ``mlp`` (``ln``, ``up``, ``down``), its SwiGLU
  experts under ``mlp`` (``ln``, ``router``, ``dispatch``, ``experts``,
  ``combine``, ``shared``) and its KDA layer under ``kda`` (inner ``ln``,
  ``qkv``, ``conv``, ``gate``, ``scan``, ``out_norm``, ``out``;
  ``nn/functional/kda.py``); ``optimizer`` from
  ``jit.apply_functional_update``, ``grad_exchange`` where a step reduces
  gradients itself (``parallel/zero.py``).  jax adds the pass: ``jvp(`` is
  the forward, ``transpose(`` the backward, ``rematted_computation`` the
  forward that ``jax.checkpoint`` runs again.
* counters (``framework.monitor``), added once per traced call, so
  trace-time counts: ``flash_subtiles_computed_total`` / ``_skipped_total``,
  ``flash_dispatch_kernel_total`` / ``_xla_for_speed_total`` and
  ``flash_bwd_fused_total`` (backwards that ran as the one kernel
  ``flash_bwd_dq_dkv``; ``ops/pallas/flash_attention.py``),
  ``moe_calls_traced_total``,
  ``moe_expert_rows_computed_total``, ``moe_expert_rows_expected_total``
  (``nn/functional/moe.py``), ``moe_router_kept_blocks_total`` (expert
  blocks whose ``jax.checkpoint`` keeps the router's choice for the
  backward, one per ``E`` block of a stack traced under ``remat``;
  ``models/nemotron_h.py``, ``models/bailing_hybrid.py``),
  ``ssm_chunks_traced_total`` (``nn/functional/ssm.py``),
  ``kda_chunks_traced_total`` and ``kda_carry_kernel_total`` (calls
  whose state crossed the chunks in ``ops/pallas/kda_carry.py``;
  ``nn/functional/kda.py``).
* set-up counters, always on: ``model_init_seconds_total`` (the seconds
  of every ``model.init`` span) and, for the compiles booked to step calls
  (``framework.health``), ``jit_traces_total``,
  ``jit_trace_seconds_total``, ``jit_lower_seconds_total``,
  ``jit_backend_seconds_total`` and ``jit_cold_compile_seconds_total``;
  ``health.compile_report()`` gives the same numbers per site as
  ``traces``, ``trace_s``, ``lower_s``, ``backend_s`` and
  ``cold_compile_s``.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["RecordEvent", "CountedEvent", "record_event", "record_span",
           "start_profiler", "stop_profiler", "reset_profiler", "profiler",
           "export_chrome_tracing", "is_profiling"]

_state = {
    "on": False,
    "device": False,        # jax.profiler trace running
    "trace_dir": None,
}
_lock = threading.Lock()
# name -> [calls, total, min, max] running aggregates (seconds; calls is
# an int) — O(1) memory per distinct name, however long the profiling run
_events: Dict[str, list] = {}
_spans: List[tuple] = []                 # (name, tid, t0, t1, args)
_dropped = [0]                                # spans over the retention cap
_t_start = [0.0]


def _max_spans() -> int:
    """Retention cap for the chrome-trace span list (the aggregate
    table above is O(names) regardless).  FLAGS_profiler_max_spans."""
    from paddle_tpu.framework.flags import flag
    return int(flag("profiler_max_spans"))


def is_profiling() -> bool:
    return _state["on"]


class RecordEvent:
    """Named host span (platform/profiler.h:127).  Usable as a context
    manager or decorator.  Always emits a jax TraceAnnotation (so names
    appear in device traces even outside start/stop_profiler: with no
    trace running a TraceMe is a flag test); ``annotations`` become the
    event's stats there (``step=3``).  Aggregates host wall time only
    while profiling is on."""

    def __init__(self, name: str, **annotations):
        self.name = name
        self._annotations = annotations
        self._ann = None
        self._t0 = 0.0

    def __enter__(self):
        # a TraceMe starts at construction, so it is built here
        self._ann = TraceAnnotation(self.name, **self._annotations)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        if _state["on"]:
            record_span(self.name, self._t0, t1)
        return False

    def __call__(self, fn):
        def wrapped(*a, **k):
            with RecordEvent(self.name):
                return fn(*a, **k)
        wrapped.__name__ = getattr(fn, "__name__", "wrapped")
        return wrapped


class CountedEvent(RecordEvent):
    """A :class:`RecordEvent` whose seconds also go, profiling or not,
    into the ``framework.monitor`` counter named after it, dots as
    underscores: ``model.init`` adds to ``model_init_seconds_total``.
    For set-up spans a run reads back, never for a per-step one."""

    def __exit__(self, *exc):
        super().__exit__(*exc)
        from paddle_tpu.framework import monitor
        monitor.stat_add(self.name.replace(".", "_") + "_seconds_total",
                         time.perf_counter() - self._t0)
        return False


def record_span(name: str, t0: float, t1: float, **args):
    """Book one span of ``name`` from ``t0`` to ``t1`` (``perf_counter``
    seconds) on the calling thread, while profiling is on: into the
    aggregate table and the timeline, ``args`` as its chrome-trace
    arguments."""
    if not _state["on"]:
        return
    dur = t1 - t0
    with _lock:
        e = _events.get(name)
        if e is None:
            _events[name] = [1, dur, dur, dur]
        else:
            e[0] += 1
            e[1] += dur
            if dur < e[2]:
                e[2] = dur
            if dur > e[3]:
                e[3] = dur
        # the aggregate above keeps counting unconditionally; only the
        # per-span timeline is bounded (long profiling runs must not grow
        # host memory without limit)
        if len(_spans) < _max_spans():
            _spans.append((name, threading.get_ident(), t0, t1, args))
        else:
            _dropped[0] += 1


@contextlib.contextmanager
def record_event(name: str):
    """fluid/profiler.py record_event parity (contextmanager form)."""
    with RecordEvent(name):
        yield


def reset_profiler():
    """fluid/profiler.py:109."""
    with _lock:
        _events.clear()
        _spans.clear()
        _dropped[0] = 0
    _t_start[0] = time.perf_counter()


def start_profiler(state: str = "All", tracer_option: str = "Default",
                   trace_dir: Optional[str] = None):
    """fluid/profiler.py:131.  state: 'CPU' = host spans only;
    'GPU'/'TPU'/'All' = also start the XLA device trace (written under
    ``trace_dir``, default /tmp/paddle_tpu_profile, TensorBoard format)."""
    if state not in ("CPU", "GPU", "TPU", "All"):
        raise ValueError("state must be 'CPU', 'GPU', 'TPU' or 'All'")
    if tracer_option not in ("Default", "OpDetail", "AllOpDetail"):
        raise ValueError("tracer_option must be 'Default', 'OpDetail' "
                         "or 'AllOpDetail'")
    reset_profiler()
    _state["on"] = True
    if state != "CPU":
        import jax
        d = trace_dir or "/tmp/paddle_tpu_profile"
        os.makedirs(d, exist_ok=True)
        try:
            jax.profiler.start_trace(d)
            _state["device"] = True
            _state["trace_dir"] = d
        except Exception:                      # already tracing, or no device
            _state["device"] = False


def stop_profiler(sorted_key: Optional[str] = None,
                  profile_path: str = "/tmp/profile"):
    """fluid/profiler.py:198 — stop, print the Profiling Report, and (if a
    device trace was running) finalize it; host spans also go to
    ``profile_path`` as chrome-trace JSON (timeline.py role)."""
    if not _state["on"]:
        return
    if _state["device"]:
        import jax
        jax.profiler.stop_trace()
        _state["device"] = False
    _state["on"] = False
    export_chrome_tracing(profile_path)
    _print_report(sorted_key)


def _print_report(sorted_key):
    if sorted_key not in (None, "calls", "total", "max", "min", "ave"):
        raise ValueError("sorted_key must be one of None/'calls'/'total'/"
                         "'max'/'min'/'ave'")
    with _lock:
        rows = []
        grand = 0.0
        for name, (calls, tot, mn, mx) in _events.items():
            grand += tot
            rows.append((name, calls, tot * 1e3, mn * 1e3,
                         mx * 1e3, tot / calls * 1e3))
        dropped = _dropped[0]
    keyi = {"calls": 1, "total": 2, "min": 3, "max": 4, "ave": 5}
    if sorted_key:
        rows.sort(key=lambda r: r[keyi[sorted_key]], reverse=True)
    print("------------------------->     Profiling Report     "
          "<-------------------------\n")
    print("Place: TPU\nTime unit: ms\nSorted by {} in descending order in "
          "the same thread\n".format(sorted_key or "first end time"))
    hdr = f"{'Event':<32}{'Calls':>8}{'Total':>12}{'Min.':>10}" \
          f"{'Max.':>10}{'Ave.':>10}{'Ratio.':>10}"
    print(hdr)
    for name, calls, tot, mn, mx, ave in rows:
        ratio = tot / (grand * 1e3) if grand else 0.0
        print(f"{name:<32}{calls:>8}{tot:>12.4f}{mn:>10.4f}{mx:>10.4f}"
              f"{ave:>10.4f}{ratio:>10.6f}")
    if dropped:
        print(f"\n{dropped} span(s) dropped from the timeline "
              f"(FLAGS_profiler_max_spans={_max_spans()}); the "
              "aggregates above still count every event")
    if _state["trace_dir"]:
        print(f"\nDevice trace (TensorBoard/XProf): {_state['trace_dir']}")


def export_chrome_tracing(path: str = "/tmp/profile"):
    """Write host RecordEvent spans as chrome://tracing JSON — the
    tools/timeline.py role (its _chrome_trace_format output)."""
    with _lock:
        spans = list(_spans)
        dropped = _dropped[0]
    t0 = _t_start[0]
    events = [{"name": name, "ph": "X", "pid": 0, "tid": tid,
               "ts": (a - t0) * 1e6, "dur": (b - a) * 1e6, "cat": "host",
               **({"args": args} if args else {})}
              for name, tid, a, b, args in spans]
    payload = {"traceEvents": events, "displayTimeUnit": "ms",
               "metadata": {"dropped_spans": dropped,
                            "max_spans": _max_spans()}}
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f)
    return path


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: Optional[str] = None,
             profile_path: str = "/tmp/profile",
             tracer_option: str = "Default"):
    """fluid/profiler.py:255 context-manager parity."""
    start_profiler(state, tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)

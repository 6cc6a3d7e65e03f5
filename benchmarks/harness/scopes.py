"""Device time by named region and pass, and the step's own host spans.

The program names the inside of its step: ``jax.named_scope`` regions in
the compiled step (``embed``, ``attn``, ``mlp``, ``head_loss``,
``optimizer``, ``grad_exchange``), to which jax adds the pass
(``jvp(...)`` forward, ``transpose(...)`` backward,
``rematted_computation`` the forward run again), and ``TraceAnnotation``s
around the phases of ``TrainStep.__call__``.  This module reads both out
of the ``.xplane.pb`` the traced stretch just wrote.

Where the scope path is (looked at by hand on a v5e, jax 0.9.0, PR 25):
not in what ``jax.profiler.ProfileData`` shows of an ``XLA Ops`` event.
Its name is the HLO text without ``metadata={...}``, its stats are
``device_offset_ps``, ``device_duration_ps`` and a multiplier; the
``tf_op``, ``flops`` and ``bytes_accessed`` that XProf shows sit on the
plane's event *metadata*, which ``ProfileData`` does not expose.  The
same file holds, on the plane ``/host:metadata``, one ``Hlo Proto`` per
executed program, named like the program's ``XLA Modules`` events, and
in it every instruction with its ``OpMetadata.op_name``.  That is read
here, with twenty lines of protobuf wire format and no dependency: the
step is not compiled again, and the CPU rehearsal has the same plane.

A fusion carries one path, its root's: that is this instrument's
resolution.  Sums are of event durations on the first chip inside
``trace.window``, per step, like ``Trace.per_step``; control flow is
left out by opcode as ``trace_reduce.labelled`` does; an asynchronous
operation's time in flight is not added (the collective metrics have it).
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import statistics
from collections import defaultdict

from benchmarks.harness import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(HERE, "scope_regions.json")) as _f:
    _NAMES = json.load(_f)
MODEL_REGIONS = tuple(_NAMES["model"])
OWN_PASS = tuple(_NAMES["own_pass"])
HOST_SPANS = tuple(_NAMES["host_spans"])
UNSCOPED = "unscoped"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_TOKEN = re.compile(r"[^/()]+")


# -- the scope path of an operation -------------------------------------------

def classify(path) -> tuple:
    """(pass, region) of one ``op_name`` path; either may be None.  A
    region is the first region name among the path's tokens; a transform
    is a name followed by ``(``, so the primitive ``transpose`` at the
    end of a path is not the backward pass.  jax supplies the pass of
    what the step differentiates: ``rematted_computation`` is the
    forward run again, ``transpose(`` the backward, ``jvp(`` alone the
    forward, in a region or not (AMP's casts of the stacked parameters
    and the layer scan's bookkeeping are in none)."""
    path = path or ""
    tokens = _TOKEN.findall(path)
    region = next((t for t in tokens if t in MODEL_REGIONS + OWN_PASS), None)
    if region in OWN_PASS:
        return region, region
    if "rematted_computation" in tokens:
        return "recompute", region
    if "transpose(" in path:
        return "bwd", region
    if region or "jvp(" in path:
        return "fwd", region
    return None, None


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        value = shift = 0
        while True:
            byte = buf[i]
            i += 1
            value |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                return value

    while i < n:
        key = varint()
        kind = key & 7
        if kind == 0:
            yield key >> 3, varint()
            continue
        size = {1: 8, 5: 4}.get(kind)
        if size is None:
            if kind != 2:
                raise ValueError(f"wire type {kind} in a trace file")
            size = varint()
        yield key >> 3, buf[i:i + size]
        i += size


def _sub(buf, number):
    return (value for field, value in _fields(buf) if field == number)


def hlo_paths(raw) -> dict:
    """``{program: {instruction: (opcode, op_name)}}`` from the bytes of
    an ``.xplane.pb``.  Field numbers (tsl ``xplane.proto``, xla
    ``hlo.proto``): XSpace.planes 1; XPlane.name 2, .event_metadata 4 (a
    map entry's value is 2); XEventMetadata.name 2, .stats 5;
    XStat.bytes_value 6; HloProto.hlo_module 1; HloModuleProto
    .computations 3; HloComputationProto.instructions 2;
    HloInstructionProto.name 1, .opcode 2, .metadata 7;
    OpMetadata.op_name 2."""
    out = {}
    for plane in _sub(memoryview(raw), 1):
        if bytes(next(_sub(plane, 2), b"")) != b"/host:metadata":
            continue
        for entry in _sub(plane, 4):
            for event in _sub(entry, 2):
                program = bytes(next(_sub(event, 2), b"")).decode()
                for stat in _sub(event, 5):
                    for proto in _sub(stat, 6):
                        out.setdefault(program, {}).update(
                            _instructions(proto))
    return out


def _instructions(hlo_proto):
    for module in _sub(hlo_proto, 1):
        for computation in _sub(module, 3):
            for instruction in _sub(computation, 2):
                name = opcode = path = ""
                for field, value in _fields(instruction):
                    if field == 1:
                        name = bytes(value).decode()
                    elif field == 2:
                        opcode = bytes(value).decode()
                    elif field == 7:
                        path = bytes(next(_sub(value, 2), b"")).decode()
                yield name, (opcode, path)


# -- the trace file -----------------------------------------------------------

def newest_trace_file(root: str):
    found = glob.glob(os.path.join(root, ".bench_trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _seconds(e) -> tuple:
    return e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9


def read_events(data, chip: int) -> dict:
    """What ``trace_reduce.load`` drops: per-line event counts to know
    the file by, ``(program, instruction, text, start, end)`` of every
    operation on ``chip`` (on the CPU: XLA:CPU's thunks), and the
    program's own host spans."""
    structure, ops, modules, cpu_ops = {}, [], [], []
    spans = defaultdict(list)
    on_chip = any(_DEVICE_PLANE.match(p.name) for p in data.planes)
    for plane in data.planes:
        device = _DEVICE_PLANE.match(plane.name)
        lines = structure.setdefault(plane.name, {})
        for line in plane.lines:
            if device and line.name in (trace_reduce.OPS_LINE,
                                        trace_reduce.ASYNC_LINE,
                                        trace_reduce.MODULES_LINE):
                events = list(line.events)
                lines[line.name] = len(events)
                if int(device.group(1)) != chip:
                    continue
                if line.name == trace_reduce.MODULES_LINE:
                    modules += [(*_seconds(e), e.name) for e in events]
                elif line.name == trace_reduce.OPS_LINE:
                    ops += [(e.name, *_seconds(e)) for e in events]
            elif plane.name.startswith("/host:"):
                lines[line.name] = 0
                for e in line.events:
                    lines[line.name] += 1
                    if e.name in HOST_SPANS:
                        spans[e.name].append(_seconds(e))
                    elif not on_chip:
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            cpu_ops.append((
                                f"{stats.get('hlo_module')}"
                                f"({stats.get('program_id')})",
                                stats["hlo_op"], e.name, *_seconds(e)))
    if not on_chip:
        return {"structure": structure, "ops": cpu_ops, "spans": spans}
    modules.sort()
    starts = [m[0] for m in modules]
    placed = []
    for text, start, end in ops:
        i = bisect.bisect_right(starts, start) - 1
        program = modules[i][2] if i >= 0 and start < modules[i][1] else ""
        placed.append((program, text.partition(" = ")[0].lstrip("%"), text,
                       start, end))
    return {"structure": structure, "ops": placed, "spans": spans}


# -- the table ----------------------------------------------------------------

def reduce_ops(ops, paths: dict, lo: float, hi: float, steps: int) -> dict:
    """Seconds a step by (pass, region) of the operations inside
    ``[lo, hi]``, control flow left out; the ten largest with their
    scope, and the five largest of those with neither pass nor region,
    which are ``unscoped``."""
    by, top = defaultdict(float), defaultdict(float)
    unscoped = defaultdict(float)
    for program, instruction, text, start, end in ops:
        seconds = min(end, hi) - max(start, lo)
        if seconds <= 0:
            continue
        opcode, path = paths.get(program, {}).get(instruction, ("", ""))
        if (trace_reduce.opcode(text) or opcode) in trace_reduce.CONTROL_FLOW:
            continue
        which, region = classify(path)
        by[(which or UNSCOPED, region)] += seconds / steps
        label = trace_reduce.op_label(text) if " = " in text else text
        top[(label, path)] += seconds / steps
        if which is None:
            unscoped[(label, path)] += seconds / steps
    return {"by": dict(by), "total": sum(by.values()),
            "scoped": any(region for _, region in by),
            "top": _largest(top, 10), "top_unscoped": _largest(unscoped, 5)}


def _largest(sums: dict, n: int) -> list:
    return sorted(sums.items(), key=lambda kv: -kv[1])[:n]


def table(trace, run) -> dict | None:
    """The reduction of the traced stretch's own file, made once a run
    (kept on ``trace``) and printed through ``run["say"]``.  None if
    there is no such file or it is another run's."""
    if not hasattr(trace, "scope_table"):
        trace.scope_table = _build_table(trace, run["say"])
    return trace.scope_table


def _build_table(trace, say):
    from jax.profiler import ProfileData
    path = newest_trace_file(ROOT)
    if path is None:
        say("scopes: no .xplane.pb under .bench_trace/")
        return None
    chip = trace.chips[0]
    read = read_events(ProfileData.from_file(path), chip)
    if read["structure"] != trace.structure:
        say(f"scopes: {path} is not this run's trace (its per-line event "
            f"counts differ from trace.structure): not read")
        return None
    with open(path, "rb") as f:
        paths = hlo_paths(f.read())
    lo, hi = trace.window[chip]
    out = reduce_ops(read["ops"], paths, lo, hi, trace.steps)
    host_lo = min(w[0] for w in trace.window.values())
    out["host"] = {name: [e - s for s, e in spans if s >= host_lo]
                   for name, spans in read["spans"].items()}
    step_ms = 1e3 * trace.busy_s() / trace.steps
    rows = defaultdict(dict)
    for (which, region), seconds in sorted(
            out["by"].items(), key=lambda kv: -kv[1]):
        rows[which][region or "no_region"] = [
            round(1e3 * seconds, 4), round(1e5 * seconds / step_ms, 2)]
    say("scopes: " + json.dumps({
        "file": os.path.relpath(path, ROOT), "programs_with_hlo": len(paths),
        "ms_a_step_and_pct_of_step_device_ms": rows,
        "all_operations_ms": round(1e3 * out["total"], 4),
        "step_device_ms": round(step_ms, 4),
        "host_spans_median_ms_and_count": {
            name: [round(1e3 * statistics.median(d), 4), len(d)]
            for name, d in out["host"].items() if d},
        **{f"{key}_operations_ms_a_step": [
            [label, scope, round(1e3 * seconds, 4)]
            for (label, scope), seconds in out[key]]
           for key in ("top", "top_unscoped")}}))
    return out


# -- what the readers under layer_metrics/ ask for ---------------------------

def ms_per_step(trace, run, passes=None, regions=None):
    """Summed ms a step of the operations whose pass is in ``passes``
    and whose region is in ``regions`` (None: any).  None where the
    program names no region at all (before PR 25) or nothing matches."""
    scopes = table(trace, run)
    if scopes is None or not scopes["scoped"]:
        return None
    seconds = sum(s for (which, region), s in scopes["by"].items()
                  if (passes is None or which in passes)
                  and (regions is None or region in regions))
    return 1e3 * seconds if seconds > 0 else None


def unscoped_share(trace, run):
    scopes = table(trace, run)
    if scopes is None or not scopes["scoped"]:
        return None
    unscoped = sum(s for (which, _), s in scopes["by"].items()
                   if which == UNSCOPED)
    return 100.0 * unscoped / scopes["total"]


def host_span_ms(trace, run, name: str):
    """Median ms of the program's host span ``name`` in the window."""
    scopes = table(trace, run)
    spans = scopes["host"].get(name) if scopes else None
    return 1e3 * statistics.median(spans) if spans else None

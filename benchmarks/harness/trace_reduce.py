"""From a profiler trace to intervals, unions and sums by name.

Everything below ``load`` is arithmetic on plain tuples and is checked by
hand-worked cases in ``benchmarks/tests/test_trace_reduce.py``.  An event
is ``(name, start, end)`` in seconds on the trace's clock; an interval is
``(start, end)``.

What a TPU trace looks like (jax 0.9, ``.xplane.pb``): one plane per chip
named ``/device:TPU:<n>``, whose line ``XLA Ops`` holds one event per
executed HLO operation, named by its whole HLO text (a ``while`` or
``call`` encloses its body's events; operations of different units may
overlap by a little), whose line ``Async XLA Ops`` holds
asynchronous operations from start to done (copies, collectives), and
whose line ``XLA Modules`` holds one event per executed program; the
plane ``/host:CPU`` has one line per host thread, and the
benchmark's ``TraceAnnotation`` spans are events on the main thread's.
All planes share one clock.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

SMALL_GAP_S = 20e-6
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
MOSAIC_TARGET = "tpu_custom_call"
CONTROL_FLOW = ("while", "conditional", "call")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


# -- intervals ---------------------------------------------------------------

def union(intervals) -> list:
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def total(intervals) -> float:
    return sum(hi - lo for lo, hi in intervals)


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a, b) -> list:
    """The parts of union(a) that union(b) does not cover."""
    out, b = [], union(b)
    for lo, hi in union(a):
        for blo, bhi in b:
            if bhi <= lo or blo >= hi:
                continue
            if blo > lo:
                out.append((lo, blo))
            lo = max(lo, bhi)
            if lo >= hi:
                break
        if lo < hi:
            out.append((lo, hi))
    return out


def spans_of(events) -> list:
    return [(s, e) for _, s, e in events]


# -- events ------------------------------------------------------------------

def sum_by_name(events, lo: float, hi: float) -> dict:
    """Seconds inside ``[lo, hi]`` by event name."""
    out = defaultdict(float)
    for name, s, e in events:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            out[name] += d
    return dict(out)


def matching(events, pattern: str) -> list:
    rx = re.compile(pattern)
    return [ev for ev in events if rx.search(ev[0])]


def idle_gaps(busy, lo: float, hi: float) -> list:
    return subtract([(lo, hi)], busy)


def attribute_gaps(gaps, host_spans, programs=()) -> dict:
    """Idle seconds by what the host was doing: each gap goes to the host
    span that covers its midpoint (``outside_spans`` if none), split by
    whether a program was executing around it; gaps under 20 us are one
    row, because they are the device's own spacing between operations."""
    out = defaultdict(float)
    for lo, hi in gaps:
        if hi - lo < SMALL_GAP_S:
            out["gaps_under_20us"] += hi - lo
            continue
        mid = (lo + hi) / 2
        span = next((n for n, s, e in host_spans if s <= mid < e),
                    "outside_spans")
        inside = any(s <= mid < e for _, s, e in programs)
        out[f"{span}.{'inside_program' if inside else 'between_programs'}"] \
            += hi - lo
    return dict(out)


def _result_and_rest(text: str) -> tuple:
    """An instruction's HLO text ``%name = <result type> opcode(...)``
    split after the result type, which may be a tuple with spaces."""
    body = text.partition(" = ")[2]
    depth = 0
    for i, ch in enumerate(body):
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            return body[:i], body[i + 1:]
    return body, ""


def opcode(text: str) -> str:
    return _result_and_rest(text)[1].partition("(")[0]


def op_label(text: str) -> str:
    """One label for the same operation in every layer: the instruction's
    name without its number, and the result's shapes where the event is
    named by its HLO text (``%fusion.7 = bf16[8,1024]{1,0} fusion(...)``)."""
    label = re.sub(r"\.\d+$", "", text.partition(" = ")[0].lstrip("%"))
    shapes = re.findall(r"[a-z]+\d+\[[\d,]*\]", _result_and_rest(text)[0])
    if shapes:
        label += "_" + "_".join(shapes)
    return re.sub(r"[^A-Za-z0-9.\-]+", "_", label).strip("_")


def labelled(raw_events) -> tuple:
    """(events, Mosaic labels) from events named by HLO text.  Control
    flow (``while``, ``conditional``, ``call``) is left out: it encloses
    its body's events, and only the body is work.  Nothing else is
    judged by containment: a kernel's event may well span a small
    operation of another unit (a ``copy-start``)."""
    events, mosaic, seen = [], set(), {}
    for text, start, end in raw_events:
        if text not in seen:
            seen[text] = (None if opcode(text) in CONTROL_FLOW
                          else op_label(text))
            if MOSAIC_TARGET in text:
                mosaic.add(seen[text])
        if seen[text] is not None:
            events.append((seen[text], start, end))
    return events, mosaic


# -- the trace ---------------------------------------------------------------

@dataclass
class Trace:
    """What the readers under ``layer_metrics/`` are given."""
    ops: dict = field(default_factory=dict)        # chip -> op events
    async_ops: dict = field(default_factory=dict)  # chip -> start-to-done
    mosaic: set = field(default_factory=set)       # labels of Mosaic calls
    programs: dict = field(default_factory=dict)   # chip -> program events
    host: list = field(default_factory=list)       # the benchmark's spans
    window: dict = field(default_factory=dict)     # chip -> (lo, hi)
    steps: int = 0
    structure: dict = field(default_factory=dict)  # plane -> line -> count

    @property
    def chips(self) -> list:
        return sorted(self.ops)

    def window_s(self) -> float:
        return (sum(hi - lo for lo, hi in self.window.values())
                / max(len(self.window), 1))

    def busy(self, chip: int) -> list:
        lo, hi = self.window[chip]
        return union(clip(spans_of(self.ops[chip]), lo, hi))

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the chips."""
        return sum(total(self.busy(c)) for c in self.chips) / len(self.chips)

    def in_flight(self, chip: int, pattern: str) -> list:
        """Disjoint intervals of the window in which an operation whose
        name matches runs or, if asynchronous, is between start and
        done."""
        lo, hi = self.window[chip]
        evs = matching(self.ops[chip] + self.async_ops.get(chip, []),
                       pattern)
        return union(clip(spans_of(evs), lo, hi))

    def per_step(self, chip: int, pattern: str) -> float:
        """Summed seconds a step of the ops whose name matches."""
        lo, hi = self.window[chip]
        return sum(sum_by_name(matching(self.ops[chip], pattern),
                               lo, hi).values()) / self.steps


def steady_window(programs, host_steps, skip: int):
    """(lo, hi, steps): from the start of one step to the start of a later
    one, so that it holds whole periods.  On the chip a step is one
    execution of the program that takes most of the time; where the trace
    has no program line (the CPU rehearsal) a step is one host span."""
    if programs:
        by_name = defaultdict(list)
        for ev in programs:
            by_name[ev[0]].append(ev)
        marks = max(by_name.values(), key=lambda evs: total(spans_of(evs)))
    else:
        marks = host_steps
    starts = sorted(s for _, s, _ in marks)[skip:]
    if len(starts) < 2:
        return None
    return starts[0], starts[-1], len(starts) - 1


def build(ops, programs, host, step_span: str, skip: int) -> Trace:
    """A ``Trace`` from events: per-chip ops and the steady window of
    each chip on its own program executions."""
    tr = Trace(host=sorted(host, key=lambda ev: ev[1]))
    host_steps = [ev for ev in tr.host if ev[0] == step_span]
    for chip, evs in ops.items():
        win = steady_window(programs.get(chip, ()), host_steps, skip)
        if win is None:
            continue
        tr.ops[chip] = list(evs)
        tr.programs[chip] = list(programs.get(chip, ()))
        tr.window[chip] = win[:2]
        tr.steps = win[2] if not tr.steps else min(tr.steps, win[2])
    return tr


def load(path: str, span_names, step_span: str, skip: int) -> Trace:
    """Read an ``.xplane.pb`` with jax's own reader.  On the TPU an op
    event's name is the operation's whole HLO text."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, async_ops, programs, host, structure = {}, {}, {}, [], {}
    cpu_ops, mosaic = [], set()

    def events(line):
        return [(e.name, e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]

    on_chip = any(_DEVICE_PLANE.match(p.name) for p in data.planes)
    for plane in data.planes:
        chip = _DEVICE_PLANE.match(plane.name)
        lines = structure.setdefault(plane.name, {})
        for line in plane.lines:
            if chip and line.name in (OPS_LINE, ASYNC_LINE, MODULES_LINE):
                dest = {OPS_LINE: ops, ASYNC_LINE: async_ops,
                        MODULES_LINE: programs}[line.name]
                evs = events(line)
                lines[line.name] = len(evs)
                if line.name != MODULES_LINE:
                    evs, kernels = labelled(evs)
                    mosaic |= kernels
                dest.setdefault(int(chip.group(1)), []).extend(evs)
            elif plane.name.startswith("/host:"):
                lines[line.name] = 0
                for e in line.events:
                    lines[line.name] += 1
                    ev = (e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                    if e.name in span_names:
                        host.append(ev)
                    elif not on_chip and any(k == "hlo_op"
                                             for k, _ in e.stats):
                        cpu_ops.append(ev)
    if not on_chip:
        ops[0] = cpu_ops      # the CPU rehearsal: XLA:CPU's thunks
    tr = build(ops, programs, host, step_span, skip)
    tr.async_ops = {c: async_ops.get(c, []) for c in tr.ops}
    tr.mosaic, tr.structure = mosaic, structure
    return tr

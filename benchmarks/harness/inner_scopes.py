"""Device time by a region's inner scope: ``ssm/scan``, ``mlp/experts``.

``scopes.py`` sums by pass and by the regions of ``scope_regions.json``;
this reads the same trace file once more, through ``scopes.read_events``
and ``scopes.hlo_paths``, and matches any token of an operation's scope
path: a region is a token of the path, its inner scope the first token
after it that ``inner_regions.json`` lists for that region (so the
primitive ``scan`` inside ``mlp/router`` is no inner scope, and an
einsum's subscripts, which jax writes into the path, are none either).
Sums are of event durations on the first chip inside ``trace.window``,
per step, control flow left out by opcode, as in ``scopes.reduce_ops``.
Where the program names no such region, as before PR 27, the readers
return None and raise nothing.  A region that ``inner_regions.json`` does
not list is read through the inner names its reader passes (``names``);
a region known neither to the file nor to the caller reads None, as one
the program does not name.
"""
from __future__ import annotations

import json
import os
import re

from benchmarks.harness import scopes, trace_reduce

with open(os.path.join(scopes.HERE, "inner_regions.json")) as _f:
    INNER = {k: tuple(v) for k, v in json.load(_f).items()
             if not k.startswith("_")}
_TOKEN = re.compile(r"[^/()]+")


def _names(region: str, names):
    """The region's inner names: ``names`` where the caller gives them,
    else ``inner_regions.json``'s; None where neither knows the region."""
    return INNER.get(region) if names is None else tuple(names)


def inner_of(path: str, region: str, names=None):
    """The inner scope of ``region`` that ``path`` lies in: None where the
    path is not under the region or the region's inner names are unknown
    (``names``: see ``_names``), "" where it is under none of them."""
    names = _names(region, names)
    tokens = _TOKEN.findall(path or "")
    if names is None or region not in tokens:
        return None
    after = tokens[tokens.index(region) + 1:]
    return next((t for t in after if t in names), "")


def sum_inner(rows, region: str, inner=None, names=None):
    """Summed seconds of ``rows`` = [(path, seconds)] under ``region``,
    in any of the inner scopes ``inner`` (None: the whole region); None
    where the region's inner names are unknown."""
    names = _names(region, names)
    if names is None:
        return None
    total = 0.0
    for path, seconds in rows:
        found = inner_of(path, region, names)
        if found is not None and (inner is None or found in inner):
            total += seconds
    return total


def _rows(trace, run):
    """[(scope path, seconds a step)] of the traced stretch's own file,
    read once a run and kept on ``trace``; None if there is no such file
    or it is another run's."""
    if not hasattr(trace, "inner_scope_rows"):
        trace.inner_scope_rows = _read(trace)
    return trace.inner_scope_rows


def _read(trace):
    from jax.profiler import ProfileData
    path = scopes.newest_trace_file(scopes.ROOT)
    if path is None:
        return None
    chip = trace.chips[0]
    read = scopes.read_events(ProfileData.from_file(path), chip)
    if read["structure"] != trace.structure:
        return None
    with open(path, "rb") as f:
        paths = scopes.hlo_paths(f.read())
    lo, hi = trace.window[chip]
    rows = []
    for program, instruction, text, start, end in read["ops"]:
        seconds = min(end, hi) - max(start, lo)
        if seconds <= 0:
            continue
        opcode, scope = paths.get(program, {}).get(instruction, ("", ""))
        if (trace_reduce.opcode(text) or opcode) in trace_reduce.CONTROL_FLOW:
            continue
        rows.append((scope, seconds / trace.steps))
    return rows


def ms_per_step(trace, run, region: str, inner=None, names=None):
    """Summed ms a step, every pass, of the operations under ``region``
    (in the inner scopes ``inner``, among the region's inner ``names``
    where ``inner_regions.json`` lacks it); None where nothing matches."""
    rows = _rows(trace, run)
    seconds = None if rows is None else sum_inner(rows, region, inner, names)
    return 1e3 * seconds if seconds else None

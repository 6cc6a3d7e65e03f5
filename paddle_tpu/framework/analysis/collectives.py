"""Distributed-semantics analysis passes (PTA5xx) — the fourth front end
on the shared Diagnostic core.

The repo now carries five distinct sharded-execution paths
(``parallel/zero.py``, ``parallel/sharded.py``, ``parallel/dp_meta.py``,
``parallel/ring_attention.py``, the PS pipeline) whose correctness
contracts — every gradient reduced exactly once on ``dp``, replicas
bit-identical after the update, quantized payloads never summed by a
collective — were enforced only by example-specific tests.  These passes
make the contracts whole-program facts: they walk ``shard_map``/``pjit``
regions of a traced jaxpr and re-run the replication analysis the repo
deliberately disables at trace time (every manual region goes through
``mesh.shard_map_compat`` with ``check_vma=False``) as
*diagnostics* instead of trace errors.

The core is a mapped-axis **varying set** per value (the vma
lattice): a value is *varying* over a mesh axis when replicas along that
axis may hold different data.  Sources: inputs whose ``in_specs`` shard
a dim over the axis, and ``axis_index``.  Sinks: ``psum``/``pmax``/
``pmin`` and ``all_gather`` (no ``axis_index_groups``) clear the axis;
``psum_scatter``/``all_to_all``/``ppermute`` keep it (replicas still
hold different chunks).  Everything else unions its operands.

Shipped passes (stable IDs, see diagnostics.RULES):

========  ==============================================================
PTA501    unreduced value on a mapped axis: a shard_map output whose
          ``out_specs`` claim replication over an axis the value still
          varies on — the grad-leaf-reaches-the-optimizer-without-a-
          psum bug; replicas silently diverge (error).  A *complete
          ring* scan is recognized as a gather: a scan whose body
          ``ppermute``s over axis A with a single full cycle of size
          ``n = |A|`` and runs ``n`` or ``n-1`` trips has shown every
          replica every chunk, so scan outputs with leading dim ``n``
          (the assembled buffer) stop varying over A
          (``parallel/ring.py``'s ring_all_gather)
PTA502    collective axis mismatch: an axis name absent from the
          enclosing manual region (error), or a ``psum`` of an
          already-replicated value that is not a ``pmean`` — the
          double reduction multiplies by the axis size (warning)
PTA503    replicated/sharded mixing: ``all_gather`` whose only
          consumers statically slice one chunk back out — every
          replica gets chunk 0; a ``dynamic_slice`` at
          ``axis_index * shard_len`` was almost certainly meant
PTA504    quantized payload summed by a collective: int8 rows fed to
          ``psum``/``psum_scatter`` (error — the sum of encodings is
          not the encoding of the sum) or bf16/f16 payloads (warning —
          the wire accumulates in reduced precision); the legal idioms
          are ``wire.py`` quantize → ``all_to_all``/``all_gather`` →
          dequantize → local sum, and the fused ring
          (``parallel/ring.py``): quantize inside a ``ppermute`` scan
          carry with an **f32 accumulator**.  The pass also flags the
          fused ring gone wrong — an ``add`` consuming a ``ppermute``
          result that is still int8/uint8 encoded (error) or bf16/f16
          (warning) sums encoded payloads one hop at a time
PTA505    donated buffer crossing a collective boundary: a donated
          input consumed *directly* by a collective with no
          shape/dtype-matching output to alias — XLA cannot reuse the
          storage across the collective, so the donation only deletes
          the caller's array (warning)
PTA506    collective under a divergent traced conditional: a
          collective inside a ``cond``/``while`` region whose
          predicate varies over the collective's axis — replicas take
          different branches and the collective deadlocks on TPU
          (error); uniform predicates (the LocalSGD sync gate) pass
========  ==============================================================

Entry points: :func:`analyze_collectives` standalone, and
``jaxpr_passes.analyze_jaxpr`` runs the family over every trace — so
``TrainStep.analyze()`` / ``ShardedUpdateTrainStep.analyze()`` and the
``prog_lint --collectives`` zoo audit distributed semantics for free.
Jaxpr diagnostics carry no source line; suppress by rule ID via the
``disable=`` argument / ``--disable`` (the PTA1xx discipline).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
from jax.extend.core import Literal

from paddle_tpu.framework.analysis.diagnostics import (
    Diagnostic, Report, Severity, register_rule)

__all__ = ["analyze_collectives", "run_collective_passes",
           "COLLECTIVE_PRIMS"]

register_rule("PTA501", "unreduced value on a mapped axis",
              Severity.ERROR, "collective")
register_rule("PTA502", "collective axis mismatch / double reduction",
              Severity.ERROR, "collective")
register_rule("PTA503", "replicated/sharded mixing (gather-then-slice)",
              Severity.WARNING, "collective")
register_rule("PTA504", "quantized payload summed by a collective",
              Severity.ERROR, "collective")
register_rule("PTA505", "donated buffer crosses a collective boundary",
              Severity.WARNING, "collective")
register_rule("PTA506", "collective under a divergent traced conditional",
              Severity.ERROR, "collective")

#: collectives that REDUCE over their axes (replicas agree afterwards)
_REDUCE_PRIMS = frozenset({"psum", "pmax", "pmin"})
#: collectives whose output is identical on every group member
_GATHER_PRIMS = frozenset({"all_gather"})
#: collectives whose output still differs per replica (chunks move)
_VARY_KEEP_PRIMS = frozenset({"psum_scatter", "reduce_scatter",
                              "all_to_all", "ppermute", "pbroadcast"})
#: collectives whose payload is SUMMED elementwise on the wire
_SUM_PRIMS = frozenset({"psum", "psum_scatter", "reduce_scatter"})

COLLECTIVE_PRIMS = _REDUCE_PRIMS | _GATHER_PRIMS | _VARY_KEEP_PRIMS

# eqn.params keys holding nested jaxprs for generic call-like descent
_CALL_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")

_EMPTY = frozenset()


def _collective_axes(eqn) -> Tuple[str, ...]:
    """Axis names of a collective eqn, across the per-primitive
    spellings (``axes`` for psum/pmax/pmin, ``axis_name`` for the
    rest; tuples may nest)."""
    ax = eqn.params.get("axes", eqn.params.get("axis_name"))
    if ax is None:
        return ()
    out: List[str] = []
    stack = [ax]
    while stack:
        a = stack.pop()
        if isinstance(a, (tuple, list, frozenset, set)):
            stack.extend(a)
        elif isinstance(a, str):
            out.append(a)
    return tuple(sorted(out))


def _spec_axes(spec) -> frozenset:
    """Axis set of one shard_map in_specs/out_specs entry (a
    ``PartitionSpec``: per dim None, an axis name or a tuple of them)."""
    out = set()
    for axes in spec or ():
        if isinstance(axes, (tuple, list)):
            out.update(a for a in axes if isinstance(a, str))
        elif isinstance(axes, str):
            out.add(axes)
    return frozenset(out)


def _np_dtype(aval):
    try:
        return np.dtype(getattr(aval, "dtype", None))
    except TypeError:
        return None


def _aval_key(aval):
    return (tuple(getattr(aval, "shape", ())), _np_dtype(aval))


class _Ctx:
    """Per-analysis state threaded through the walk."""

    __slots__ = ("report", "name", "manual", "sizes", "donated",
                 "out_labels", "out_avals", "seen_manual", "flagged_505",
                 "ppermute_outs", "flagged_ring_sum")

    def __init__(self, report: Report, name: str):
        self.report = report
        self.name = name
        self.manual: frozenset = _EMPTY     # manual axes in scope
        self.sizes: Dict[str, int] = {}     # mesh axis -> size
        # donated *body* vars -> (outer global-view aval key, label)
        self.donated: Dict[object, object] = {}
        self.out_labels: Dict[object, str] = {}   # program outvar -> label
        self.out_avals: List[tuple] = []    # program output (shape, dtype)
        self.seen_manual = False
        self.flagged_505: set = set()       # one finding per donated var
        # ppermute result vars -> dtype (the fused-ring PTA504 check)
        self.ppermute_outs: Dict[object, object] = {}
        self.flagged_ring_sum: set = set()  # one finding per add eqn


def _vary(env, v) -> frozenset:
    if isinstance(v, Literal):
        return _EMPTY
    return env.get(v, _EMPTY)


def _is_mean_psum(eqn, jaxpr, ctx: _Ctx) -> bool:
    """True when this psum's result is immediately divided by the
    product of its axis sizes — the ``pmean`` lowering, which is the
    identity on an already-replicated value (sum·k/k), not the
    multiply-by-k double reduction PTA502 warns about."""
    axes = _collective_axes(eqn)
    k = 1
    for a in axes:
        k *= int(ctx.sizes.get(a, 0) or 0)
    if k <= 0:
        return False
    outs = set(eqn.outvars)
    for consumer in jaxpr.eqns:
        if consumer.primitive.name != "div":
            continue
        if consumer.invars and consumer.invars[0] in outs:
            d = consumer.invars[1]
            if not isinstance(d, Literal):
                continue
            try:
                if float(np.asarray(d.val)) == float(k):
                    return True
            except (TypeError, ValueError):
                continue
    return False


def _check_gather_then_slice(eqn, jaxpr, ctx: _Ctx):
    """PTA503: every consumer of this all_gather statically slices a
    single pre-gather chunk back out — chunk 0 on every device."""
    out = eqn.outvars[0]
    dim = int(eqn.params.get("all_gather_dimension", 0))
    size = int(eqn.params.get("axis_size", 0) or 0)
    if size <= 1:
        return
    tiled = bool(eqn.params.get("tiled", False))
    in_aval = getattr(eqn.invars[0], "aval", None)
    if in_aval is None or not getattr(in_aval, "shape", None):
        local = None
    else:
        local = in_aval.shape[dim] if dim < len(in_aval.shape) else None
    consumers = [e for e in jaxpr.eqns
                 if any((not isinstance(v, Literal)) and v is out
                        for v in e.invars)]
    if not consumers:
        return

    def _is_chunk_slice(e):
        if e.primitive.name != "slice":
            return False
        starts = e.params.get("start_indices", ())
        limits = e.params.get("limit_indices", ())
        if dim >= len(starts):
            return False
        span = limits[dim] - starts[dim]
        if tiled:
            return local is not None and span == local
        return span == 1              # one gathered row of the new dim
    if all(_is_chunk_slice(e) for e in consumers):
        ctx.report.add(Diagnostic(
            "PTA503",
            f"{ctx.name}: all_gather result is only consumed by static "
            "slices of one chunk — every replica reads the SAME chunk, "
            "mixing a replicated gather with per-replica intent",
            Severity.WARNING,
            hint="dynamic_slice at axis_index(axis) * shard_len selects "
                 "each replica's own chunk without moving the other "
                 "replicas' data at all"))


def _check_collective(eqn, jaxpr, env, ctx: _Ctx, pred_vary: frozenset):
    pname = eqn.primitive.name
    axes = _collective_axes(eqn)
    groups = eqn.params.get("axis_index_groups")
    unknown = [a for a in axes if a not in ctx.manual]
    if unknown:
        ctx.report.add(Diagnostic(
            "PTA502",
            f"{ctx.name}: collective `{pname}` names axis "
            f"{unknown if len(unknown) > 1 else unknown[0]!r} which is "
            "not a manual axis of the enclosing shard_map region "
            f"(manual: {sorted(ctx.manual) or 'none'})",
            Severity.ERROR,
            hint="add the axis to the mesh/manual set, or move the "
                 "collective inside the shard_map that binds it"))
    hot = pred_vary & set(axes)
    if hot:
        ctx.report.add(Diagnostic(
            "PTA506",
            f"{ctx.name}: collective `{pname}` over {sorted(hot)} inside "
            "a traced conditional whose predicate varies over the same "
            "axis — replicas that take different branches deadlock the "
            "collective on TPU",
            Severity.ERROR,
            hint="hoist the collective out of the cond/while, or make "
                 "the predicate replicated (psum/pmean it) first"))
    if pname in _SUM_PRIMS:
        for v in eqn.invars:
            dt = _np_dtype(getattr(v, "aval", None))
            if dt is None:
                continue
            if dt in (np.dtype(np.int8), np.dtype(np.uint8)):
                ctx.report.add(Diagnostic(
                    "PTA504",
                    f"{ctx.name}: `{pname}` sums an {dt}-encoded "
                    "payload — the sum of quantized encodings is not "
                    "the encoding of the sum (garbage after one hop)",
                    Severity.ERROR,
                    hint="use the wire.py idiom: quantize -> "
                         "all_to_all/all_gather -> dequantize -> local "
                         "sum (parallel/zero.py reduce_scatter leg)"))
            elif dt.name in ("bfloat16", "float16"):
                ctx.report.add(Diagnostic(
                    "PTA504",
                    f"{ctx.name}: `{pname}` reduces a {dt} payload — "
                    "the wire accumulates in half precision, so the "
                    "reduced value loses bits the operands still had",
                    Severity.WARNING,
                    hint="exchange the encoded rows (all_to_all/"
                         "all_gather) and sum after dequantizing to "
                         "f32, or reduce in f32 and cast afterwards"))
    if pname == "psum" and axes and groups is None:
        for v in eqn.invars:
            if isinstance(v, Literal):
                continue
            if _vary(env, v).isdisjoint(axes) and \
                    not _is_mean_psum(eqn, jaxpr, ctx):
                ctx.report.add(Diagnostic(
                    "PTA502",
                    f"{ctx.name}: psum over {list(axes)} of a value "
                    "already replicated on those axes — the second "
                    "reduction multiplies by the axis size",
                    Severity.WARNING,
                    hint="drop the redundant psum (or use pmean if the "
                         "multiply-by-world-size was the bug)"))
                break
    if pname in _GATHER_PRIMS:
        _check_gather_then_slice(eqn, jaxpr, ctx)
    for v in eqn.invars:
        if v in ctx.donated and v not in ctx.flagged_505:
            key, label = ctx.donated[v]
            if key in ctx.out_avals:
                continue              # round-trips to an aliasable output
            ctx.flagged_505.add(v)
            shape, dt = key
            ctx.report.add(Diagnostic(
                "PTA505",
                f"{ctx.name}: donated input `{label}` "
                f"({dt}{list(shape)}) is consumed directly by "
                f"`{pname}` and no output matches its shape/dtype — "
                "XLA cannot reuse donated storage across a collective "
                "boundary, so the donation only deletes the caller's "
                "array",
                Severity.WARNING,
                hint="drop it from donate_argnums, or return an "
                     "updated buffer of the same shape so the alias "
                     "survives"))


def _check_ring_sum(eqn, ctx: _Ctx):
    """PTA504, fused-ring flavor: an ``add`` consuming a ``ppermute``
    result that is still wire-encoded.  The legal hop body decodes the
    received chunk to f32 first (``parallel/ring.py``); adding raw
    encodings accumulates garbage (int8) or half-precision error
    (bf16/f16) on every hop."""
    if id(eqn) in ctx.flagged_ring_sum:
        return                        # scan fixpoint re-walks the body
    for v in eqn.invars:
        if isinstance(v, Literal) or v not in ctx.ppermute_outs:
            continue
        dt = ctx.ppermute_outs[v]
        if dt in (np.dtype(np.int8), np.dtype(np.uint8)):
            ctx.flagged_ring_sum.add(id(eqn))
            ctx.report.add(Diagnostic(
                "PTA504",
                f"{ctx.name}: fused ring sums encoded payloads — "
                f"`add` consumes a {dt} `ppermute` result directly, "
                "so each hop accumulates quantized encodings instead "
                "of values (garbage after one hop)",
                Severity.ERROR,
                hint="dequantize the received chunk to f32, add the "
                     "local block at full precision, and re-encode "
                     "for the next hop (parallel/ring.py hop body)"))
            return
        if dt is not None and dt.name in ("bfloat16", "float16"):
            ctx.flagged_ring_sum.add(id(eqn))
            ctx.report.add(Diagnostic(
                "PTA504",
                f"{ctx.name}: fused ring accumulates in {dt} — `add` "
                "consumes a ppermute result without widening, so the "
                "partial sum loses bits on every hop",
                Severity.WARNING,
                hint="accumulate the ring carry in f32 and cast back "
                     "to the wire dtype only for the next ppermute"))
            return


def _is_full_cycle(perm, n: int) -> bool:
    """True iff ``perm`` is a permutation of ``range(n)`` forming one
    cycle that visits every member — the neighbor rotation every ring
    hop reuses."""
    try:
        step = {int(s): int(d) for s, d in (perm or ())}
    except (TypeError, ValueError):
        return False
    if len(step) != n or set(step) != set(range(n)) \
            or set(step.values()) != set(range(n)):
        return False
    cur = 0
    for hops in range(1, n + 1):
        cur = step[cur]
        if cur == 0:
            return hops == n
    return False


def _scan_ring_axes(eqn, body, ctx: _Ctx) -> frozenset:
    """Axes over which this scan is a *complete ring*: the body
    ``ppermute``s over axis A with a single full cycle of size
    ``n = |A|`` and the scan runs ``n`` or ``n-1`` trips — by the last
    trip every replica has seen every replica's chunk, so an assembled
    buffer (leading dim ``n``) no longer varies over A."""
    length = eqn.params.get("length")
    if length is None or not hasattr(body, "eqns"):
        return _EMPTY
    out = set()
    for beqn in body.eqns:
        if beqn.primitive.name != "ppermute":
            continue
        for a in _collective_axes(beqn):
            n = int(ctx.sizes.get(a, 0) or 0)
            if n >= 2 and int(length) in (n, n - 1) \
                    and _is_full_cycle(beqn.params.get("perm"), n):
                out.add(a)
    return frozenset(out)


def _call_body(eqn):
    for k in _CALL_KEYS:
        v = eqn.params.get(k)
        if v is not None:
            return getattr(v, "jaxpr", v)
    return None


def _bind(env, ctx, outer_vars, inner_vars):
    """Map call-like eqn invars onto body invars.  Aligned from the END
    when lengths differ (leading const conventions); unmatched body
    invars conservatively inherit the union of every operand."""
    n_in, n_body = len(outer_vars), len(inner_vars)
    union = _EMPTY
    for v in outer_vars:
        union |= _vary(env, v)
    off = n_body - n_in
    for j, bv in enumerate(inner_vars):
        i = j - off
        if 0 <= i < n_in:
            ov = outer_vars[i]
            env[bv] = _vary(env, ov)
            if not isinstance(ov, Literal) and ov in ctx.donated:
                ctx.donated[bv] = ctx.donated[ov]
        else:
            env[bv] = union


def _walk(jaxpr, env, ctx: _Ctx, pred_vary: frozenset):
    """One pass over ``jaxpr``'s eqns, propagating varying sets and
    emitting diagnostics.  Recurses into every nested region."""
    for eqn in jaxpr.eqns:
        pname = eqn.primitive.name
        union = _EMPTY
        for v in eqn.invars:
            union |= _vary(env, v)
        if pname in COLLECTIVE_PRIMS:
            _check_collective(eqn, jaxpr, env, ctx, pred_vary)
            axes = frozenset(_collective_axes(eqn))
            if eqn.params.get("axis_index_groups") is not None:
                out = union               # group reduces stay conservative
            elif pname in _REDUCE_PRIMS or pname in _GATHER_PRIMS:
                out = union - axes
            else:
                out = union
            for o in eqn.outvars:
                env[o] = out
                if pname == "ppermute":
                    ctx.ppermute_outs[o] = _np_dtype(
                        getattr(o, "aval", None))
            continue
        if pname in ("add", "add_any"):
            _check_ring_sum(eqn, ctx)
        if pname == "axis_index":
            ax = eqn.params.get("axis_name")
            axset = frozenset(a for a in (
                ax if isinstance(ax, (tuple, list)) else (ax,))
                if isinstance(a, str))
            for o in eqn.outvars:
                env[o] = axset
            continue
        if pname == "shard_map":
            _walk_shard_map(eqn, env, ctx)
            continue
        if pname == "cond":
            _walk_cond(eqn, env, ctx, pred_vary)
            continue
        if pname == "while":
            _walk_while(eqn, env, ctx, pred_vary)
            continue
        if pname == "scan":
            _walk_scan(eqn, env, ctx, pred_vary)
            continue
        body = _call_body(eqn)
        if body is not None:
            _bind(env, ctx, list(eqn.invars), list(body.invars))
            _walk(body, env, ctx, pred_vary)
            bouts = list(body.outvars)
            for i, o in enumerate(eqn.outvars):
                env[o] = _vary(env, bouts[i]) if i < len(bouts) else union
            continue
        for o in eqn.outvars:
            env[o] = union


def _walk_shard_map(eqn, env, ctx: _Ctx):
    p = eqn.params
    mesh = p.get("mesh")
    manual = frozenset(p.get("manual_axes") or ())
    body = getattr(p.get("jaxpr"), "jaxpr", p.get("jaxpr"))
    if body is None or not hasattr(body, "eqns"):
        return
    in_specs = p.get("in_specs") or ()
    out_specs = p.get("out_specs") or ()
    for i, bv in enumerate(body.invars):
        env[bv] = _spec_axes(in_specs[i] if i < len(in_specs)
                             else None) & manual
        ov = eqn.invars[i] if i < len(eqn.invars) else None
        if ov is not None and not isinstance(ov, Literal) \
                and ov in ctx.donated:
            ctx.donated[bv] = ctx.donated[ov]
    saved = (ctx.manual, ctx.sizes, ctx.seen_manual)
    ctx.manual = manual
    try:
        shp = dict(getattr(mesh, "shape", {}) or {})
    except TypeError:
        shp = {}
    ctx.sizes = {a: int(s) for a, s in shp.items()}
    ctx.seen_manual = True
    try:
        _walk(body, env, ctx, _EMPTY)
        for j, bov in enumerate(body.outvars):
            claimed = _spec_axes(out_specs[j] if j < len(out_specs)
                                 else None)
            leak = _vary(env, bov) - claimed
            if leak:
                outer = eqn.outvars[j] if j < len(eqn.outvars) else None
                label = ctx.out_labels.get(outer, f"output[{j}]")
                ctx.report.add(Diagnostic(
                    "PTA501",
                    f"{ctx.name}: shard_map output `{label}` is claimed "
                    f"replicated over {sorted(leak)} but still varies "
                    "there — no psum/psum_scatter/all_gather reduced it, "
                    "so replicas silently diverge (each applies its own "
                    "local value)",
                    Severity.ERROR,
                    hint="psum (grads), pmean (buffers/loss) or "
                         "all_gather (updated shards) the value on "
                         f"{sorted(leak)}, or declare the output sharded "
                         "over that axis in out_specs"))
    finally:
        ctx.manual, ctx.sizes, ctx.seen_manual = saved
    for o in eqn.outvars:
        env[o] = _EMPTY               # global view outside the region


def _walk_cond(eqn, env, ctx: _Ctx, pred_vary: frozenset):
    pred = eqn.invars[0]
    ops = list(eqn.invars[1:])
    inner_pred = pred_vary | _vary(env, pred)
    branches = eqn.params.get("branches") or ()
    out_sets = [_EMPTY] * len(eqn.outvars)
    for br in branches:
        body = getattr(br, "jaxpr", br)
        _bind(env, ctx, ops, list(body.invars))
        _walk(body, env, ctx, inner_pred)
        for i in range(len(eqn.outvars)):
            if i < len(body.outvars):
                out_sets[i] = out_sets[i] | _vary(env, body.outvars[i])
    for i, o in enumerate(eqn.outvars):
        env[o] = out_sets[i] | _vary(env, pred)


def _walk_while(eqn, env, ctx: _Ctx, pred_vary: frozenset):
    p = eqn.params
    cond_j = getattr(p.get("cond_jaxpr"), "jaxpr", p.get("cond_jaxpr"))
    body_j = getattr(p.get("body_jaxpr"), "jaxpr", p.get("body_jaxpr"))
    cn = int(p.get("cond_nconsts", 0))
    bn = int(p.get("body_nconsts", 0))
    cond_consts = list(eqn.invars[:cn])
    body_consts = list(eqn.invars[cn:cn + bn])
    carry = list(eqn.invars[cn + bn:])
    carry_vary = [_vary(env, v) for v in carry]
    inner_pred = pred_vary
    for _ in range(8):                   # fixpoint over the carry lattice
        if cond_j is not None:
            _bind(env, ctx, cond_consts + carry, list(cond_j.invars))
            for i, bv in enumerate(cond_j.invars[len(cond_consts):]):
                env[bv] = carry_vary[i] if i < len(carry_vary) else _EMPTY
            _walk(cond_j, env, ctx, inner_pred)
            pv = _EMPTY
            for ov in cond_j.outvars:
                pv |= _vary(env, ov)
            inner_pred = pred_vary | pv
        if body_j is None:
            break
        _bind(env, ctx, body_consts + carry, list(body_j.invars))
        for i, bv in enumerate(body_j.invars[len(body_consts):]):
            env[bv] = carry_vary[i] if i < len(carry_vary) else _EMPTY
        _walk(body_j, env, ctx, inner_pred)
        new = [_vary(env, ov) if i < len(body_j.outvars) else _EMPTY
               for i, ov in enumerate(body_j.outvars)]
        new = [carry_vary[i] | (new[i] if i < len(new) else _EMPTY)
               for i in range(len(carry_vary))]
        if new == carry_vary:
            break
        carry_vary = new
    for i, o in enumerate(eqn.outvars):
        env[o] = (carry_vary[i] if i < len(carry_vary) else _EMPTY) \
            | inner_pred


def _walk_scan(eqn, env, ctx: _Ctx, pred_vary: frozenset):
    p = eqn.params
    body = getattr(p.get("jaxpr"), "jaxpr", p.get("jaxpr"))
    if body is None:
        return
    nc = int(p.get("num_consts", 0))
    ncar = int(p.get("num_carry", 0))
    consts = list(eqn.invars[:nc])
    carry = list(eqn.invars[nc:nc + ncar])
    xs = list(eqn.invars[nc + ncar:])
    carry_vary = [_vary(env, v) for v in carry]
    for _ in range(8):                   # fixpoint: trip-uniform schedule
        _bind(env, ctx, consts + carry + xs, list(body.invars))
        for i in range(ncar):
            j = nc + i
            if j < len(body.invars):
                env[body.invars[j]] = carry_vary[i]
        _walk(body, env, ctx, pred_vary)
        new = [_vary(env, body.outvars[i]) if i < len(body.outvars)
               else _EMPTY for i in range(ncar)]
        new = [carry_vary[i] | new[i] for i in range(ncar)]
        if new == carry_vary:
            break
        carry_vary = new
    for i, o in enumerate(eqn.outvars):
        if i < ncar:
            env[o] = carry_vary[i]
        else:
            j = i
            env[o] = _vary(env, body.outvars[j]) \
                if j < len(body.outvars) else _EMPTY
    ring_axes = _scan_ring_axes(eqn, body, ctx)
    if ring_axes:
        # complete-ring gather: outputs holding one slot per replica
        # (leading dim == axis size) have been filled from every seat
        for o in eqn.outvars:
            shape = tuple(getattr(getattr(o, "aval", None),
                                  "shape", ()) or ())
            if not shape:
                continue
            done = frozenset(a for a in ring_axes
                             if int(ctx.sizes.get(a, 0)) == shape[0])
            if done:
                env[o] = _vary(env, o) - done


def run_collective_passes(closed_jaxpr, name: str, report: Report,
                          donate_argnums: Optional[Sequence[int]] = None,
                          invar_labels: Optional[Sequence[str]] = None,
                          outvar_labels: Optional[Sequence[str]] = None):
    """Run the PTA5xx family over a ``jax.make_jaxpr`` result, appending
    findings to ``report``.  A program with no shard_map region and no
    collective eqns produces no diagnostics — the passes are free for
    ordinary jit programs, which is what lets ``analyze_jaxpr`` run them
    unconditionally."""
    jaxpr = closed_jaxpr.jaxpr
    ctx = _Ctx(report, name)
    if donate_argnums:
        for i in donate_argnums:
            if i < len(jaxpr.invars):
                v = jaxpr.invars[i]
                label = invar_labels[i] if invar_labels and \
                    i < len(invar_labels) else f"input[{i}]"
                ctx.donated[v] = (_aval_key(getattr(v, "aval", None)),
                                  label)
    ctx.out_avals = [_aval_key(getattr(o, "aval", None))
                     for o in jaxpr.outvars
                     if not isinstance(o, Literal)]
    if outvar_labels:
        for o, lbl in zip(jaxpr.outvars, outvar_labels):
            if not isinstance(o, Literal):
                ctx.out_labels[o] = lbl
    env: Dict[object, frozenset] = {}
    _walk(jaxpr, env, ctx, _EMPTY)
    return report


def analyze_collectives(closed_jaxpr, name: str = "<traced>",
                        donate_argnums: Optional[Sequence[int]] = None,
                        invar_labels: Optional[Sequence[str]] = None,
                        outvar_labels: Optional[Sequence[str]] = None,
                        disable: Sequence[str] = ()) -> Report:
    """Standalone entry: just the distributed-semantics passes over a
    traced program (the full stack lives in ``analyze_jaxpr``)."""
    report = Report()
    run_collective_passes(closed_jaxpr, name, report,
                          donate_argnums=donate_argnums,
                          invar_labels=invar_labels,
                          outvar_labels=outvar_labels)
    return report.filter(disable=disable)

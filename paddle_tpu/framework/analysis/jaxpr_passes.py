"""Jaxpr-level IR passes — the post-trace half of the program analyzer.

The reference validates and rewrites ProgramDescs through graph passes
(paddle/fluid/framework/ir + inference/analysis) before the executor
runs them; here the traced IR is a jaxpr, so the passes run over
``jax.make_jaxpr`` output instead of an SSA graph of OpDescs.  Each pass
reads the closed jaxpr (plus trace metadata: input labels, donation) and
emits :class:`~.diagnostics.Diagnostic` records; nothing is rewritten —
XLA owns optimization, the analyzer owns *explaining the trace to the
human* before a TPU hour is spent on it.

Shipped passes (stable IDs, see diagnostics.RULES):

========  ==============================================================
PTA101    silent dtype upcasts: mixed-width float promotion inside an
          eqn, and any f64/c128 value appearing in the program
PTA102    dead equations and unused inputs (params that never reach an
          output — the trace equivalent of unused-var warnings)
PTA103    host callbacks / syncs inside the traced program
          (debug_callback, io_callback, pure_callback)
PTA104    donated-buffer misuse: a donated input whose shape/dtype
          matches no output can never be reused (XLA warns at runtime;
          this catches it pre-dispatch), and large aliasable
          inputs that are NOT donated
PTA105    dispatch-cache defeaters baked in as constants: large arrays
          closed over instead of passed in, frozen rng keys, weak-typed
          scalar closures that retrace on every new Python value
PTA106    per-eqn FLOP/byte estimates with a top-k heaviest-ops report
========  ==============================================================

The distributed-semantics family (PTA501-506, collectives.py) runs as
part of :func:`analyze_jaxpr` too — free on ordinary jit programs, and
the cost pass is shard_map-aware: inside a manual region shapes are
already per-device, higher-order wrapper eqns (pjit/shard_map) are not
double-counted, and collective eqns are tagged with ANALYTIC wire bytes
(``distributed/wire.py::wire_nbytes`` on the payload encoding, scaled
by the ring/gather traffic factor for the mesh axis size) instead of
host-memory-moved estimates — so ``perf_report attribute`` stops
over-counting sharded programs.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
from jax.extend.core import Literal

from paddle_tpu.framework.analysis.collectives import (
    COLLECTIVE_PRIMS, run_collective_passes)
from paddle_tpu.framework.analysis.diagnostics import (
    Diagnostic, Report, Severity, register_rule)

__all__ = ["analyze_jaxpr", "analyze_callable", "analyze_model",
           "iter_eqns", "eqn_cost"]

register_rule("PTA101", "silent dtype upcast", Severity.WARNING, "jaxpr")
register_rule("PTA102", "dead equation / unused input", Severity.WARNING,
              "jaxpr")
register_rule("PTA103", "host callback inside jit", Severity.WARNING,
              "jaxpr")
register_rule("PTA104", "donated-buffer misuse", Severity.WARNING, "jaxpr")
register_rule("PTA105", "dispatch-cache defeating constant",
              Severity.WARNING, "jaxpr")
register_rule("PTA106", "op cost report", Severity.INFO, "jaxpr")

# consts at or above this many elements should be inputs, not closures
_LARGE_CONST_ELEMS = 4096
# un-donated aliasable inputs at or above this many bytes get the
# donation hint (below it the saved HBM is noise)
_DONATION_HINT_BYTES = 1 << 20

_CALLBACK_PRIMS = {"debug_callback", "debug_print", "io_callback",
                   "pure_callback"}

# eqn.params values holding nested jaxprs, by primitive
_SUBJAXPR_KEYS = ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr",
                  "branches", "fun_jaxpr")


def _float_width(dt) -> Optional[int]:
    try:
        dt = np.dtype(dt)
    except TypeError:                  # extended dtypes (prng keys) / tokens
        return None
    if dt.kind in ("f", "c"):
        return dt.itemsize
    return None


def _np_dtype(aval):
    try:
        return np.dtype(getattr(aval, "dtype", None))
    except TypeError:
        return None


def _aval(v):
    import jax
    if hasattr(v, "aval"):
        return v.aval
    return jax.typeof(v.val if hasattr(v, "val") else v)


def _nbytes(aval) -> int:
    try:
        return int(np.prod(aval.shape, dtype=np.int64)) * \
            np.dtype(aval.dtype).itemsize
    except Exception:                  # noqa: BLE001 — abstract tokens etc.
        return 0


def _subjaxprs(eqn):
    """Nested jaxprs of a higher-order eqn (pjit, scan, while, cond,
    custom_*), normalized to plain Jaxpr objects."""
    out = []
    for k in _SUBJAXPR_KEYS:
        v = eqn.params.get(k)
        if v is None:
            continue
        vs = v if isinstance(v, (list, tuple)) else [v]
        for j in vs:
            j = getattr(j, "jaxpr", j)     # ClosedJaxpr -> Jaxpr
            if hasattr(j, "eqns"):
                out.append(j)
    return out


def iter_eqns(jaxpr, depth: int = 0):
    """Yield ``(eqn, depth)`` over the jaxpr and every nested sub-jaxpr
    (scan/while/cond bodies, pjit-inlined calls)."""
    for eqn in jaxpr.eqns:
        yield eqn, depth
        for sub in _subjaxprs(eqn):
            yield from iter_eqns(sub, depth + 1)


# ---------------------------------------------------------------------------
# cost model (PTA106)
# ---------------------------------------------------------------------------


def eqn_cost(eqn) -> Tuple[int, int]:
    """(flops, bytes) estimate for one eqn.  Deliberately coarse — the
    point is ranking ops inside one program, not absolute roofline math
    (compare the reference's per-op benchmark configs, which measure
    instead of estimating)."""
    name = eqn.primitive.name
    out_elems = sum(int(np.prod(_aval(o).shape, dtype=np.int64))
                    for o in eqn.outvars)
    moved = sum(_nbytes(_aval(v)) for v in
                list(eqn.invars) + list(eqn.outvars))
    if name == "dot_general":
        dn = eqn.params["dimension_numbers"]
        (lhs_c, _), _ = dn
        lhs = _aval(eqn.invars[0]).shape
        k = int(np.prod([lhs[i] for i in lhs_c], dtype=np.int64)) or 1
        return 2 * out_elems * k, moved
    if name == "conv_general_dilated":
        rhs = _aval(eqn.invars[1]).shape
        dn = eqn.params.get("dimension_numbers")
        spatial_and_in = [d for i, d in enumerate(rhs)
                          if dn is None or i != dn.rhs_spec[0]]
        per_out = int(np.prod(spatial_and_in, dtype=np.int64)) or 1
        feature_group = int(eqn.params.get("feature_group_count", 1)) or 1
        return 2 * out_elems * per_out // feature_group, moved
    if name.startswith("reduce_") or name in ("argmax", "argmin"):
        in_elems = sum(int(np.prod(_aval(v).shape, dtype=np.int64))
                       for v in eqn.invars)
        return in_elems, moved
    return out_elems, moved


# ---------------------------------------------------------------------------
# the passes
# ---------------------------------------------------------------------------


def _pass_dtype(jaxpr, consts, name, report: Report):
    x64 = {np.dtype(d) for d in ("float64", "complex128")}
    for c in consts:
        dt = _np_dtype(c)
        if dt in x64:
            report.add(Diagnostic(
                "PTA101", f"{name}: float64 constant closed over the "
                f"traced program (shape {tuple(np.shape(c))}) — on TPU "
                "this silently widens every consumer and falls off the "
                "fast path", Severity.ERROR,
                hint="build the constant with an explicit float32/"
                     "bfloat16 dtype, or disable jax_enable_x64"))
    in_f64 = any(_float_width(_aval(v).dtype) == 8
                 for v in jaxpr.invars
                 if _float_width(_aval(v).dtype) is not None)
    for eqn, depth in iter_eqns(jaxpr):
        widths = {}
        for v in eqn.invars:
            w = _float_width(_aval(v).dtype)
            if w is not None:
                widths.setdefault(w, str(np.dtype(_aval(v).dtype)))
        out_w = [(_float_width(_aval(o).dtype), _aval(o).dtype)
                 for o in eqn.outvars]
        if len(widths) > 1 and eqn.primitive.name != \
                "convert_element_type":
            widest = max(widths)
            if any(w == widest for w, _ in out_w if w is not None):
                report.add(Diagnostic(
                    "PTA101",
                    f"{name}: {eqn.primitive.name} mixes float widths "
                    f"({', '.join(sorted(widths.values()))}) — the "
                    f"result is silently promoted to {widths[widest]}",
                    Severity.WARNING,
                    hint="cast the narrow operand explicitly, or keep "
                         "both sides in the compute dtype (bf16 under "
                         "amp) so the MXU path is not lost"))
        if not in_f64:
            for w, dt in out_w:
                if w == 8:
                    report.add(Diagnostic(
                        "PTA101",
                        f"{name}: {eqn.primitive.name} produces "
                        f"{np.dtype(dt)} with no float64 program input "
                        "— an accidental x64 upcast",
                        Severity.ERROR,
                        hint="trace the source constant/op and pin its "
                             "dtype to float32"))
                    break


def _pass_dead_code(jaxpr, name, invar_labels, report: Report):
    live = {v for v in jaxpr.outvars
            if not isinstance(v, Literal)}
    for eqn in reversed(jaxpr.eqns):
        out_live = any(o in live for o in eqn.outvars)
        if out_live or eqn.effects:
            for v in eqn.invars:
                if not isinstance(v, Literal):
                    live.add(v)
        elif eqn.primitive.name in ("broadcast_in_dim", "iota") and \
                all(isinstance(v, Literal) for v in eqn.invars):
            # a dead LITERAL materialization is free: jax's own vjp
            # rules leave these behind (e.g. relu's custom_jvp zeros)
            # and XLA constant-folds them — flagging would teach users
            # to ignore PTA102
            continue
        else:
            report.add(Diagnostic(
                "PTA102",
                f"{name}: dead equation `{eqn.primitive.name}` — its "
                "outputs are never used by any program output",
                Severity.WARNING,
                hint="drop the computation, or return its result; XLA "
                     "DCEs it, but the trace (and every retrace) still "
                     "pays for it"))
    for i, v in enumerate(jaxpr.invars):
        if v not in live:
            label = invar_labels[i] if invar_labels and \
                i < len(invar_labels) else f"input[{i}]"
            if label == "rng_key":
                # the capture protocol threads a key into every trace;
                # an eval-mode model legitimately ignores it
                continue
            report.add(Diagnostic(
                "PTA102",
                f"{name}: input `{label}` never reaches any output "
                "(dead parameter)",
                Severity.WARNING,
                hint="remove the input, or check the forward actually "
                     "uses the layer it belongs to"))


def _pass_callbacks(jaxpr, name, report: Report):
    for eqn, depth in iter_eqns(jaxpr):
        pname = eqn.primitive.name
        if pname in _CALLBACK_PRIMS or "callback" in pname:
            cb = eqn.params.get("callback")
            what = getattr(cb, "__name__", None) or pname
            report.add(Diagnostic(
                "PTA103",
                f"{name}: host callback `{pname}` ({what}) inside the "
                "traced program — every execution round-trips to the "
                "host, serializing the device stream",
                Severity.WARNING,
                hint="strip jax.debug.print/io_callback from production "
                     "traces, or gate them behind a debug flag"))


def _pass_donation(jaxpr, name, donate_argnums, invar_labels,
                   report: Report):
    out_avals = [(tuple(getattr(_aval(o), "shape", ())), _np_dtype(_aval(o)))
                 for o in jaxpr.outvars if _np_dtype(_aval(o)) is not None]
    pool = list(out_avals)
    donated = set(donate_argnums or ())
    for i in sorted(donated):
        if i >= len(jaxpr.invars):
            continue
        a = _aval(jaxpr.invars[i])
        if _np_dtype(a) is None:
            continue
        key = (tuple(a.shape), _np_dtype(a))
        label = invar_labels[i] if invar_labels and \
            i < len(invar_labels) else f"input[{i}]"
        if key in pool:
            pool.remove(key)          # each output aliases one buffer
        else:
            report.add(Diagnostic(
                "PTA104",
                f"{name}: donated input `{label}` "
                f"{key[1]}{list(key[0])} matches no output — the "
                "buffer is freed but never reused, and any later use "
                "of the live Tensor hits a deleted array",
                Severity.WARNING,
                hint="donate only buffers the step returns updated "
                     "(params/opt states), or drop it from "
                     "donate_argnums"))
    if donate_argnums is not None:
        pool = list(out_avals)
        for i, v in enumerate(jaxpr.invars):
            if i in donated:
                continue
            a = _aval(v)
            if _np_dtype(a) is None:
                continue
            key = (tuple(a.shape), _np_dtype(a))
            if key in pool and _nbytes(a) >= _DONATION_HINT_BYTES:
                pool.remove(key)
                label = invar_labels[i] if invar_labels and \
                    i < len(invar_labels) else f"input[{i}]"
                report.add(Diagnostic(
                    "PTA104",
                    f"{name}: input `{label}` ({_nbytes(a) >> 20} MiB) "
                    "shape-matches an output but is not donated — HBM "
                    "holds two live copies across the step",
                    Severity.INFO,
                    hint="add it to donate_argnums if the caller never "
                         "reads the pre-step value"))


def _pass_consts(jaxpr, consts, name, report: Report):
    import jax
    for c in consts:
        arr = np.asarray(c) if not hasattr(c, "dtype") else c
        shape = tuple(getattr(arr, "shape", ()))
        elems = int(np.prod(shape, dtype=np.int64)) if shape else 1
        dt = _np_dtype(arr)
        if dt is None:                 # prng key const: frozen randomness
            report.add(Diagnostic(
                "PTA105",
                f"{name}: typed rng key baked in as a constant — every "
                "call replays identical randomness",
                Severity.WARNING,
                hint="take the key as an argument (see "
                     "jit._GeneratorKeyGuard: keys are traced inputs)"))
            continue
        if elems >= _LARGE_CONST_ELEMS:
            kib = elems * dt.itemsize >> 10
            report.add(Diagnostic(
                "PTA105",
                f"{name}: large constant ({dt}{list(shape)}, {kib} KiB) "
                "baked into the traced program — it is re-hashed on "
                "every dispatch-cache probe and re-staged per "
                "executable", Severity.WARNING,
                hint="pass it as an argument (params/buffers thread "
                     "through capture) instead of closing over it"))
            continue
        if dt == np.uint32 and shape and shape[-1] == 2:
            report.add(Diagnostic(
                "PTA105",
                f"{name}: rng key baked in as a constant — every call "
                "replays identical randomness, and threading a fresh "
                "key instead forces a retrace per step",
                Severity.WARNING,
                hint="take the key as an argument (see "
                     "jit._GeneratorKeyGuard: keys are traced inputs)"))
            continue
        try:
            weak = jax.typeof(c).weak_type
        except Exception:              # noqa: BLE001
            weak = False
        if weak and elems == 1:
            report.add(Diagnostic(
                "PTA105",
                f"{name}: weak-typed Python scalar ({dt}) closed over "
                "the trace — each distinct value is a fresh cache "
                "entry (silent recompilation)",
                Severity.WARNING,
                hint="pass it as a jnp array argument, or mark it "
                     "static if it is genuinely a config constant"))


# ring/gather traffic per replica as a multiple of the local payload
# bytes, by collective family (k = mesh axis size): a psum is a ring
# all-reduce (2(k-1)/k), all_gather pulls every peer's shard (k-1),
# reduce-scatter/all-to-all move (k-1)/k, ppermute one full payload
def _collective_traffic_factor(pname: str, k: int) -> float:
    if k <= 1:
        return 0.0
    if pname in ("psum", "pmax", "pmin"):
        return 2.0 * (k - 1) / k
    if pname == "all_gather":
        return float(k - 1)
    if pname in ("psum_scatter", "reduce_scatter", "all_to_all"):
        return (k - 1) / k
    return 1.0                        # ppermute / pbroadcast


_WIRE_OF_DTYPE = {"float32": "f32", "bfloat16": "bf16",
                  "float16": "f16", "int8": "int8", "uint8": "int8"}


def _collective_wire_bytes(eqn, axis_sizes: Dict[str, int]) -> int:
    """Analytic per-replica wire bytes of one collective eqn — the
    payload encoded per ``distributed/wire.py::wire_nbytes``, scaled by
    the traffic factor for the collective family and axis size."""
    from paddle_tpu.distributed.wire import wire_nbytes
    from paddle_tpu.framework.analysis.collectives import _collective_axes
    k = 1
    for a in _collective_axes(eqn):
        k *= int(axis_sizes.get(a, 1) or 1)
    factor = _collective_traffic_factor(eqn.primitive.name, k)
    total = 0.0
    for v in eqn.invars:
        aval = _aval(v)
        dt = _np_dtype(aval)
        if dt is None:
            continue
        elems = int(np.prod(getattr(aval, "shape", ()), dtype=np.int64))
        wire = _WIRE_OF_DTYPE.get(dt.name)
        if wire is None:              # wider ints/floats account as f32
            total += float(elems * dt.itemsize) * factor
        else:
            total += float(wire_nbytes(elems, wire)) * factor
    return int(total)


def _pass_cost(jaxpr, name, top_k, report: Report):
    rows: List[Tuple[int, int, str]] = []
    total_f = total_b = coll_b = 0
    by_op: dict = {}
    state = {"manual": False}

    def note(pname, f, b, n=1):
        nonlocal total_f, total_b
        total_f += f
        total_b += b
        rows.append((f, b, pname))
        agg = by_op.setdefault(pname, [0, 0, 0])
        agg[0] += f
        agg[1] += b
        agg[2] += n

    def walk(jx, axis_sizes, trips=1):
        nonlocal coll_b
        for eqn in jx.eqns:
            pname = eqn.primitive.name
            subs = _subjaxprs(eqn)
            if pname == "shard_map":
                # manual region: body shapes are already PER-DEVICE —
                # count only the body, under the region's mesh sizes
                state["manual"] = True
                mesh = eqn.params.get("mesh")
                try:
                    sizes = {a: int(s) for a, s in
                             dict(getattr(mesh, "shape", {})).items()}
                except TypeError:
                    sizes = axis_sizes
                for sub in subs:
                    walk(sub, sizes, trips)
                continue
            if pname in COLLECTIVE_PRIMS:
                b = _collective_wire_bytes(eqn, axis_sizes) * trips
                coll_b += b
                note(pname, 0, b, trips)
                continue
            if subs:
                # higher-order wrapper (pjit/scan/cond/custom_*): its
                # cost IS its bodies' — counting the wrapper's global
                # outputs too is exactly the sharded-program over-count.
                # A scan body runs `length` times, so its costs (and
                # the ring collectives inside it) multiply by the trip
                # count — the fused-ring wire bytes would otherwise
                # read as one hop
                t = trips * max(1, int(eqn.params.get("length", 1) or 1)) \
                    if pname == "scan" else trips
                for sub in subs:
                    walk(sub, axis_sizes, t)
                continue
            f, b = eqn_cost(eqn)
            note(pname, f * trips, b * trips, trips)

    walk(jaxpr, {})
    # structured twin of the PTA106 diagnostics: per-primitive
    # aggregates the span<->cost join (tools/perf_report.py attribute)
    # consumes without parsing message strings.  per_device=True marks
    # totals counted inside manual regions (shard-local shapes);
    # collective rows carry analytic wire bytes, not FLOPs
    report.cost = {
        "name": name,
        "total_flops": int(total_f),
        "total_bytes": int(total_b),
        "n_eqns": len(rows),
        "per_device": bool(state["manual"]),
        "collective_wire_bytes": int(coll_b),
        "by_op": [{"op": op, "flops": int(f), "bytes": int(b),
                   "count": int(c)}
                  for op, (f, b, c) in sorted(
                      by_op.items(), key=lambda kv: -kv[1][0])],
    }
    rows.sort(key=lambda r: -r[0])
    for rank, (f, b, pname) in enumerate(rows[:top_k], start=1):
        if f == 0:
            break
        share = f / total_f if total_f else 0.0
        report.add(Diagnostic(
            "PTA106",
            f"{name}: #{rank} heaviest op `{pname}` ≈ {f:,} flops "
            f"({share:.0%} of program), {b >> 10} KiB moved",
            Severity.INFO))
    report.add(Diagnostic(
        "PTA106",
        f"{name}: program total ≈ {total_f:,} flops, "
        f"{total_b >> 20} MiB moved across {len(rows)} eqns "
        f"(arithmetic intensity {total_f / total_b if total_b else 0:.1f} "
        "flop/byte)", Severity.INFO))


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def analyze_jaxpr(closed_jaxpr, name: str = "<traced>",
                  donate_argnums: Optional[Sequence[int]] = None,
                  invar_labels: Optional[Sequence[str]] = None,
                  outvar_labels: Optional[Sequence[str]] = None,
                  top_k: int = 5, disable: Sequence[str] = (),
                  with_cost: bool = True) -> Report:
    """Run every jaxpr pass over a ``jax.make_jaxpr`` result —
    the PTA1xx family plus the distributed-semantics PTA5xx passes
    (collectives.py; no-ops on programs without shard_map regions).
    ``outvar_labels`` name the program outputs so a PTA501 finding can
    say WHICH leaf escapes unreduced."""
    jaxpr = closed_jaxpr.jaxpr
    consts = list(closed_jaxpr.consts)
    report = Report()
    _pass_dtype(jaxpr, consts, name, report)
    _pass_dead_code(jaxpr, name, invar_labels, report)
    _pass_callbacks(jaxpr, name, report)
    _pass_donation(jaxpr, name, donate_argnums, invar_labels, report)
    _pass_consts(jaxpr, consts, name, report)
    run_collective_passes(closed_jaxpr, name, report,
                          donate_argnums=donate_argnums,
                          invar_labels=invar_labels,
                          outvar_labels=outvar_labels)
    if with_cost:
        _pass_cost(jaxpr, name, top_k, report)
    return report.filter(disable=disable)


def _to_aval(x):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core import Tensor
    if isinstance(x, jax.ShapeDtypeStruct):
        return x
    if isinstance(x, Tensor):
        return jax.ShapeDtypeStruct(tuple(x.shape), jnp.dtype(x.dtype))
    arr = jnp.asarray(x)
    return jax.ShapeDtypeStruct(arr.shape, arr.dtype)


def analyze_callable(fn: Callable, *example_args,
                     donate_argnums: Sequence[int] = (),
                     tensors: bool = False, name: Optional[str] = None,
                     **analyze_kwargs) -> Report:
    """Trace ``fn`` on aval stand-ins of ``example_args`` and analyze the
    jaxpr.  ``tensors=True`` wraps array arguments in paddle Tensors
    before the call (for paddle-level functions); plain jax functions
    take arrays directly.  Tracing is abstract — no FLOP is spent."""
    import jax
    from paddle_tpu.core import Tensor
    avals = [_to_aval(a) for a in example_args]
    if tensors:
        def wrapped(*arrs):
            out = fn(*[Tensor(a) for a in arrs])
            leaves = jax.tree_util.tree_leaves(
                out, is_leaf=lambda x: isinstance(x, Tensor))
            return tuple(o._data if isinstance(o, Tensor) else o
                         for o in leaves)
        target = wrapped
    else:
        target = fn
    closed = jax.make_jaxpr(target)(*avals)
    return analyze_jaxpr(
        closed, name=name or getattr(fn, "__name__", "<callable>"),
        donate_argnums=donate_argnums, **analyze_kwargs)


def analyze_model(model, *example_inputs, name: Optional[str] = None,
                  **analyze_kwargs) -> Report:
    """Trace a Layer's forward the way jit.to_static captures it —
    params and buffers threaded as labeled inputs (so PTA102 names a
    dead parameter and PTA105 does not misread weights as baked
    constants) — then run the jaxpr passes."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core import Tensor, no_grad
    from paddle_tpu.jit import _GeneratorKeyGuard
    named_params = [(n, p) for n, p in model.named_parameters()]
    named_buffers = [(n, b) for n, b in model.named_buffers()
                     if b is not None]
    n_p, n_b = len(named_params), len(named_buffers)
    # a to_static-wrapped Layer carries a StaticFunction as .forward —
    # trace its underlying function so the analysis sees flat equations
    # instead of one opaque pjit call
    forward = model.forward
    forward = getattr(forward, "_function", forward)

    def pure(key, *flat):
        params = dict((named_params[i][0], flat[i]) for i in range(n_p))
        buffers = dict((named_buffers[i][0], flat[n_p + i])
                       for i in range(n_b))
        inputs = flat[n_p + n_b:]
        with _GeneratorKeyGuard(key):
            with model._swapped_state(params, buffers):
                with no_grad():
                    out = forward(*[Tensor(a) for a in inputs])
        leaves = jax.tree_util.tree_leaves(
            out, is_leaf=lambda x: isinstance(x, Tensor))
        return tuple(o._data if isinstance(o, Tensor) else o
                     for o in leaves)

    key_aval = jax.ShapeDtypeStruct((2,), jnp.uint32)
    avals = ([_to_aval(p) for _, p in named_params] +
             [_to_aval(b) for _, b in named_buffers] +
             [_to_aval(x) for x in example_inputs])
    closed = jax.make_jaxpr(pure)(key_aval, *avals)
    labels = (["rng_key"] + [n for n, _ in named_params] +
              [n for n, _ in named_buffers] +
              [f"input[{i}]" for i in range(len(example_inputs))])
    return analyze_jaxpr(
        closed, name=name or type(model).__name__,
        invar_labels=labels, **analyze_kwargs)

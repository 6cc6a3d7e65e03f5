"""Differential oracle for Pallas kernels — the runtime half of the
PTA6xx static passes (framework/analysis/pallas_kernels.py).

A kernel that compiles is not a kernel that is right: Mosaic clips
out-of-bounds writes and pads out-of-bounds reads, so a tiling bug
produces silently wrong numbers, not a fault.  The oracle closes the
loop the way the parity probe does for replica state: run the SAME
kernel callable three ways —

* compiled (whatever path the dispatcher picks on this backend),
* ``interpret=True`` (the Pallas interpreter, exact block semantics),
* the pure-jnp reference (ground truth),

and gate tolerance agreement per output leaf.  A disagreement names the
first divergent operand with the SAME ``<name>.<operand>`` label the
static pass prints (see ``pallas_kernels.operand_labels``), so a static
PTA601 finding and a runtime ``PALLAS_DIVERGENCE`` line point at one
name.

Armed via ``FLAGS_pallas_verify`` (also armed per tiling candidate by
``tools/flash_autotune.py`` before any candidate is timed).  Disarmed
is one flag lookup — the callables are not even invoked.  The oracle
NEVER raises: the ``pallas.verify`` chaos point plus swallow-and-count
(``pallas_verify_errors_total``) keep the watcher from crashing the
watched (``tools/chaos_drill.py`` discipline).

Metrics: ``pallas_verify_checks_total``, ``pallas_divergence_total``,
``pallas_verify_errors_total``; divergences additionally record a
``pallas.divergence`` flight event carrying the operand label and the
max abs error.
"""
from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

from paddle_tpu.framework import chaos, monitor
from paddle_tpu.framework.flags import flag

__all__ = ["armed", "verify_call", "interpreted", "boundary_corpus",
           "check_flash_candidate", "VerifyResult", "SCALE_TOL",
           "max_errors"]

#: a bf16 kernel against a float32 reference: the max abs error allowed
#: per output, as a share of the reference's largest value (about five
#: bf16 ulps at the top of the range).  Two bf16 pipelines round in
#: different places, and elementwise closeness is too strict for
#: gradients that sum thousands of terms — measured on the chip, XLA's own
#: bf16 attention sits 2-5x further from the float32 result than the
#: flash kernel does.
SCALE_TOL = 2e-2

monitor.describe("pallas_verify_checks_total",
                 "differential-oracle checks completed (armed only)")
monitor.describe("pallas_divergence_total",
                 "kernel outputs that disagreed between the compiled/"
                 "interpret/reference legs; the pallas.divergence "
                 "flight event names the operand")
monitor.describe("pallas_verify_errors_total",
                 "oracle faults (real or pallas.verify chaos) swallowed "
                 "without touching the watched kernel call")

# (mode label, mode label) pairs compared by verify_call; kept as data so
# the report names which legs disagreed
_LEGS = ("compiled", "interpret", "reference")


def armed() -> bool:
    """One flag lookup — the entire disarmed cost of the oracle."""
    try:
        return bool(flag("pallas_verify"))
    except Exception:                  # noqa: BLE001 — flags not initialised
        return False


@contextlib.contextmanager
def interpreted(*modules):
    """Flip each kernel module's ``_INTERPRET`` toggle for the scope —
    the same switch the interpret-mode tests use."""
    saved = [getattr(m, "_INTERPRET", False) for m in modules]
    for m in modules:
        m._INTERPRET = True
    try:
        yield
    finally:
        for m, s in zip(modules, saved):
            m._INTERPRET = s


@dataclass
class VerifyResult:
    """Outcome of one oracle check.  ``divergent`` is True when any
    output leaf disagrees between any two legs; ``operand`` then names
    the first divergent leaf with the static pass's label."""
    name: str
    divergent: bool = False
    operand: Optional[str] = None
    legs: Optional[Tuple[str, str]] = None
    max_abs_err: float = 0.0
    checked: int = 0
    labels: List[str] = field(default_factory=list)


def _leaves(out) -> List[Any]:
    import jax
    return [x for x in jax.tree_util.tree_leaves(out)
            if hasattr(x, "shape")]


def max_errors(got, want) -> List[Tuple[float, float]]:
    """Per output leaf: (max abs error, the reference's largest abs
    value) — what the on-chip checks (``chip_smoke.py``,
    ``tools/kernel_check.py``) hold against :data:`SCALE_TOL`."""
    out = []
    for g, w in zip(_leaves(got), _leaves(want)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        if g.shape != w.shape:
            raise ValueError(f"output shape {g.shape} vs reference "
                             f"{w.shape}")
        out.append((float(np.max(np.abs(g - w), initial=0.0)),
                    float(np.max(np.abs(w), initial=0.0))))
    return out


def _labels_for(name: str, run_kernel, args, n_out: int,
                out_labels) -> List[str]:
    if out_labels:
        return list(out_labels)
    # derive from the kernel model so the runtime label matches the
    # static pass exactly (single-pallas_call kernels; others fall back
    # to positional labels)
    try:
        from paddle_tpu.framework.analysis.pallas_kernels import (
            trace_kernels)
        models = trace_kernels(run_kernel, *args)
        if len(models) == 1 and len(models[0].outputs) == n_out:
            return [f"{name}.{op.label}" for op in models[0].outputs]
    except Exception:                  # noqa: BLE001 — labels are best-effort
        pass
    return [f"{name}.out{i}" for i in range(n_out)]


def _compare(name: str, outs: List[Tuple[str, List[Any]]],
             labels: List[str], rtol: float,
             atol: float) -> VerifyResult:
    res = VerifyResult(name=name, labels=labels)
    for i in range(min(len(o) for _, o in outs)):
        res.checked += 1
        for (la, oa), (lb, ob) in zip(outs, outs[1:]):
            a = np.asarray(oa[i], dtype=np.float64)
            b = np.asarray(ob[i], dtype=np.float64)
            ok = a.shape == b.shape and bool(
                np.allclose(a, b, rtol=rtol, atol=atol, equal_nan=False))
            if ok:
                continue
            if a.shape != b.shape:
                err = float("inf")
            else:
                diff = np.abs(a - b)
                finite = diff[~np.isnan(diff)]
                err = float(finite.max()) if finite.size else float("nan")
            res.divergent = True
            res.operand = labels[i] if i < len(labels) else \
                f"{name}.out{i}"
            res.legs = (la, lb)
            res.max_abs_err = max(res.max_abs_err, err)
            return res
    return res


def verify_call(name: str, run_kernel: Callable, run_reference:
                Optional[Callable], args: Sequence[Any] = (), *,
                interpret_modules: Sequence[Any] = (),
                out_labels: Optional[Sequence[str]] = None,
                skip_compiled: bool = False,
                rtol: float = 1e-4,
                atol: float = 1e-5) -> Optional[VerifyResult]:
    """Run the differential oracle on one kernel call site.

    ``run_kernel(*args)`` is the kernel exactly as the caller would run
    it; ``run_reference(*args)`` the pure-jnp ground truth (None skips
    that leg).  ``interpret_modules`` are the kernel modules whose
    ``_INTERPRET`` toggle selects the interpreter leg (empty skips it).
    ``skip_compiled`` drops the compiled leg — the CPU configuration,
    where Mosaic cannot lower and only interpret-vs-reference is
    meaningful.

    Disarmed (``FLAGS_pallas_verify`` false): returns None WITHOUT
    invoking any callable — the cost is one flag lookup.  Armed: never
    raises; a broken oracle (real or injected via the ``pallas.verify``
    chaos point) is swallowed and counted
    (``pallas_verify_errors_total``), the caller's own kernel call is
    untouched.
    """
    if not armed():
        return None
    from paddle_tpu.framework.observability import flight
    try:
        chaos.fault_point("pallas.verify", meta={"name": name})
        outs: List[Tuple[str, List[Any]]] = []
        if not skip_compiled:
            outs.append(("compiled", _leaves(run_kernel(*args))))
        if interpret_modules:
            with interpreted(*interpret_modules):
                outs.append(("interpret", _leaves(run_kernel(*args))))
        if run_reference is not None:
            outs.append(("reference", _leaves(run_reference(*args))))
        if len(outs) < 2:
            return None
        labels = _labels_for(name, run_kernel, args,
                             len(outs[0][1]), out_labels)
        res = _compare(name, outs, labels, rtol, atol)
    except Exception:                  # noqa: BLE001 — swallow-and-count
        monitor.stat_add("pallas_verify_errors_total")
        return None
    monitor.stat_add("pallas_verify_checks_total")
    if res.divergent:
        monitor.stat_add("pallas_divergence_total")
        flight.record("pallas.divergence", severity="error",
                      name=name, operand=res.operand,
                      legs=list(res.legs or ()),
                      max_abs_err=res.max_abs_err)
    return res


def boundary_corpus(block_q: int = 128, block_k: int = 128,
                    d: int = 64) -> List[dict]:
    """The deterministic boundary-shape corpus the autotune oracle
    sweeps per tiling candidate: non-divisible lengths (tail blocks on
    both grid axes), the single-block case, a zero-tail case, a square
    that both tile sides divide (the two-level loop nest: sub-tiles
    under, on and above the diagonal), and the dtype matrix.  Pure
    function of the block shape — same candidate, same corpus, same
    verdict."""
    bq, bk = int(block_q), int(block_k)
    # two tiles a side where that stays within S = 1024, as far as the
    # nest's VMEM budget reaches (flash_attention._two_level); a lopsided
    # tile such as (1024, 256) still has four pieces a block there
    side = math.lcm(bq, bk)
    if 2 * side <= 1024:
        side *= 2
    shapes = [
        # (sq, sk): non-divisible tails on q, on k, on both, single block,
        # multi-tile square
        (bq + bq // 2, bk + bk // 2),
        (bq, bk + 1),
        (bq + 1, bk),
        (bq, bk),
        (side, side),
    ]
    corpus = []
    for dtype in ("float32", "bfloat16"):
        for sq, sk in shapes:
            corpus.append({"sq": int(sq), "sk": int(sk), "d": int(d),
                           "dtype": dtype})
    return corpus


def check_flash_candidate(block_q, block_k, *, d=64, dtype="bfloat16",
                          causal=False, biased=False, heads=2,
                          grads=True):
    """Validate one flash-attention tiling candidate on the boundary
    corpus (flash_autotune's pre-timing gate: a fast wrong kernel must
    never win a sweep).

    Each corpus case runs fwd (and, with ``grads``, dq/dk/dv) through
    :func:`verify_call` — compiled vs interpret vs the XLA reference —
    with the candidate blocks forced.  Returns [] when every case
    agrees, else one ``{"sq", "sk", "dtype", "operand"}`` dict per
    failed case: a divergence names its operand, and a case the oracle
    could not finish (:func:`verify_call` returned None — e.g. Mosaic
    refused the compiled leg) fails as ``<name>.oracle_fault``, because a
    tiling that did not run is not a tiling that was verified.  Corpus
    cases the dispatcher would not send to the kernel anyway (masked
    non-divisible shapes, causal sq>sk) are skipped, not failed.
    Requires ``FLAGS_pallas_verify``: disarmed, the oracle runs nothing.
    """
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import autotune
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas.common import backend_is_tpu

    if not armed():
        raise RuntimeError("check_flash_candidate needs FLAGS_pallas_verify "
                           "armed: the disarmed oracle checks nothing")
    failures = []
    for case in boundary_corpus(block_q, block_k, d):
        sq, sk, cd = case["sq"], case["sk"], case["d"]
        if causal and sq > sk:
            continue
        if biased and (sq % block_q or sk % block_k):
            continue
        jdt = jnp.bfloat16 if case["dtype"] == "bfloat16" else jnp.float32
        rng = np.random.default_rng(sq * 7919 + sk)
        q = jnp.asarray(rng.standard_normal((1, sq, heads, cd)), jdt)
        k = jnp.asarray(rng.standard_normal((1, sk, heads, cd)), jdt)
        v = jnp.asarray(rng.standard_normal((1, sk, heads, cd)), jdt)
        bias = jnp.asarray(rng.standard_normal((1, 1, 1, sk)),
                           jnp.float32) if biased else None
        scale = 1.0 / float(np.sqrt(cd))

        def _loss(fn, q_, k_, v_):
            return (fn(q_, k_, v_) ** 2).astype(jnp.float32).sum()

        def run_kernel(q_, k_, v_):
            flash = lambda a, b, c: fa.flash_attention(
                a, b, c, causal=causal, scale=scale, bias=bias)
            with autotune.force_blocks(block_q, block_k):
                if not grads:
                    return flash(q_, k_, v_)
                return jax.value_and_grad(
                    lambda a, b, c: _loss(flash, a, b, c),
                    argnums=(0, 1, 2))(q_, k_, v_)

        def run_reference(q_, k_, v_):
            ref = lambda a, b, c: fa._xla_reference(
                a, b, c, scale, causal, bias=bias)
            if not grads:
                return ref(q_, k_, v_)
            return jax.value_and_grad(
                lambda a, b, c: _loss(ref, a, b, c),
                argnums=(0, 1, 2))(q_, k_, v_)

        name = f"flash[{block_q}x{block_k}]"
        labels = [f"{name}.out"] if not grads else \
            [f"{name}.{x}" for x in ("loss", "dq", "dk", "dv")]
        # on the TPU, XLA multiplies f32 in bf16 passes by default (the
        # interpret and reference legs) while Mosaic does not, so f32
        # agrees no tighter than bf16 there
        loose = case["dtype"] == "bfloat16" or backend_is_tpu()
        res = verify_call(name, run_kernel, run_reference, (q, k, v),
                          interpret_modules=(fa,), out_labels=labels,
                          skip_compiled=not backend_is_tpu(),
                          rtol=5e-2 if loose else 5e-3,
                          atol=5e-2 if loose else 5e-4)
        if res is None or res.divergent:
            failures.append({"sq": sq, "sk": sk, "dtype": case["dtype"],
                             "operand": res.operand if res is not None
                             else f"{name}.oracle_fault"})
    return failures

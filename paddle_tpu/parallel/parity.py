"""Runtime replica-parity probe — the dynamic half of the PTA5xx
distributed-semantics plane.

The static passes (``framework/analysis/collectives.py``) prove a traced
step cannot *claim* replication it did not earn; this module checks the
claim against what actually sits in device memory.  Every manual region
in the repo runs with jax's replication checking disabled
(``mesh.shard_map_compat``: ``check_vma=False``), so a missing
``psum`` produces a global array whose per-device buffers silently
differ while its sharding says "replicated" — the PTA501 bug class at
runtime.  With ``FLAGS_replica_parity`` armed, the train-step classes
fold a per-leaf content hash of every *replicated, multi-device*
param/opt-state leaf through a ``psum``-based agreement check every
``FLAGS_replica_parity_every`` steps:

* the hash is a position-weighted wrap-sum of the leaf's raw bits
  (uint32) — bitwise, dtype-blind, deterministic, and O(n) fused into
  one tiny jitted shard_map program per (mesh, tree) signature;
* inside the region each replica ``psum``-s its hash vector and checks
  ``sum == dp * h`` (agreement is cheap on the wire: one uint32 per
  leaf); the per-replica hash matrix also ships back (``P(axis)`` out
  spec) so the host verdict is exact, not modulo the wrap;
* a divergent leaf fires ONE ``parity.divergence`` flight event naming
  the first divergent leaf (sorted leaf order — the same order the
  static PTA501 labels use, so both halves name the same leaf) and
  counts ``parity_divergence_total``; the probe NEVER raises — the
  ``parity.observe`` chaos point plus a swallow-and-count guard
  (``parity_observe_errors_total``) pin the watcher-never-crashes-the-
  watched contract.

Disarmed, the whole plane is one flag lookup per step, the step classes
build exactly the seed computation (the probe is a *separate* jitted
program — zero aux outputs, signature-cache keys byte-identical), and
nothing is compiled.  Leaves that are not fully replicated across >1
device (dp-sharded ZeRO moments, single-device arrays) are skipped —
per-replica state is *supposed* to differ.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

import numpy as np

from paddle_tpu.framework import chaos, monitor
from paddle_tpu.framework.flags import flag

__all__ = ["enabled", "probe_every", "ParityRecord", "ParityProbe",
           "maybe_observe", "reset", "leaf_hash_host"]


def enabled() -> bool:
    """True when the probe is armed (``FLAGS_replica_parity``)."""
    return bool(flag("replica_parity"))


def probe_every() -> int:
    """Probe cadence in steps (``FLAGS_replica_parity_every``; min 1)."""
    return max(1, int(flag("replica_parity_every")))


# ---------------------------------------------------------------------------
# traced hash (inside the probe's shard_map)
# ---------------------------------------------------------------------------


def _leaf_hash_traced(x):
    """uint32 content hash of one leaf's raw bits: position-weighted
    wrap-sum over the bit pattern.  Bitwise — any single-bit difference
    between replicas flips the hash (modulo the 2^32 wrap, which the
    host-side exact compare of the gathered hash matrix closes)."""
    import jax
    import jax.numpy as jnp
    flat = x.reshape(-1)
    if flat.dtype == jnp.bool_:
        flat = flat.astype(jnp.uint8)
    size = np.dtype(flat.dtype).itemsize
    if size == 1:
        bits = flat.astype(jnp.uint32)
    elif size == 2:
        bits = jax.lax.bitcast_convert_type(
            flat, jnp.uint16).astype(jnp.uint32)
    elif size == 4:
        bits = jax.lax.bitcast_convert_type(flat, jnp.uint32)
    else:                        # 8-byte: bitcast appends a (2,) word dim
        bits = jax.lax.bitcast_convert_type(
            flat, jnp.uint32).reshape(-1)
    if bits.shape[0] == 0:
        return jnp.zeros((), jnp.uint32)
    w = jnp.arange(bits.shape[0], dtype=jnp.uint32) * jnp.uint32(2) \
        + jnp.uint32(1)
    return jnp.sum(bits * w, dtype=jnp.uint32)


def leaf_hash_host(x) -> int:
    """Numpy twin of :func:`_leaf_hash_traced` — bit-identical hash of
    a HOST array, no trace, no device.  The postmortem plane
    (framework/incident.py, tools/replay.py) hashes recorded and
    re-executed state trees with this so a replay's first-divergence
    bisection names the same leaf either probe would."""
    flat = np.ascontiguousarray(np.asarray(x)).reshape(-1)
    if flat.dtype == np.bool_:
        flat = flat.astype(np.uint8)
    size = flat.dtype.itemsize
    if size == 1:
        bits = flat.astype(np.uint32)
    elif size == 2:
        bits = flat.view(np.uint16).astype(np.uint32)
    elif size == 4:
        bits = flat.view(np.uint32)
    else:                            # 8-byte: two uint32 words per element
        bits = flat.view(np.uint32)
    if bits.shape[0] == 0:
        return 0
    w = np.arange(bits.shape[0], dtype=np.uint32) * np.uint32(2) \
        + np.uint32(1)
    with np.errstate(over="ignore"):
        return int((bits * w).sum(dtype=np.uint32))


# ---------------------------------------------------------------------------
# host-side record
# ---------------------------------------------------------------------------


class ParityRecord:
    """One probe's verdict: per-leaf hashes per replica + agreement."""

    __slots__ = ("names", "hashes", "agree", "step")

    def __init__(self, names: List[str], hashes: np.ndarray,
                 agree: np.ndarray, step: Optional[int] = None):
        self.names = list(names)
        self.hashes = np.asarray(hashes)      # (replicas, leaves) uint32
        self.agree = np.asarray(agree)        # (replicas, leaves) bool
        self.step = step

    def divergent_leaves(self) -> List[str]:
        """Leaves whose hash differs across replicas (exact compare of
        the gathered matrix — immune to the psum wrap)."""
        if self.hashes.size == 0:
            return []
        differs = (self.hashes != self.hashes[0:1]).any(axis=0)
        differs |= ~self.agree.all(axis=0)
        return [n for n, d in zip(self.names, differs) if d]

    def first_divergent_leaf(self) -> Optional[str]:
        bad = self.divergent_leaves()
        return bad[0] if bad else None

    def ok(self) -> bool:
        return not self.divergent_leaves()

    def to_dict(self) -> dict:
        return {"step": self.step, "leaves": len(self.names),
                "divergent": self.divergent_leaves()}


# ---------------------------------------------------------------------------
# the probe
# ---------------------------------------------------------------------------


class ParityProbe:
    """Compiled replica-agreement check over one mesh axis.

    One instance per step object; the compiled shard_map program is
    cached per (leaf names, shapes, dtypes) signature, so a stable
    training loop compiles the probe exactly once."""

    def __init__(self, mesh=None, axis: str = "dp",
                 every: Optional[int] = None):
        from paddle_tpu.parallel.mesh import get_mesh
        self.mesh = mesh or get_mesh()
        self.axis = axis
        self.every = every
        self._fns: Dict[tuple, object] = {}
        self._calls = 0
        self._lock = threading.Lock()

    # -- leaf selection ------------------------------------------------------
    def _probe_leaves(self, tree: Dict[str, object]) -> Dict[str, object]:
        """The leaves the probe can meaningfully check: fully-replicated
        arrays spanning more than one device.  Sharded leaves (ZeRO
        moments on dp) and single-device arrays are skipped — their
        per-replica bytes differ by design / cannot diverge."""
        out = {}
        for n, a in tree.items():
            sh = getattr(a, "sharding", None)
            if sh is None or not getattr(sh, "is_fully_replicated", False):
                continue
            try:
                if len(sh.device_set) <= 1:
                    continue
            except Exception:            # noqa: BLE001 — exotic shardings
                continue
            out[n] = a
        return out

    def _build(self, names, leaves):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.parallel.mesh import shard_map_compat
        axis = self.axis
        k = jnp.uint32(self.mesh.shape.get(axis, 1))

        def body(*ls):
            h = jnp.stack([_leaf_hash_traced(x) for x in ls]) \
                if ls else jnp.zeros((0,), jnp.uint32)
            hs = jax.lax.psum(h, axis)
            agree = hs == h * k
            return h[None], agree[None]

        mapped = shard_map_compat(
            body, mesh=self.mesh, in_specs=(P(),) * len(names),
            out_specs=(P(axis), P(axis)))
        return jax.jit(mapped)

    # -- checks --------------------------------------------------------------
    def check(self, tree: Dict[str, object],
              step: Optional[int] = None) -> Optional[ParityRecord]:
        """Hash-compare every probeable leaf across replicas.  Returns
        the record, or None when nothing in ``tree`` is probeable (dp=1
        mesh, single-device state)."""
        if self.mesh.shape.get(self.axis, 1) <= 1:
            return None
        leaves = self._probe_leaves(tree)
        if not leaves:
            return None
        names = sorted(leaves)
        arrs = [leaves[n] for n in names]
        sig = tuple((n, tuple(a.shape), str(a.dtype))
                    for n, a in zip(names, arrs))
        fn = self._fns.get(sig)
        if fn is None:
            fn = self._fns[sig] = self._build(names, arrs)
        h, agree = fn(*arrs)
        return ParityRecord(names, np.asarray(h), np.asarray(agree),
                            step=step)

    def observe(self, tree: Dict[str, object],
                step: Optional[int] = None) -> Optional[ParityRecord]:
        """The armed per-step entry: every-K gate, chaos point, flight
        event on divergence.  NEVER raises — an injected or real probe
        fault is swallowed and counted (the watcher must not crash the
        watched train loop)."""
        if not enabled():
            return None
        with self._lock:
            self._calls += 1
            due = (self._calls % (self.every or probe_every())) == 0
        if not due:
            return None
        from paddle_tpu.framework.observability import flight
        try:
            chaos.fault_point("parity.observe", meta={"step": step})
            rec = self.check(tree, step=step)
        except Exception:                # noqa: BLE001 — swallow-and-count
            monitor.stat_add("parity_observe_errors_total")
            return None
        if rec is None:
            return None
        monitor.stat_add("parity_checks_total")
        bad = rec.divergent_leaves()
        if bad:
            monitor.stat_add("parity_divergence_total")
            flight.record("parity.divergence", severity="error",
                          first_bad_leaf=bad[0], leaves=bad,
                          step=step)
        return rec


# ---------------------------------------------------------------------------
# train-step hook
# ---------------------------------------------------------------------------


def _state_tree(step) -> Dict[str, object]:
    """Param + opt-state leaves of a TrainStep-surface object as one
    flat name->array dict (sorted names; opt leaves prefixed ``opt.``
    so a divergent moment is named distinctly from its param)."""
    import jax.tree_util as jtu
    tree = {}
    model = getattr(step, "model", None)
    if model is not None:
        for n, p in model.named_parameters():
            tree[n] = p._data
    states = getattr(step, "_opt_states", None)
    if states is not None:
        flat, _ = jtu.tree_flatten_with_path(states)
        for path, leaf in flat:
            if hasattr(leaf, "shape"):
                tree["opt" + jtu.keystr(path)] = leaf
    return tree


def maybe_observe(step, mesh=None, axis: str = "dp"):
    """The one-line hook the train-step classes call after committing a
    step: no-op (one flag lookup) unless ``FLAGS_replica_parity`` is
    armed.  Lazily attaches a :class:`ParityProbe` to ``step``."""
    if not enabled():
        return None
    probe = getattr(step, "_parity_probe", None)
    if probe is None:
        probe = ParityProbe(mesh=mesh, axis=axis)
        step._parity_probe = probe
    opt = getattr(step, "optimizer", None)
    at = int(getattr(opt, "_global_step", 0)) if opt is not None else None
    return probe.observe(_state_tree(step), step=at)


def reset():
    """Nothing module-global to clear (probes live on their steps);
    kept for symmetry with the other observability planes."""

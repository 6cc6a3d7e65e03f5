"""Device mesh + hybrid topology.

Replaces the reference's ring/process-group bookkeeping:
- ``HybridCommunicateGroup`` (reference: python/paddle/distributed/fleet/base/
  topology.py:97) — rank → (dp, mp, pp, sharding) coordinates — becomes
  ``HybridTopology``, a thin view over a named ``jax.sharding.Mesh``.
- NCCL comm creation + TCP id broadcast (reference: paddle/fluid/platform/
  gen_comm_id_helper.cc:126, collective_helper.h:67) has no analogue: XLA owns
  ICI/DCN channel setup; multi-host bootstrap is ``jax.distributed.initialize``.

Axis-name conventions (used across the framework):
  ``dp``  data parallel          ``sharding``  ZeRO/optimizer-state shards
  ``pp``  pipeline stages        ``mp``        tensor (model) parallel
  ``sp``  sequence/context parallel   ``ep``   expert parallel
"""
from __future__ import annotations

import contextlib
import math
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["make_mesh", "get_mesh", "set_mesh", "auto_mesh", "mesh_axis_size",
           "HybridTopology", "DistAttr", "shard_spec", "shard_map_compat"]


def shard_map_compat(f, mesh, in_specs, out_specs):
    """Fully-manual ``jax.shard_map`` with the replication check off
    (``check_vma=False``): the bodies here mix replicated and varying
    values on purpose, and ``framework/analysis/collectives.py`` re-runs
    that analysis as diagnostics instead of trace errors."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


_global_mesh: Optional[Mesh] = None

# canonical axis order: pipeline outermost (DCN-friendly), then data/sharding,
# model/sequence innermost (highest-bandwidth ICI neighbours)
AXIS_ORDER = ("pp", "dp", "sharding", "mp", "sp", "ep")


def make_mesh(axes: Dict[str, int], devices=None) -> Mesh:
    """Build a named mesh. ``axes`` maps axis name → size; sizes must multiply
    to the device count (a size of -1 is inferred)."""
    if devices is None:
        devices = jax.devices()
    names = [a for a in AXIS_ORDER if a in axes] + [
        a for a in axes if a not in AXIS_ORDER]
    sizes = [axes[n] for n in names]
    n_dev = len(devices)
    if -1 in sizes:
        known = math.prod(s for s in sizes if s != -1)
        sizes[sizes.index(-1)] = n_dev // known
    need = math.prod(sizes)
    if need > n_dev:
        raise ValueError(
            f"mesh axes {dict(zip(names, sizes))} exceed {n_dev} devices")
    # a sub-mesh over the first `need` chips is fine (parity: new_group over
    # a rank subset)
    arr = np.asarray(devices[:need]).reshape(sizes)
    return Mesh(arr, tuple(names))


def auto_mesh(dp: int = -1, mp: int = 1, pp: int = 1, sharding: int = 1,
              sp: int = 1, ep: int = 1, devices=None) -> Mesh:
    """Fleet-style mesh from hybrid degrees (parity: DistributedStrategy
    hybrid_configs dp/mp/pp degrees)."""
    axes = {}
    for name, size in (("pp", pp), ("dp", dp), ("sharding", sharding),
                       ("mp", mp), ("sp", sp), ("ep", ep)):
        if size != 1:
            axes[name] = size
    if not axes:
        axes = {"dp": -1}
    # explicit degrees smaller than the device count run a sub-mesh (same
    # policy as fleet's strategy compiler); degrees exceeding it raise in
    # make_mesh
    return make_mesh(axes, devices)


def set_mesh(mesh: Mesh):
    global _global_mesh
    _global_mesh = mesh
    return mesh


def get_mesh() -> Mesh:
    """The active mesh; defaults to a 1-D data-parallel mesh over all
    devices (the implicit 'world' ring of the reference)."""
    global _global_mesh
    if _global_mesh is None:
        _global_mesh = make_mesh({"dp": len(jax.devices())})
    return _global_mesh


def mesh_axis_size(axis: str, mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or get_mesh()
    return mesh.shape.get(axis, 1)


def _clean_axes(axes, mesh: Mesh) -> PartitionSpec:
    """Drop axes absent from ``mesh`` (they become replicated), so code can
    annotate for the full hybrid layout and still run on a smaller mesh."""
    cleaned = []
    for a in axes:
        if a is None:
            cleaned.append(None)
        elif isinstance(a, (tuple, list)):
            keep = tuple(x for x in a if x in mesh.shape)
            cleaned.append(keep if keep else None)
        else:
            cleaned.append(a if a in mesh.shape else None)
    while cleaned and cleaned[-1] is None:
        cleaned.pop()
    return PartitionSpec(*cleaned)


def shard_spec(*axes) -> PartitionSpec:
    """Mesh-tolerant PartitionSpec over the active mesh."""
    return _clean_axes(axes, get_mesh())


_MANUAL_REGION = threading.local()


@contextlib.contextmanager
def manual_region():
    """Mark the dynamic extent of a fully-manual ``shard_map`` trace:
    :func:`constrain` becomes a no-op inside it.  The explicitly-
    collective train steps (``parallel/zero.py``, ``dp_meta``) wrap their
    dispatch in this so model-internal activation constraints (e.g.
    GPT's) are skipped without staging a with_sharding_constraint that
    would only be rejected."""
    prev = getattr(_MANUAL_REGION, "depth", 0)
    _MANUAL_REGION.depth = prev + 1
    try:
        yield
    finally:
        _MANUAL_REGION.depth = prev


def in_manual_region() -> bool:
    return getattr(_MANUAL_REGION, "depth", 0) > 0


def constrain(arr, *axes, strip=()):
    """with_sharding_constraint on a raw array over the active mesh.

    The single sharding-constraint helper used by models/tp layers. Axes
    absent from the mesh (or listed in ``strip``) are replicated; inside a
    fully-manual shard_map region the constraint is skipped (meaningless
    there); any other failure is a real error and raises."""
    import jax
    if in_manual_region():
        return arr
    axes = tuple(None if a in strip else a for a in axes)
    spec = shard_spec(*axes)
    if len(spec) > arr.ndim:
        raise ValueError(
            f"sharding spec {tuple(spec)} has rank {len(spec)} > array "
            f"rank {arr.ndim}")
    sharding = NamedSharding(get_mesh(), spec)
    try:
        return jax.lax.with_sharding_constraint(arr, sharding)
    except ValueError as e:
        if "manual" in str(e).lower():
            return arr
        raise


def per_device(fn, mesh: Mesh, axes):
    """Run ``fn`` — a Pallas kernel over arrays that share one layout —
    on each device's shard.

    GSPMD cannot partition a Mosaic custom call (lowering one under a
    mesh of several devices raises "wrap the call in a shard_map"), so
    there the kernel runs in a fully-manual region, ``axes`` naming the
    mesh axes of each array dim (mesh-tolerant, as in :func:`constrain`;
    the dims must divide).  On one device, or inside an enclosing manual
    region (the pipeline trunk, the explicitly-collective steps), ``fn``
    is returned as it is."""
    if mesh.size == 1 or jax.sharding.get_abstract_mesh().manual_axes:
        return fn
    spec = _clean_axes(axes, mesh)
    return shard_map_compat(fn, mesh, in_specs=spec, out_specs=spec)


class DistAttr:
    """Sharding annotation carried by a Parameter/Tensor.

    The TPU-native replacement for the reference's per-op ring_id attributes
    and the sharding meta-optimizer's variable→device maps
    (fleet/meta_optimizers/sharding_optimizer.py): a parameter simply names
    the mesh axes each of its dims is split over; the pjit'd train step turns
    that into a NamedSharding and XLA does the rest.
    """

    __slots__ = ("spec",)

    def __init__(self, spec: Sequence):
        self.spec = PartitionSpec(*spec) if not isinstance(
            spec, PartitionSpec) else spec

    def sharding(self, mesh: Optional[Mesh] = None) -> NamedSharding:
        mesh = mesh or get_mesh()
        return NamedSharding(mesh, _clean_axes(tuple(self.spec), mesh))

    def __repr__(self):
        return f"DistAttr({tuple(self.spec)})"


class HybridTopology:
    """Rank-coordinate bookkeeping over a named mesh.

    Parity: ``HybridCommunicateGroup`` (reference: python/paddle/distributed/
    fleet/base/topology.py:97) — exposes the same queries (world rank →
    parallel-group ranks, degrees, stage ids) expressed over mesh axes
    instead of comm rings.
    """

    def __init__(self, mesh: Optional[Mesh] = None):
        self._mesh = mesh or get_mesh()
        self._names = list(self._mesh.axis_names)
        self._sizes = [self._mesh.shape[n] for n in self._names]
        self._n = math.prod(self._sizes)

    @property
    def mesh(self) -> Mesh:
        return self._mesh

    def world_size(self) -> int:
        return self._n

    def coordinate(self, rank: int) -> Tuple[int, ...]:
        coord = []
        rem = rank
        for size in reversed(self._sizes):
            coord.append(rem % size)
            rem //= size
        return tuple(reversed(coord))

    def rank_of(self, coord: Sequence[int]) -> int:
        rank = 0
        for c, size in zip(coord, self._sizes):
            rank = rank * size + c
        return rank

    def _axis_idx(self, axis: str) -> int:
        if axis not in self._names:
            raise ValueError(f"axis {axis!r} not in mesh {self._names}")
        return self._names.index(axis)

    def get_degree(self, axis: str) -> int:
        return self._sizes[self._axis_idx(axis)] if axis in self._names else 1

    def axis_rank(self, rank: int, axis: str) -> int:
        """This rank's index along ``axis`` (e.g. its pipeline stage)."""
        if axis not in self._names:
            return 0
        return self.coordinate(rank)[self._axis_idx(axis)]

    def group_ranks(self, rank: int, axis: str) -> List[int]:
        """All world ranks in ``rank``'s communicator along ``axis``
        (parity: topology.py get_comm_group)."""
        i = self._axis_idx(axis)
        coord = list(self.coordinate(rank))
        out = []
        for k in range(self._sizes[i]):
            coord[i] = k
            out.append(self.rank_of(coord))
        return out

    # paddle-parity convenience accessors -----------------------------------
    def get_data_parallel_world_size(self):
        return self.get_degree("dp")

    def get_model_parallel_world_size(self):
        return self.get_degree("mp")

    def get_pipe_parallel_world_size(self):
        return self.get_degree("pp")

    def get_sharding_parallel_world_size(self):
        return self.get_degree("sharding")

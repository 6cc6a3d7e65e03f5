"""ShardedTrainStep — hybrid-parallel whole-step capture.

This single class is the TPU-native equivalent of the reference's entire
distributed-training execution path:

- DP allreduce insertion (reference: paddle/fluid/framework/details/
  all_reduce_op_handle.cc:68 and imperative/reducer.cc bucketed fused
  allreduce): here the batch is sharded over the ``dp`` axis and grads come
  out of ``jax.grad`` already partial; XLA's sharding propagation inserts the
  (fused, overlapped) reduce — no buckets, no hooks.
- Sharding/ZeRO meta-optimizer (reference: fleet/meta_optimizers/
  sharding_optimizer.py:115 — 4-D hybrid mp×sharding×pp×dp): optimizer
  states (stage≥1), gradients (stage≥2) and parameters (stage 3) get
  NamedShardings over the ``sharding`` axis; XLA emits reduce-scatter /
  all-gather where the reference inserted c_broadcast/c_allreduce ops.
- Recompute meta-optimizer (reference: python/paddle/fluid/backward.py:729
  checkpoint backward): ``jax.checkpoint`` over the loss closure.
- Gradient merge (reference: fleet/gradient_merge_optimizer.py):
  ``accumulate_steps`` micro-batch scan inherited from jit.TrainStep.
- AMP meta-optimizer: bf16 cast inherited from jit.TrainStep.

Parameters/activations opt in to tensor/pipeline/sequence parallelism by
carrying a ``DistAttr`` (see mesh.py) — set directly by the parallel layers
in paddle_tpu.distributed.tp_layers or via ``shard_module`` name rules.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from paddle_tpu.jit import TrainStep
from paddle_tpu.nn.layer.layers import Layer
from paddle_tpu.parallel.mesh import DistAttr, get_mesh

__all__ = ["ShardedTrainStep", "shard_module"]


def shard_module(module: Layer, rules: Dict[str, tuple]) -> Layer:
    """Attach DistAttrs to parameters by name-regex rules,
    e.g. ``{r"qkv_proj\\.weight": (None, "mp")}``."""
    for name, p in module.named_parameters():
        for pat, spec in rules.items():
            if re.search(pat, name):
                p.dist_attr = DistAttr(spec)
                break
    return module


def _replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def _param_sharding(p, mesh: Mesh) -> NamedSharding:
    attr = getattr(p, "dist_attr", None)
    if attr is None:
        return _replicated(mesh)
    return attr.sharding(mesh)


def _shard_over_axis(shape, base: PartitionSpec, axis: str, axis_size: int,
                     mesh: Mesh) -> NamedSharding:
    """ZeRO placement: additionally split the first free, divisible dim of
    ``shape`` over ``axis`` (the reference shards whole variables across
    ranks, sharding_optimizer.py; on TPU splitting a dim gives XLA clean
    reduce-scatter/all-gather patterns)."""
    spec = list(base) + [None] * (len(shape) - len(base))
    used = set()
    for s in spec:
        if isinstance(s, (tuple, list)):
            used.update(s)
        elif s is not None:
            used.add(s)
    if axis in used or axis_size <= 1:
        return NamedSharding(mesh, PartitionSpec(*spec))
    for i, dim in enumerate(shape):
        if spec[i] is None and dim % axis_size == 0 and dim >= axis_size:
            spec[i] = axis
            break
    while spec and spec[-1] is None:
        spec.pop()
    return NamedSharding(mesh, PartitionSpec(*spec))


class ShardedTrainStep(TrainStep):
    """TrainStep compiled over a mesh with full hybrid shardings.

    Args beyond TrainStep:
      mesh: named device mesh (defaults to the global mesh).
      data_axes: mesh axes the batch dim is split over (dp [+ sharding],
        mirroring the reference where the sharding group is also a data
        group, sharding_optimizer.py:118).
      sharding_stage: 0 none, 1 optimizer states, 2 +grad reduce-scatter,
        3 +parameters (ZeRO-3).
      recompute: full-activation recompute via jax.checkpoint.
      input_specs: optional list of PartitionSpec for step inputs; default
        shards dim 0 of every input over ``data_axes``.
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 mesh: Optional[Mesh] = None, data_axes=None,
                 sharding_stage: int = 0, recompute: bool = False,
                 input_specs=None, **kwargs):
        super().__init__(model, loss_fn, optimizer, recompute=recompute,
                         **kwargs)
        self.mesh = mesh or get_mesh()
        if data_axes is None:
            data_axes = tuple(a for a in ("dp", "sharding")
                              if self.mesh.shape.get(a, 1) > 1) or None
        self.data_axes = data_axes
        self.sharding_stage = sharding_stage
        self.recompute = recompute
        self.input_specs = input_specs

    # -- sharding layout ----------------------------------------------------
    def _layouts(self, params: dict, opt_states, buffers: dict, arrs):
        mesh = self.mesh
        named = dict(self.model.named_parameters())
        zero_axis = "sharding" if mesh.shape.get("sharding", 1) > 1 else "dp"
        zero_size = mesh.shape.get(zero_axis, 1)
        stage = self.sharding_stage

        p_shard, p_opt = {}, {}
        for n, arr in params.items():
            base = _param_sharding(named[n], mesh)
            if stage >= 3:
                p_shard[n] = _shard_over_axis(arr.shape, base.spec, zero_axis,
                                              zero_size, mesh)
            else:
                p_shard[n] = base
            if stage >= 1:
                p_opt[n] = _shard_over_axis(arr.shape, base.spec, zero_axis,
                                            zero_size, mesh)
            else:
                p_opt[n] = p_shard[n]

        def state_sharding(path_param, leaf):
            ps = p_opt[path_param]
            if leaf.shape == params[path_param].shape:
                return ps
            return _replicated(mesh)

        opt_shard = {
            n: jax.tree_util.tree_map(lambda l: state_sharding(n, l), st)
            for n, st in opt_states.items()}
        buf_shard = {n: _replicated(mesh) for n in buffers}
        if self.input_specs is not None:
            in_shard = [NamedSharding(mesh, s) for s in self.input_specs]
        else:
            data_spec = PartitionSpec(self.data_axes)
            in_shard = [
                NamedSharding(mesh, data_spec) if a.ndim >= 1
                else _replicated(mesh) for a in arrs]
        return p_shard, opt_shard, buf_shard, in_shard

    # -- step build ---------------------------------------------------------
    def _make_step(self, numerics_aux: bool = False):
        base = super()._make_step(numerics_aux=numerics_aux)
        # Pull the un-jitted python callable back out: TrainStep returns
        # jax.jit(step); we re-jit with shardings, so call its wrapped fn.
        inner = base.__wrapped__

        layouts = self._pending_layouts
        p_shard, opt_shard, buf_shard, in_shard = layouts
        repl = _replicated(self.mesh)
        donate = (0, 1, 2) if self.donate else ()
        out_shardings = (p_shard, opt_shard, buf_shard, repl)
        if numerics_aux:
            # the aux vectors are full reductions — replicated, like
            # the loss
            from paddle_tpu.framework import numerics
            out_shardings += ({k: repl for k in numerics.AUX_KEYS},)
        return jax.jit(
            inner,
            in_shardings=(p_shard, opt_shard, buf_shard, repl, repl,
                          *in_shard),
            out_shardings=out_shardings,
            donate_argnums=donate)

    def _make_multi_step(self):
        scan_fn, unrolled_fn = super()._make_multi_step()
        p_shard, opt_shard, buf_shard, in_shard = self._pending_layouts
        repl = _replicated(self.mesh)
        # stacked inputs carry a leading K (steps) axis that stays
        # unsharded; the per-step layout shifts right by one dim
        stacked_in = [NamedSharding(self.mesh,
                                    PartitionSpec(None, *s.spec))
                      for s in in_shard]
        donate = (0, 1, 2) if self.donate else ()
        shardings = dict(
            in_shardings=(p_shard, opt_shard, buf_shard, repl, repl,
                          *stacked_in),
            out_shardings=(p_shard, opt_shard, buf_shard, repl),
            donate_argnums=donate)
        return (jax.jit(scan_fn.__wrapped__, **shardings),
                jax.jit(unrolled_fn.__wrapped__, **shardings))

    def _cached_layouts(self, tag, inputs, strip_steps_axis):
        """Memoized sharding layouts for the current param/input
        structure.  Shapes/dtypes only — the device conversion of the
        input payload happens once, inside the base-class step.  With
        ``strip_steps_axis`` the layout is computed on the per-step slice
        shapes (the stacked leading K axis must not eat the data_axes
        annotation)."""
        model = self.model
        params = {n: p._data for n, p in model.named_parameters()}
        buffers = {n: b._data for n, b in model.named_buffers()
                   if b is not None}
        if self._opt_states is None:
            self._opt_states = self.optimizer.functional_init_states(params)
        avals = [(tuple(i._data.shape), i._data.dtype)
                 if hasattr(i, "_data") else
                 (np.shape(i), np.asarray(i).dtype) for i in inputs]
        slices = [jax.ShapeDtypeStruct(s[1:] if strip_steps_axis else s, d)
                  for s, d in avals]
        lkey = (tag, tuple(params),
                tuple((s, str(d)) for s, d in avals),
                self.sharding_stage)
        cache = getattr(self, "_layout_cache", None)
        if cache is None:
            cache = self._layout_cache = {}
        if lkey not in cache:
            cache[lkey] = self._layouts(params, self._opt_states, buffers,
                                        slices)
        return cache[lkey]

    def _resolve_layouts(self, tag, inputs):
        # place model params on the mesh once (parity: the reference's
        # startup-program broadcast of initial params, sharding_optimizer's
        # param→device assignment)
        self._pending_layouts = self._cached_layouts(tag, inputs,
                                                     tag == "multi")

    # -- introspection (compile-only test tier) -----------------------------
    def lower_hlo(self, *inputs) -> str:
        """Compile the step and return optimized HLO text — the analogue of
        the reference's meta-optimizer tests that inspect the rewritten
        Program for inserted collective ops (SURVEY.md §4)."""
        model = self.model
        params = {n: p._data for n, p in model.named_parameters()}
        buffers = {n: b._data for n, b in model.named_buffers()
                   if b is not None}
        if self._opt_states is None:
            self._opt_states = self.optimizer.functional_init_states(params)
        arrs = [i._data if hasattr(i, "_data") else jnp.asarray(i)
                for i in inputs]
        self._pending_layouts = self._layouts(params, self._opt_states,
                                              buffers, arrs)
        fn = self._make_step()
        key = jax.random.PRNGKey(0)
        lr = jnp.float32(self.optimizer.get_lr())
        lowered = fn.lower(params, self._opt_states, buffers, key, lr, *arrs)
        return lowered.compile().as_text()

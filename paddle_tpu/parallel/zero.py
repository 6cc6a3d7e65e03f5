"""ZeRO-style sharded weight update over the ``dp`` axis.

Reference point: *Automatic Cross-Replica Sharding of Weight Update in
Data-Parallel Training* (PAPERS.md) — in plain data parallelism every
replica all-reduces full-width gradients and then redundantly applies
the SAME optimizer update to the SAME full parameter set, holding a full
copy of the optimizer moments.  :class:`ShardedUpdateTrainStep` removes
both redundancies inside one fused XLA step:

1. **reduce-scatter** — the backward runs under ``shard_map`` on each
   replica's batch shard; each gradient leaf is flattened, padded to a
   dp-divisible length and reduce-scattered, so a replica receives only
   the summed 1/N chunk it owns;
2. **sharded update** — the optimizer update (clip, weight decay,
   moments) runs on the owned chunk only; the moments live permanently
   as dp-sharded flat vectors, so optimizer-state bytes per replica
   drop to ~1/N (+ replicated scalars like Adam's beta powers);
3. **all-gather** — the updated parameter chunks are gathered back to
   full replicated parameters for the next forward.

Wire quantization (*EQuARX*, PAPERS.md) layers on top via the shared
helpers in ``distributed/wire.py`` — the same encode/decode the PS
transport ships.  ``wire_dtype``:

- ``"f32"`` — exact fallback, pinned by parity tests: the trajectory is
  element-for-element the replicated data-parallel trajectory (the
  update math is elementwise, so sharding it changes nothing);
- ``"bf16"`` (FLAGS_zero_wire_dtype default) — both legs ship bf16, half
  the f32 bytes; the reduce-scatter becomes quantize → ``all_to_all`` →
  dequantize → local sum (a collective cannot sum encoded payloads);
- ``"int8"`` — quarter the bytes + one f32 scale per ``chunk`` elements
  (symmetric per-chunk scale, same discipline as the PS int8 wire);
- ``"int4"`` — eighth the bytes: two nibbles per byte + one f32 scale
  per chunk (PR 19's packed codec, shared with the PS wire).

``FLAGS_zero_ring_collectives`` (or ``ring=True``) swaps both legs for
the fused ring in ``parallel/ring.py``: quantize/dequantize overlapped
with the neighbor ``ppermute`` instead of a bandwidth-serial codec
prologue around ``all_to_all``/``all_gather``.  Analytic wire bytes
are identical (``(dp-1)`` encoded chunks per leg per replica); the f32
wire keeps the native XLA collectives, so the exact leg stays
bitwise-identical with the ring flag on or off.

Observability: a ``zero.step`` tracer span wraps the dispatch with
``zero.reduce_scatter`` / ``zero.update`` / ``zero.all_gather`` child
marker spans carrying the ANALYTIC per-replica wire/state bytes (the
step is one fused XLA computation — per-leg device timing is not
observable from the host, but byte accounting is exact);
``opt_state_bytes_per_replica`` and ``zero_collective_bytes_per_step``
export as monitor gauges; the MemoryTracker hook attributes
params/opt_state/buffers.  The ``zero.collective`` chaos point fires
once per collective leg at the dispatch head — an injected error is
retried (bounded) before dispatch, so a dropped collective is re-issued
deterministically.

Interop: the ``TrainStep`` surface (``model``, ``optimizer``,
``_opt_states``, callable → loss Tensor) is preserved, so
``ResilientTrainStep`` NaN skip-and-restore and
``distributed/checkpoint.py`` save/restore work unchanged;
``_opt_states`` is a property whose setter re-places restored host
arrays onto the dp sharding.  Checkpoints record shard bookkeeping
(:meth:`ShardedUpdateTrainStep.checkpoint_extra_meta`) so
``load_train_state`` can reshard moments onto a DIFFERENT dp world size
— and a replicated ``TrainStep`` checkpoint adopts into a sharded step
(and vice versa) by flatten/pad/strip on the same bookkeeping.

Scope: exact for elementwise optimizers (SGD/Momentum/Adam/AdamW —
everything ``functional_update`` supports); a global-norm grad clip is
computed shard-locally and ``psum``-ed (same math, reduction order may
differ in the last ulp).  Norm-PER-PARAMETER optimizers (LARS) would
need an extra per-leaf psum and are not sharded exactly — use the
replicated step for those.
"""
from __future__ import annotations

import math
import time
from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.core import Tensor
from paddle_tpu.distributed.wire import (COLLECTIVE_WIRE_DTYPES,
                                         dequantize_rows_traced,
                                         normalize_wire,
                                         quantize_rows_traced, wire_nbytes)
from paddle_tpu.framework import chaos, monitor, numerics
from paddle_tpu.framework.observability import flight, tracer
from paddle_tpu.nn.layer.layers import Layer
from paddle_tpu.parallel.dp_meta import _loss_closure, _require_pure_dp
from paddle_tpu.parallel.mesh import (get_mesh, manual_region,
                                      shard_map_compat)
from paddle_tpu.tensor.random import default_generator

__all__ = ["ShardSpec", "ShardedUpdateTrainStep", "build_shard_specs"]


class ShardSpec(NamedTuple):
    """Flat-shard bookkeeping for one parameter leaf: logical ``size``,
    ``padded`` length (dp·chunk-divisible) and per-replica
    ``shard_len = padded // dp``.  Reused by checkpointing to reshard
    moments across dp world sizes."""
    size: int
    padded: int
    shard_len: int


def build_shard_specs(params: Dict[str, jnp.ndarray], dp: int,
                      chunk: int = 256) -> Dict[str, ShardSpec]:
    """Per-leaf :class:`ShardSpec` map: every leaf flattens to ``size``
    and pads up to a multiple of ``dp * chunk`` (chunk-divisible shards
    keep the int8 per-chunk scales aligned for every wire dtype, so the
    checkpoint layout never depends on the wire)."""
    specs = {}
    q = dp * chunk
    for n, p in params.items():
        size = int(np.prod(p.shape)) if p.ndim else 1
        padded = int(math.ceil(size / q) * q)
        specs[n] = ShardSpec(size=size, padded=padded,
                             shard_len=padded // dp)
    return specs


class ShardedUpdateTrainStep:
    """Drop-in ``TrainStep`` variant with a dp-sharded weight update and
    (optionally) quantized collectives — see the module docstring.

    API-compatible with ``jit.TrainStep`` / the ``dp_meta`` variants:
    construct with ``(model, loss_fn, optimizer)``, call with the global
    batch (sharded over ``dp`` internally), read back the loss Tensor.
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 mesh: Optional[Mesh] = None, wire_dtype: Optional[str] = None,
                 chunk: int = 256, amp_level=None, amp_dtype="bfloat16",
                 recompute: bool = False, donate: bool = True,
                 collective_retries: int = 2,
                 ring: Optional[bool] = None):
        from paddle_tpu.framework.flags import flag
        from paddle_tpu.optimizer import LarsMomentum
        if isinstance(optimizer, LarsMomentum):
            # LARS computes a trust ratio from per-PARAMETER norms; on a
            # 1/dp chunk those norms are wrong and training silently
            # diverges — fail loudly instead (module docstring: use the
            # replicated step for norm-per-parameter optimizers)
            raise TypeError(
                "ShardedUpdateTrainStep cannot shard a norm-per-"
                "parameter optimizer (LarsMomentum): the trust-ratio "
                "norms would be computed over 1/dp chunks.  Use the "
                "replicated TrainStep/CompressedAllReduceTrainStep.")
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh or get_mesh()
        _require_pure_dp(self.mesh, "the sharded weight update")
        self.dp = self.mesh.shape.get("dp", 1)
        if wire_dtype is None:
            wire_dtype = flag("zero_wire_dtype")
        self.wire = normalize_wire(wire_dtype,
                                   known=COLLECTIVE_WIRE_DTYPES)
        # fused ring legs (parallel/ring.py): quant/dequant overlapped
        # with the neighbor ppermute; f32 stays on the native ops
        self.ring = bool(flag("zero_ring_collectives")
                         if ring is None else ring)
        if int(chunk) < 1:
            raise ValueError("chunk must be >= 1")
        self.chunk = int(chunk)
        self.amp_level = amp_level
        self.amp_dtype = jnp.bfloat16 if str(amp_dtype) in (
            "bfloat16", "bf16") else jnp.float16
        self.recompute = recompute
        self.donate = donate
        self.collective_retries = int(collective_retries)
        self._specs: Optional[Dict[str, ShardSpec]] = None
        self._opt_shards: Optional[dict] = None
        self._fns: Dict[bool, Callable] = {}   # keyed by numerics armed
        self._last_call_start: Optional[float] = None

    # -- sharded optimizer state --------------------------------------------
    def _sharding(self):
        return NamedSharding(self.mesh, P("dp"))

    def _place_shard(self, arr) -> jax.Array:
        return jax.device_put(jnp.asarray(arr), self._sharding())

    def _ensure_state(self):
        if self._opt_shards is not None:
            return
        params = {n: p._data for n, p in self.model.named_parameters()}
        for n, p in params.items():
            if not jnp.issubdtype(p.dtype, jnp.floating):
                raise TypeError(
                    f"sharded update needs floating params; {n!r} is "
                    f"{p.dtype}")
        self._specs = build_shard_specs(params, self.dp, self.chunk)
        shards = {}
        for n, p in params.items():
            spec = self._specs[n]
            flat = jnp.pad(p.reshape(-1), (0, spec.padded - spec.size))
            slots = {}
            # init on the padded flat view: every in-tree optimizer's
            # init_state is shape-elementwise (zeros/ones/scalars), so
            # the flat init equals the flattened replicated init
            for k, v in self.optimizer.init_state(flat).items():
                v = jnp.asarray(v)
                if v.ndim == 1 and v.shape[0] == spec.padded:
                    slots[k] = self._place_shard(v)
                elif v.ndim == 0:
                    slots[k] = v
                else:
                    raise TypeError(
                        f"optimizer slot {k!r} for {n!r} has shape "
                        f"{v.shape} — neither elementwise nor scalar; "
                        "the sharded update cannot place it")
            shards[n] = slots
        self._opt_shards = shards
        monitor.stat_set("opt_state_bytes_per_replica",
                         self.opt_state_bytes_per_replica())

    @property
    def _opt_states(self):
        """The dp-sharded moments as a plain pytree of global arrays —
        the ``TrainStep._opt_states`` surface ResilientTrainStep
        snapshots and ``save_train_state`` persists (each moment leaf
        saves as one file per dp shard)."""
        return self._opt_shards

    @_opt_states.setter
    def _opt_states(self, tree):
        """Restore path (ResilientTrainStep.restore / checkpoint load):
        re-place every padded flat vector onto the dp sharding — host
        numpy copies come back as properly sharded device arrays."""
        if tree is None:
            self._opt_shards = None
            return

        def place(v):
            v = jnp.asarray(v)
            return self._place_shard(v) if v.ndim == 1 else v
        self._opt_shards = jax.tree_util.tree_map(place, tree)

    def opt_state_bytes_per_replica(self) -> int:
        """Measured bytes of optimizer state ONE replica holds: sharded
        vector slots count 1/dp of their global bytes, replicated
        scalars count whole."""
        self._ensure_state()
        total = 0
        for slots in self._opt_shards.values():
            for v in slots.values():
                n = int(v.nbytes)
                total += n // self.dp if v.ndim == 1 else n
        return total

    def collective_wire_bytes(self, wire: Optional[str] = None
                              ) -> Dict[str, int]:
        """Analytic per-replica wire bytes per step for each collective
        leg (deterministic — the op_bench gate keys off these).  Both
        reduce-scatter and all-gather move ``(dp-1)/dp`` of every padded
        leaf through each replica, encoded per :attr:`wire` (or the
        ``wire`` override — pure shape math, e.g. for a what-if ratio
        against f32 without building a second step)."""
        if self._specs is None:
            params = {n: p._data
                      for n, p in self.model.named_parameters()}
            self._specs = build_shard_specs(params, self.dp, self.chunk)
        wire = self.wire if wire is None else normalize_wire(
            wire, known=COLLECTIVE_WIRE_DTYPES)
        rs = ag = 0
        for spec in self._specs.values():
            per_chunk = wire_nbytes(spec.shard_len, wire, row=self.chunk)
            rs += per_chunk * (self.dp - 1)
            ag += per_chunk * (self.dp - 1)
        return {"reduce_scatter": rs, "all_gather": ag}

    # -- compiled step ------------------------------------------------------
    def _build_mapped(self, n_inputs, numerics_aux: bool = False):
        from paddle_tpu.parallel.ring import (ring_all_gather,
                                              ring_reduce_scatter)
        mesh, dp, chunk, wire = self.mesh, self.dp, self.chunk, self.wire
        use_ring = self.ring
        specs = self._specs
        opt = self.optimizer
        names = list(specs)
        loss_from = _loss_closure(self.model, self.loss_fn, self.amp_level,
                                  self.amp_dtype, self.recompute)
        grad_clip = getattr(opt, "_grad_clip", None)

        @jax.named_scope("grad_exchange")
        def reduce_scatter(gflat):
            """(padded,) local grad -> (shard_len,) owned mean chunk."""
            if use_ring:
                # fused ring (parallel/ring.py): encode/accumulate per
                # hop; f32 dispatches to the same psum_scatter below
                return ring_reduce_scatter(gflat, "dp", axis_size=dp,
                                           chunk=chunk, wire=wire) / dp
            if wire == "f32":
                return jax.lax.psum_scatter(
                    gflat, "dp", scatter_dimension=0, tiled=True) / dp
            rows = gflat.reshape(dp, -1, chunk)
            bufs = quantize_rows_traced(rows, wire)
            ex = tuple(jax.lax.all_to_all(b, "dp", split_axis=0,
                                          concat_axis=0) for b in bufs)
            return dequantize_rows_traced(ex, wire).sum(0).reshape(-1) / dp

        def all_gather(shard):
            """(shard_len,) updated chunk -> (padded,) full leaf.  The
            quantized leg dequantizes EVERY chunk — including the
            locally owned one — so all replicas hold bit-identical
            parameters."""
            if use_ring:
                return ring_all_gather(shard, "dp", axis_size=dp,
                                       chunk=chunk, wire=wire)
            if wire == "f32":
                return jax.lax.all_gather(shard, "dp", tiled=True)
            rows = shard.reshape(-1, chunk)
            bufs = quantize_rows_traced(rows, wire)
            got = tuple(jax.lax.all_gather(b, "dp") for b in bufs)
            return dequantize_rows_traced(got, wire).reshape(-1)

        def local(params, opt_sh, buffers, key, lr, *inputs):
            (loss, new_buffers), grads = jax.value_and_grad(
                lambda p: loss_from(p, buffers, key, list(inputs)),
                has_aux=True)(params)
            idx = jax.lax.axis_index("dp")
            gshards, pshards = {}, {}
            for n in names:
                spec = specs[n]
                gflat = jnp.pad(grads[n].reshape(-1),
                                (0, spec.padded - spec.size))
                gshards[n] = reduce_scatter(gflat).astype(grads[n].dtype)
                pflat = jnp.pad(params[n].reshape(-1),
                                (0, spec.padded - spec.size))
                pshards[n] = jax.lax.dynamic_slice(
                    pflat, (idx * spec.shard_len,), (spec.shard_len,))
            # numerics view over the PRE-clip grads (same point in the
            # update the replicated TrainStep samples at, so the
            # exported global grad norm is parity-comparable)
            gshards_preclip = dict(gshards) if numerics_aux else None
            # clip + sharded update + gather of the updated parameters:
            # the ``optimizer`` region of the device trace
            with jax.named_scope("optimizer"):
                if grad_clip is not None and hasattr(grad_clip,
                                                     "functional_clip"):
                    if hasattr(grad_clip, "clip_norm"):
                        # global-norm clip over SHARDED grads: shard-local
                        # sum of squares + psum == the replicated global
                        # norm (padding contributes exact zeros)
                        sq = sum(jnp.sum(g.astype(jnp.float32) ** 2)
                                 for g in gshards.values())
                        gn = jnp.sqrt(jax.lax.psum(sq, "dp"))
                        cscale = jnp.minimum(
                            grad_clip.clip_norm / jnp.maximum(gn, 1e-12),
                            1.0)
                        gshards = {n: (g * cscale).astype(g.dtype)
                                   for n, g in gshards.items()}
                    else:              # elementwise clip: shard-local
                        gshards = grad_clip.functional_clip(gshards)
                new_pshards, new_states = opt.functional_update(
                    pshards, gshards, opt_sh, lr=lr)
                new_params = {}
                for n in names:
                    spec = specs[n]
                    full = all_gather(
                        new_pshards[n].astype(params[n].dtype))
                    new_params[n] = full[:spec.size].reshape(
                        params[n].shape).astype(params[n].dtype)
            # float buffers (BN stats) average over replicas so every
            # replica leaves the step with identical state
            new_buffers = {
                n: (jax.lax.pmean(b.astype(jnp.float32),
                                  "dp").astype(b.dtype)
                    if jnp.issubdtype(b.dtype, jnp.floating) else b)
                for n, b in new_buffers.items()}
            loss_rep = jax.lax.pmean(loss, "dp")
            if numerics_aux:
                # shard-local sum-of-squares / non-finite counts psum-ed
                # over dp, max-abs pmax-ed (the global-norm clip idiom
                # above): every replica leaves with the GLOBAL per-leaf
                # vectors, so the aux is replicated (P() out spec)
                aux = numerics.compute_aux(
                    gshards_preclip, pshards, new_pshards, loss_rep,
                    axis_name="dp")
                return (new_params, new_states, new_buffers, loss_rep,
                        aux)
            return (new_params, new_states, new_buffers, loss_rep)

        opt_spec = jax.tree_util.tree_map(
            lambda v: P("dp") if v.ndim == 1 else P(), self._opt_shards)
        in_specs = (P(), opt_spec, P(), P(), P()) + (P("dp"),) * n_inputs
        out_specs = (P(), opt_spec, P(), P())
        if numerics_aux:
            out_specs = out_specs + (
                {k: P() for k in numerics.AUX_KEYS},)
        return shard_map_compat(local, mesh=mesh, in_specs=in_specs,
                                out_specs=out_specs)

    def _build(self, n_inputs, numerics_aux: bool = False):
        mapped = self._build_mapped(n_inputs, numerics_aux=numerics_aux)
        donate = (0, 1, 2) if self.donate else ()
        return jax.jit(mapped, donate_argnums=donate)

    def analyze(self, *example_inputs, **analyze_kwargs):
        """Static analysis of the shard-mapped step (framework.analysis
        jaxpr + PTA5xx collective passes) on aval stand-ins — no device
        step runs.  The mapped function is traced UNJITTED so the
        passes see the real collective equations (reduce-scatter /
        all-gather legs, the clip psum), with input AND output labels
        threaded through so a PTA501 finding names the parameter leaf
        — the same leaf the runtime replica-parity probe
        (``parallel/parity.py``) would name."""
        import jax.tree_util as jtu

        from paddle_tpu.framework import numerics
        from paddle_tpu.framework.analysis import analyze_jaxpr
        self._ensure_state()
        params = {n: p._data for n, p in self.model.named_parameters()}
        buffers = {n: b._data for n, b in self.model.named_buffers()
                   if b is not None}
        aval = lambda a: a if isinstance(a, jax.ShapeDtypeStruct) \
            else jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)  # noqa:E731
        arrs = [i._data if isinstance(i, Tensor)
                else i if isinstance(i, jax.ShapeDtypeStruct)
                else jnp.asarray(i)
                for i in example_inputs]
        tree_avals = [jtu.tree_map(aval, t)
                      for t in (params, self._opt_shards, buffers)]
        labels = []
        for prefix, tree in zip(("params", "opt", "buffers"), tree_avals):
            flat, _ = jtu.tree_flatten_with_path(tree)
            labels += [prefix + jtu.keystr(path) for path, _ in flat]
        n_donated = len(labels) if self.donate else 0
        labels += ["rng_key", "lr"] + [f"input[{i}]"
                                       for i in range(len(arrs))]
        # output labels mirror the step's return structure: (new_params,
        # new_states, new_buffers, loss[, numerics aux]) — dict trees
        # flatten sorted, exactly as the traced outputs do.  Param
        # outputs carry the BARE leaf name (e.g. `fc1.weight`), the
        # name the runtime replica-parity probe uses too
        out_labels = [n for n in sorted(params)]
        for prefix, tree in zip(("opt", "buffers"),
                                (tree_avals[1], tree_avals[2])):
            flat, _ = jtu.tree_flatten_with_path(tree)
            out_labels += [prefix + jtu.keystr(path) for path, _ in flat]
        out_labels += ["loss"]
        armed = numerics.enabled()
        if armed:
            out_labels += [f"numerics.{k}" for k in
                           sorted(numerics.AUX_KEYS)]
        mapped = self._build_mapped(len(arrs), numerics_aux=armed)
        key_aval = jax.ShapeDtypeStruct((2,), jnp.uint32)
        lr_aval = jax.ShapeDtypeStruct((), jnp.float32)
        closed = jax.make_jaxpr(mapped)(
            *tree_avals, key_aval, lr_aval, *[aval(x) for x in arrs])
        return analyze_jaxpr(
            closed, name="ShardedUpdateTrainStep", invar_labels=labels,
            outvar_labels=out_labels,
            donate_argnums=tuple(range(n_donated)), **analyze_kwargs)

    # -- chaos --------------------------------------------------------------
    def _collective_guard(self):
        """Consult the ``zero.collective`` fault point once per leg at
        the dispatch head.  The legs are host-issued parts of one pure
        computation, so an injected drop is simply retried (bounded)
        BEFORE dispatch — deterministic, no state was consumed."""
        for leg in ("reduce_scatter", "all_gather"):
            attempt = 0
            while True:
                try:
                    chaos.fault_point("zero.collective",  # pta: disable=PTA301 (bounded pre-dispatch retry below)
                                      meta={"leg": leg})
                    break
                except chaos.InjectedFault:
                    attempt += 1
                    monitor.stat_add("zero_collective_retries_total")
                    if attempt > self.collective_retries:
                        flight.record("zero.collective_failed",
                                      severity="error", leg=leg,
                                      attempts=attempt)
                        raise

    # -- dispatch -----------------------------------------------------------
    def __call__(self, *inputs):
        from paddle_tpu.framework import health
        t_start = time.perf_counter()
        model = self.model
        named_params = {n: p for n, p in model.named_parameters()}
        named_buffers = {n: b for n, b in model.named_buffers()
                         if b is not None}
        params = {n: p._data for n, p in named_params.items()}
        buffers = {n: b._data for n, b in named_buffers.items()}
        self._ensure_state()
        arrs = [i._data if isinstance(i, Tensor) else jnp.asarray(i)
                for i in inputs]
        armed = numerics.enabled()
        fn = self._fns.get(armed)
        if fn is None:
            fn = self._fns[armed] = self._build(len(arrs),
                                                numerics_aux=armed)
        key = default_generator.split()
        lr = jnp.float32(self.optimizer.get_lr())
        bytes_ = self.collective_wire_bytes()
        opt_bytes = monitor.get_stat("opt_state_bytes_per_replica")
        with tracer.start_span(
                "zero.step",
                attrs={"step": int(self.optimizer._global_step),
                       "wire": self.wire, "dp": self.dp,
                       "ring": self.ring}):
            self._collective_guard()
            with manual_region():    # model-internal constrain() no-ops
                out = fn(params, self._opt_shards, buffers, key, lr,
                         *arrs)
            if armed:
                new_params, self._opt_shards, new_buffers, loss, aux = out
                rec = numerics.NumericsRecord(
                    list(self._specs), aux,
                    step=int(self.optimizer._global_step))
                numerics.publish(rec)
                self.last_numerics = rec
            else:
                new_params, self._opt_shards, new_buffers, loss = out
            # leg marker spans: exact byte accounting for the fused
            # step's collectives.  Per-leg device timing is not
            # separable on the host, so under an armed tracer the two
            # wire legs fence the async dispatch instead — the
            # reduce-scatter span waits out the sharded opt state
            # (grad RS + update), the all-gather span the re-assembled
            # params — and carry an explicit `category` so the wait
            # claims blame as `collective` time.  Untraced steps keep
            # the async dispatch (zero-duration markers, no fence).
            traced = tracer.enabled
            with tracer.start_span("zero.reduce_scatter",
                                   attrs={"category": "collective",
                                          "wire": self.wire,
                                          "ring": self.ring,
                                          "bytes": bytes_[
                                              "reduce_scatter"]}):
                if traced:
                    jax.block_until_ready(self._opt_shards)
            with tracer.start_span("zero.update",
                                   attrs={"opt_state_bytes_per_replica":
                                          opt_bytes}):
                pass
            with tracer.start_span("zero.all_gather",
                                   attrs={"category": "collective",
                                          "wire": self.wire,
                                          "ring": self.ring,
                                          "bytes": bytes_["all_gather"]}):
                if traced:
                    jax.block_until_ready(new_params)
        for n, p in named_params.items():
            p._data = new_params[n]
        for n, b in named_buffers.items():
            b._data = new_buffers[n]
        self.optimizer._global_step += 1
        per_step = bytes_["reduce_scatter"] + bytes_["all_gather"]
        monitor.stat_set("zero_collective_bytes_per_step", per_step)
        monitor.stat_add("zero_collective_bytes_total", per_step)
        # a step: from one call's start to the next's (jit.TrainStep)
        before, self._last_call_start = self._last_call_start, t_start
        if before is not None:
            step_ms = (t_start - before) * 1e3
            monitor.observe("train_step_ms", step_ms)
            health.observe("train_step_ms", step_ms)
        monitor.stat_add("train_steps_total")
        health.maybe_sample_memory(lambda: {
            "params": sum(int(p._data.nbytes)
                          for p in named_params.values()),
            "opt_state": self.opt_state_bytes_per_replica(),
            "buffers": sum(int(b._data.nbytes)
                           for b in named_buffers.values())})
        # replica-parity probe (FLAGS_replica_parity): hash-agreement
        # over the replicated leaves every K steps; disarmed = one flag
        # lookup, and the step's own compiled fn is untouched either way
        from paddle_tpu.parallel import parity
        parity.maybe_observe(self, mesh=self.mesh)
        return Tensor(loss)

    # -- checkpoint interop -------------------------------------------------
    def checkpoint_extra_meta(self) -> dict:
        """Shard bookkeeping stamped into checkpoint metadata so a
        restore onto a DIFFERENT dp world size can strip the save-time
        padding before re-padding for its own (see
        :meth:`adopt_opt_state`)."""
        self._ensure_state()
        return {"zero": {
            "dp": self.dp, "chunk": self.chunk, "wire": self.wire,
            "leaves": {n: {"size": s.size, "padded": s.padded}
                       for n, s in self._specs.items()}}}

    def adopt_opt_state(self, tree, zero_meta: Optional[dict] = None):
        """Install checkpointed optimizer moments, resharding as needed.
        Accepts flat padded vectors from a zero checkpoint (any save-time
        dp — ``zero_meta["leaves"]`` names the logical sizes) or
        param-shaped leaves from a replicated ``TrainStep`` checkpoint;
        scalars pass through replicated."""
        self._ensure_state()
        saved = (zero_meta or {}).get("leaves", {})
        new = {}
        for n, slots in tree.items():
            if n not in self._specs:
                raise ValueError(f"checkpoint moment {n!r} has no "
                                 "matching parameter")
            spec = self._specs[n]
            out = {}
            for k, v in slots.items():
                arr = np.asarray(v)
                if arr.ndim == 0:
                    out[k] = jnp.asarray(arr)
                    continue
                flat = arr.reshape(-1)
                meta_pad = saved.get(n, {}).get("padded")
                if flat.size == spec.size:
                    pass                     # replicated / logical leaf
                elif flat.size in (meta_pad, spec.padded):
                    flat = flat[:spec.size]  # strip save-time padding
                else:
                    raise ValueError(
                        f"moment {n!r}/{k!r} has {flat.size} elements; "
                        f"expected {spec.size} (logical) or a padded "
                        f"length ({meta_pad or spec.padded})")
                out[k] = self._place_shard(
                    np.pad(np.asarray(flat),
                           (0, spec.padded - spec.size)))
            new[n] = out
        self._opt_shards = new
        monitor.stat_set("opt_state_bytes_per_replica",
                         self.opt_state_bytes_per_replica())

    def load_checkpoint_state(self, state: dict,
                              zero_meta: Optional[dict] = None):
        """Install a full checkpoint ``state`` tree (params, buffers,
        opt_states, global_step) — ``checkpoint.load_train_state``'s
        hook for sharded steps."""
        model = self.model
        for n, p in model.named_parameters():
            p._data = jnp.asarray(state["params"][n]).astype(
                p._data.dtype)
        for n, b in model.named_buffers():
            if b is not None and n in state.get("buffers", {}):
                b._data = jnp.asarray(state["buffers"][n])
        # params first: shard specs derive from the (restored) params
        self._specs = None
        self._opt_shards = None
        self._ensure_state()
        opt_states = state.get("opt_states") or {}
        if opt_states:
            self.adopt_opt_state(opt_states, zero_meta)
        self.optimizer._global_step = int(
            np.asarray(state.get("global_step", 0)))
        return state

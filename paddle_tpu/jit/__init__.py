"""paddle_tpu.jit — static capture, the TPU-native replacement for the
reference's entire static-graph machinery.

What the reference does with @to_static (AST rewriting in
dygraph_to_static/program_translator.py:756 → ProgramDesc → Executor), this
module does with functional capture: a Layer's forward becomes a pure jax
function over (params, buffers, rng_key, inputs) and compiles ONCE per input
signature (cache ≈ the reference's ExecutorCache).  Three layers:

- ``to_static(layer_or_fn)`` — forward capture.  The compiled forward enters
  the eager tape as a SINGLE node (jax.vjp of the whole jitted function), so
  dygraph-style ``loss.backward()`` still works but forward+backward are two
  fused XLA executables instead of per-op dispatch.
- ``TrainStep(model, loss_fn, optimizer)`` — whole-step capture: forward +
  backward (jax.grad) + optimizer update in ONE XLA computation with buffer
  donation; the idiomatic TPU training loop and the unit the Fleet strategies
  transform (sharding/remat/accumulation are applied here).
- ``save/load`` — jit.save analogue: state_dict + serialized StableHLO export.

Stateful RNG (dropout) threads through capture: a fresh key is passed per
call and installed into the global Generator for the trace, so randomness
varies per step without recompilation.
"""
from __future__ import annotations

import functools
import os
import pickle
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import Parameter, Tensor, apply, no_grad
from paddle_tpu.framework.resilient import ResilientTrainStep  # noqa: F401
from paddle_tpu.nn.layer.layers import Layer
from paddle_tpu.tensor.random import default_generator

__all__ = ["to_static", "TrainStep", "ResilientTrainStep", "save", "load",
           "not_to_static", "TranslatedLayer"]


def _sig_of(args) -> tuple:
    sig = []
    for a in args:
        if isinstance(a, Tensor):
            sig.append(("T", tuple(a.shape), str(a.dtype)))
        elif isinstance(a, (jnp.ndarray, np.ndarray)):
            sig.append(("A", tuple(a.shape), str(a.dtype)))
        else:
            sig.append(("S", a))
    return tuple(sig)


class _GeneratorKeyGuard:
    """Install a (possibly traced) key into the global Generator for the
    duration of a trace, so F.dropout etc. consume traced randomness."""

    def __init__(self, key):
        self.key = key

    def __enter__(self):
        self._saved = default_generator._key
        default_generator._key = self.key
        return self

    def __exit__(self, *exc):
        default_generator._key = self._saved
        return False


class StaticFunction:
    """Compiled forward (≈ StaticFunction in
    dygraph_to_static/program_translator.py)."""

    def __init__(self, function: Callable, layer: Optional[Layer] = None,
                 input_spec=None, jit_kwargs: Optional[dict] = None):
        self._function = function
        self._layer = layer
        self._input_spec = input_spec
        self._cache: Dict[tuple, Callable] = {}
        self._jit_kwargs = jit_kwargs or {}
        functools.update_wrapper(self, function)

    @property
    def forward(self):
        return self

    def concrete_program(self):
        return None

    def analyze(self, *example_inputs, **analyze_kwargs):
        """Static analysis of this capture (framework.analysis jaxpr
        passes): abstract-trace the forward on aval stand-ins of
        ``example_inputs`` and return the diagnostic Report — dtype
        upcasts, dead params, host callbacks, baked constants, cost
        ranking — without spending a device step."""
        from paddle_tpu.framework.analysis import (analyze_callable,
                                                   analyze_model)
        if self._layer is not None:
            return analyze_model(self._layer, *example_inputs,
                                 name=type(self._layer).__name__,
                                 **analyze_kwargs)
        return analyze_callable(self._function, *example_inputs,
                                tensors=True,
                                name=self._function.__name__,
                                **analyze_kwargs)

    def _build(self, sig, n_params, n_buffers, param_names, buffer_names,
               static_args, static_kwargs, out_meta):
        layer = self._layer
        fn = self._function

        def pure(key, *flat):
            params = dict(zip(param_names, flat[:n_params]))
            buffers = dict(zip(
                buffer_names, flat[n_params:n_params + n_buffers]))
            arr_inputs = flat[n_params + n_buffers:]
            tensors = []
            it = iter(arr_inputs)
            for kind, spec in static_args:
                if kind == "tensor":
                    t = Tensor(next(it))
                    t.stop_gradient = True
                    tensors.append(t)
                else:
                    tensors.append(spec)
            with _GeneratorKeyGuard(key):
                if layer is not None:
                    with layer._swapped_state(params, buffers):
                        with no_grad():
                            out = fn(*tensors, **static_kwargs)
                        new_buffers = [
                            b._data for _, b in layer.named_buffers()
                            if b is not None]
                else:
                    with no_grad():
                        out = fn(*tensors, **static_kwargs)
                    new_buffers = []
            flat_out, treedef = jax.tree_util.tree_flatten(
                out, is_leaf=lambda x: isinstance(x, Tensor))
            out_meta.append(treedef)
            arrs = tuple(o._data if isinstance(o, Tensor) else jnp.asarray(o)
                         for o in flat_out)
            return arrs + tuple(new_buffers)

        return jax.jit(pure, **self._jit_kwargs)

    def __call__(self, *args, **kwargs):
        layer = self._layer
        if layer is not None:
            named_params = [(n, p) for n, p in layer.named_parameters()]
            named_buffers = [(n, b) for n, b in layer.named_buffers()
                             if b is not None]
        else:
            named_params, named_buffers = [], []
        param_names = [n for n, _ in named_params]
        buffer_names = [n for n, _ in named_buffers]

        static_args = []
        tensor_args = []
        for a in args:
            if isinstance(a, Tensor):
                static_args.append(("tensor", None))
                tensor_args.append(a)
            elif isinstance(a, (np.ndarray,)):
                t = Tensor(a)
                static_args.append(("tensor", None))
                tensor_args.append(t)
            else:
                static_args.append(("static", a))

        training = layer.training if layer is not None else False

        def _hashable(v):
            if isinstance(v, (list,)):
                return tuple(_hashable(x) for x in v)
            if isinstance(v, dict):
                return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
            try:
                hash(v)
                return v
            except TypeError:
                return repr(v)
        sig = (_sig_of([p for _, p in named_params]) +
               _sig_of([b for _, b in named_buffers]) +
               _sig_of(tensor_args) +
               tuple(_hashable(s) for k, s in static_args if k == "static") +
               (training,
                tuple(sorted((k, _hashable(v)) for k, v in kwargs.items()))))

        from paddle_tpu.framework import health
        site = f"to_static:{getattr(self._function, '__name__', '?')}"
        entry = self._cache.get(sig)
        compile_cause = None
        if entry is None:
            # a cache miss is an XLA compile: attribute the cause by
            # diffing against the cached signatures BEFORE inserting
            compile_cause = health.classify_recompile(
                sig, list(self._cache))
            out_meta: list = []
            jitted = self._build(sig, len(named_params), len(named_buffers),
                                 param_names, buffer_names, static_args,
                                 kwargs, out_meta)
            entry = {"fn": jitted, "out_meta": out_meta}
            self._cache[sig] = entry
        else:
            health.note_cache_hit(site)

        key = default_generator.split()
        n_p, n_b = len(named_params), len(named_buffers)

        param_tensors = [p for _, p in named_params]
        buffer_tensors = [b for _, b in named_buffers]
        all_inputs = param_tensors + buffer_tensors + tensor_args

        # run through the tape: one node for the whole compiled block.
        # On a cache miss the first dispatch of the fresh executable
        # (trace+compile+run) is timed into compile_ms and spanned as
        # jit.compile; on a hit timed_compile is a no-op context.
        fn = entry["fn"]
        with health.timed_compile(site, compile_cause):
            outs = apply(lambda *arrs: fn(arrs[0], *arrs[1:]), Tensor(key),
                         *all_inputs, nondiff=(0,) + tuple(
                             i + 1 for i in range(n_p, n_p + n_b)),
                         name="to_static")
        treedef = entry["out_meta"][0]
        n_out = treedef.num_leaves
        out_tensors = list(outs[:n_out])
        new_buffer_vals = outs[n_out:]
        for (name, b), nb in zip(named_buffers, new_buffer_vals):
            b._data = nb._data
        result = jax.tree_util.tree_unflatten(treedef, out_tensors)
        return result


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """Decorator/wrapper parity with paddle.jit.to_static."""
    def decorate(obj):
        # AST pass first (reference: program_translator.py:756 →
        # DygraphToStaticAst): native if/while/for over tensors become the
        # dual-regime control-flow APIs, so the functional capture below
        # can trace them (lax.cond / lax.while_loop) — no-op when the
        # source has no such statements or can't be rewritten
        from paddle_tpu.jit.dy2static import convert_to_static
        if isinstance(obj, Layer):
            sf = StaticFunction(convert_to_static(obj.forward), layer=obj,
                                input_spec=input_spec)
            obj.forward = sf
            return obj
        # plain function or bound method
        layer = getattr(obj, "__self__", None)
        if isinstance(layer, Layer):
            return StaticFunction(convert_to_static(obj), layer=layer,
                                  input_spec=input_spec)
        return StaticFunction(convert_to_static(obj), layer=None,
                              input_spec=input_spec)
    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(func):
    func._not_to_static = True
    return func


def functional_loss_call(model, loss_fn, params, buffers, key, inputs,
                         lead_tensors=(), amp=False,
                         amp_dtype=jnp.bfloat16):
    """The shared functional core of every captured train step: evaluate
    ``loss_fn(model, *lead_tensors, *inputs)`` with ``params``/``buffers``
    swapped into the model, the RNG key installed for the trace, and the
    tape off.  Returns ``(loss_f32, new_buffers)``.  Used by TrainStep,
    ShardedTrainStep stages and PSTrainStep so clip/donation/AMP semantics
    cannot fork between them."""
    if amp:
        params = {
            n: (p.astype(amp_dtype)
                if jnp.issubdtype(p.dtype, jnp.floating) and
                p.ndim >= 1 else p)
            for n, p in params.items()}
        inputs = [i.astype(amp_dtype)
                  if jnp.issubdtype(i.dtype, jnp.floating) else i
                  for i in inputs]
    tensors = [Tensor(i) for i in inputs]
    with _GeneratorKeyGuard(key):
        with model._swapped_state(params, buffers):
            with no_grad():
                loss = loss_fn(model, *lead_tensors, *tensors)
            new_buffers = {n: b._data
                           for n, b in model.named_buffers()
                           if b is not None}
    loss_arr = loss._data if isinstance(loss, Tensor) else loss
    return loss_arr.astype(jnp.float32), new_buffers


def apply_functional_update(opt, grads, params, opt_states, lr):
    """Clip (if the optimizer carries a functional clip) + functional
    optimizer update — the tail every captured step shares, and the
    ``optimizer`` region of its device trace."""
    with jax.named_scope("optimizer"):
        grad_clip = getattr(opt, "_grad_clip", None)
        if grad_clip is not None and hasattr(grad_clip, "functional_clip"):
            grads = grad_clip.functional_clip(grads)
        return opt.functional_update(params, grads, opt_states, lr=lr)


class TrainStep:
    """One fused XLA training step: forward + grad + optimizer update.

    ``loss_fn(model_out..., *labels) -> scalar Tensor`` runs under capture.
    Parameters, optimizer states and buffers are donated each call, so HBM
    holds one live copy (the role of the reference's buffer_shared_inplace
    memory passes, framework/ir/memory_optimize_pass/).

    Options:
      amp_level: None | 'O1' | 'O2' — bf16 compute (TPU-native AMP; loss
        scaling unnecessary for bf16, matching GradScaler(enable=False)).
      grad_clip is taken from the optimizer (ClipGradByGlobalNorm supported
        functionally).
      accumulate_steps: gradient-merge (fleet GradientMergeConfig parity)
        done with a lax.scan over micro-batches inside the same computation.
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 amp_level: Optional[str] = None, amp_dtype="bfloat16",
                 accumulate_steps: int = 1, donate: bool = True,
                 recompute: bool = False):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.amp_level = amp_level
        self.amp_dtype = jnp.bfloat16 if str(amp_dtype) in (
            "bfloat16", "bf16") else jnp.float16
        self.accumulate_steps = accumulate_steps
        self.donate = donate
        self.recompute = recompute
        self._cache: Dict[tuple, Callable] = {}
        self._opt_states: Optional[dict] = None
        self._last_call_start: Optional[float] = None

    # -- pure step ----------------------------------------------------------
    def _build_one_step(self, numerics_aux: bool = False):
        """The shared step body: forward + grad (with optional micro-batch
        gradient-merge) + optimizer update.  Both the per-call jit
        (_make_step) and the device-resident loop (_make_multi_step) wrap
        exactly this function, so their training semantics cannot drift.

        ``numerics_aux=True`` (FLAGS_numerics armed at dispatch) appends
        the model-numerics aux pytree (framework/numerics.py: per-leaf
        grad/param/update sum-of-squares, max-abs, non-finite counts) as
        a fifth output — pure extra reductions over values the step
        already computes, so the loss/param trajectory is bitwise
        unchanged; disarmed, the traced computation is exactly the
        legacy one (no extra outputs)."""
        model = self.model
        loss_fn = self.loss_fn
        opt = self.optimizer
        amp = self.amp_level in ("O1", "O2")
        amp_dtype = self.amp_dtype

        def loss_from(params, buffers, key, inputs):
            return functional_loss_call(
                model, loss_fn, params, buffers, key, inputs,
                amp=amp, amp_dtype=amp_dtype)

        if self.recompute:
            # Recompute meta-optimizer parity (reference:
            # python/paddle/fluid/backward.py:729 checkpointed backward;
            # fleet/meta_optimizers/recompute_optimizer.py): drop forward
            # activations, rebuild them during the grad sweep.
            loss_from = jax.checkpoint(loss_from, static_argnums=())

        def one_step(params, opt_states, buffers, key, lr, inputs):
            micro = self.accumulate_steps
            if micro > 1:
                def micro_body(carry, xs):
                    acc_grads, bufs, key_c = carry
                    key_c, sub = jax.random.split(key_c)
                    (l, nb), g = jax.value_and_grad(
                        lambda p: loss_from(p, bufs, sub, list(xs)),
                        has_aux=True)(params)
                    acc = jax.tree_util.tree_map(jnp.add, acc_grads, g)
                    return (acc, nb, key_c), l
                zero = jax.tree_util.tree_map(
                    lambda p: jnp.zeros_like(p), params)
                stacked = [i.reshape((micro, -1) + i.shape[1:])
                           for i in inputs]
                (grads, new_buffers, _), losses = jax.lax.scan(
                    micro_body, (zero, buffers, key), tuple(stacked))
                grads = jax.tree_util.tree_map(lambda g: g / micro, grads)
                loss = jnp.mean(losses)
            else:
                (loss, new_buffers), grads = jax.value_and_grad(
                    lambda p: loss_from(p, buffers, key, list(inputs)),
                    has_aux=True)(params)
            new_params, new_states = apply_functional_update(
                opt, grads, params, opt_states, lr)
            if numerics_aux:
                from paddle_tpu.framework import numerics
                aux = numerics.compute_aux(grads, params, new_params,
                                           loss)
                return new_params, new_states, new_buffers, loss, aux
            return new_params, new_states, new_buffers, loss

        return one_step

    def _resolve_layouts(self, tag, inputs):
        """First thing inside ``TrainStep.prepare`` (``tag``: "step" or
        "multi"): nothing here, the sharding layouts of a mesh-compiled
        subclass."""

    def _prepare_dispatch(self, inputs):
        """Shared prologue of __call__ and multi_step: live state grab,
        lazy opt-state init, input conversion, RNG/lr draw."""
        model = self.model
        named_params = {n: p for n, p in model.named_parameters()}
        named_buffers = {n: b for n, b in model.named_buffers()
                         if b is not None}
        params = {n: p._data for n, p in named_params.items()}
        buffers = {n: b._data for n, b in named_buffers.items()}
        if self._opt_states is None:
            self._opt_states = self.optimizer.functional_init_states(params)
        arrs = [i._data if isinstance(i, Tensor) else jnp.asarray(i)
                for i in inputs]
        key = default_generator.split()
        lr = jnp.float32(self.optimizer.get_lr())
        return named_params, named_buffers, params, buffers, arrs, key, lr

    def _note_avals(self, fn, arrs, key):
        # for compiled_text(): only the jit fn + input avals (cheap tuple);
        # param/state avals are derived lazily from live model state there
        self._last_fn = fn
        self._last_input_avals = tuple(
            jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrs)
        self._last_key_aval = jax.ShapeDtypeStruct(key.shape, key.dtype)

    def _commit_step(self, loss, what, named_params, new_params,
                     named_buffers, new_buffers, new_states):
        """Write the step's outputs into the live model, with the
        check_nan_inf raise ordered around the writeback by donation:
        donate=False raises BEFORE any mutation (the pre-step buffers are
        alive, so the caller can catch and resume from valid state);
        donate=True raises AFTER (the old buffers were consumed by the
        jit call — an early raise would strand the model on deleted
        arrays).  The finiteness reduce only dispatches when the flag is
        armed — it is an eager op and a device->host sync per step."""
        from paddle_tpu.framework.flags import flag
        check = flag("check_nan_inf")
        msg = (f"{what} produced a non-finite loss "
               "(FLAGS_check_nan_inf is set)")
        finite = True if not check else bool(jnp.all(jnp.isfinite(loss)))
        if check and not self.donate and not finite:
            raise FloatingPointError(msg)
        self._opt_states = new_states
        for n, p in named_params.items():
            p._data = new_params[n]
        for n, b in named_buffers.items():
            b._data = new_buffers[n]
        if check and self.donate and not finite:
            raise FloatingPointError(msg)

    def _make_step(self, numerics_aux: bool = False):
        one_step = self._build_one_step(numerics_aux=numerics_aux)

        def step(params, opt_states, buffers, key, lr, *inputs):
            return one_step(params, opt_states, buffers, key, lr,
                            list(inputs))

        donate = (0, 1, 2) if self.donate else ()
        return jax.jit(step, donate_argnums=donate)

    # -- device-resident multi-step loop ------------------------------------
    def _make_multi_step(self):
        """Like _make_step, but lax.scan's ``n_steps`` optimizer steps
        inside ONE compiled computation: the host is touched once per
        loop, not once per step.  This is the role of the reference's
        DeviceWorker batch loop — one Executor invocation trains many
        batches with no Python in between
        (paddle/fluid/framework/device_worker.cc HogwildWorker::TrainFiles
        loops device_reader->Next() inside a single C++ call)."""
        one_step = self._build_one_step()

        def body(carry, xs, lr):
            p, st, bufs, k = carry
            k, sub = jax.random.split(k)
            np_, nst, nb, l = one_step(p, st, bufs, sub, lr, list(xs))
            return (np_, nst, nb, k), l

        def multi(params, opt_states, buffers, key, lr, *stacked):
            (params, opt_states, buffers, _), losses = jax.lax.scan(
                lambda c, xs: body(c, xs, lr),
                (params, opt_states, buffers, key), tuple(stacked))
            return params, opt_states, buffers, losses

        def multi_unrolled(params, opt_states, buffers, key, lr, *stacked):
            # straight-line K steps: no scan, so the carry is never
            # double-buffered — the right shape when params+opt states fill
            # most of HBM and a scan's extra live copy would spill
            carry = (params, opt_states, buffers, key)
            losses = []
            for i in range(int(stacked[0].shape[0])):
                carry, l = body(carry, [s[i] for s in stacked], lr)
                losses.append(l)
            params, opt_states, buffers, _ = carry
            return params, opt_states, buffers, jnp.stack(losses)

        donate = (0, 1, 2) if self.donate else ()
        return (jax.jit(multi, donate_argnums=donate),
                jax.jit(multi_unrolled, donate_argnums=donate))

    def multi_step(self, *inputs, unroll: bool = False):
        """Run K optimizer steps in one device dispatch.

        Each input carries a leading steps axis: shape (K, B, ...) — K
        consecutive batches, prefetched to the device up front.  The loop
        body is identical to ``__call__``; per-step losses come back as a
        (K,)-shaped Tensor after the single round trip.  Use for small
        fast steps where host dispatch latency is comparable to device
        step time (high-latency links, small models).

        ``unroll=True`` emits the K steps as straight-line code instead of
        a lax.scan: compile time scales with K, but the scan's
        double-buffered carry (a second live copy of params + optimizer
        states) disappears — required when model+states fill most of HBM.

        The learning rate is read ONCE at dispatch and held constant for
        all K steps (unlike K ``__call__``s with a scheduler stepped in
        between) — keep K within one scheduler interval, or step the
        scheduler once per multi_step call.  RNG likewise: the host
        generator is drawn once and per-step keys are jax.random.split
        from it inside the loop, so stochastic layers (dropout) see
        different — equally independent — randomness than K sequential
        ``__call__``s, and the host generator advances once, not K times.

        The model-numerics plane (FLAGS_numerics) instruments only the
        per-call ``__call__`` path: a K-step device-resident loop has no
        per-step host boundary to publish at, so the loop body stays
        the disarmed computation.
        """
        from paddle_tpu.framework import health, monitor
        from paddle_tpu.profiler import RecordEvent
        # a K-step call is no step interval: train_step_ms starts again
        self._last_call_start = None
        with RecordEvent("TrainStep.multi_step",
                         step=int(self.optimizer._global_step)), \
                health.step_call("TrainStep.multi_step"):
            with RecordEvent("TrainStep.prepare"):
                self._resolve_layouts("multi", inputs)
                named_params, named_buffers, params, buffers, arrs, key, \
                    lr = self._prepare_dispatch(inputs)
                sig = ("multi", bool(unroll)) \
                    + _sig_of(list(named_params.values())) + _sig_of(arrs)
                fn = self._cache.get(sig)
                compile_cause = None
                if fn is None:
                    compile_cause = health.classify_recompile(
                        sig, [s for s in self._cache
                              if s and s[0] == "multi"])
                    scan_fn, unrolled_fn = self._make_multi_step()
                    fn = unrolled_fn if unroll else scan_fn
                    self._cache[sig] = fn
                else:
                    health.note_cache_hit("TrainStep.multi_step")
                self._note_avals(fn, arrs, key)
            with RecordEvent("TrainStep.launch"), health.timed_compile(
                    "TrainStep.multi_step", compile_cause):
                new_params, new_states, new_buffers, losses = fn(
                    params, self._opt_states, buffers, key, lr, *arrs)
            with RecordEvent("TrainStep.commit"):
                # same per-step guard as __call__, swept over the K
                # losses in one host sync
                self._commit_step(losses, "TrainStep.multi_step",
                                  named_params, new_params, named_buffers,
                                  new_buffers, new_states)
                k = int(arrs[0].shape[0])
                self.optimizer._global_step += k
                monitor.stat_add("train_steps_total", k)
        return Tensor(losses)

    def __call__(self, *inputs):
        """One step.  On the profiler's clock it is one host span,
        ``TrainStep`` (``step=<n>``), around three that follow each other:
        ``TrainStep.prepare``, ``TrainStep.launch`` (the jitted call
        alone) and ``TrainStep.commit``; every compile in them is booked
        to the site ``TrainStep`` (``health.step_call``)."""
        import time as _time

        from paddle_tpu.framework import health, numerics
        from paddle_tpu.framework.observability import tracer
        from paddle_tpu.profiler import RecordEvent
        t_start = _time.perf_counter()
        step_no = int(self.optimizer._global_step)
        with RecordEvent("TrainStep", step=step_no), \
                health.step_call("TrainStep"):
            with RecordEvent("TrainStep.prepare"):
                self._resolve_layouts("step", inputs)
                named_params, named_buffers, params, buffers, arrs, key, \
                    lr = self._prepare_dispatch(inputs)
                armed = numerics.enabled()
                # the marker is only appended when ARMED, so the disarmed
                # signature — and the traced jaxpr behind it — is
                # byte-identical to the plane-less seed (no extra
                # outputs, no recompile)
                sig = _sig_of(list(named_params.values())) + _sig_of(arrs) \
                    + (("numerics",) if armed else ())
                fn = self._cache.get(sig)
                compile_cause = None
                if fn is None:
                    # miss = XLA compile: classify the recompile cause
                    # against the cached signatures before this one is
                    # inserted
                    compile_cause = health.classify_recompile(
                        sig, [s for s in self._cache
                              if not (s and s[0] == "multi")])
                    fn = self._make_step(numerics_aux=armed)
                    self._cache[sig] = fn
                else:
                    health.note_cache_hit("TrainStep")
                self._note_avals(fn, arrs, key)
            # the JSONL tracer's record of the same call; its profiler
            # row is TrainStep.launch
            with RecordEvent("TrainStep.launch"), tracer.start_span(
                    "train.step", attrs={"step": step_no},
                    profiler_row=False), \
                    health.timed_compile("TrainStep", compile_cause):
                out = fn(params, self._opt_states, buffers, key, lr, *arrs)
            with RecordEvent("TrainStep.commit"):
                loss = self._after_launch(out, armed, named_params,
                                          named_buffers, t_start)
        return Tensor(loss)

    def _after_launch(self, out, armed, named_params, named_buffers,
                      t_start):
        """``TrainStep.commit``: write the step's outputs back and run
        every per-step hook.  Returns the loss array."""
        from paddle_tpu.framework import health, monitor, numerics
        if armed:
            new_params, new_states, new_buffers, loss, aux = out
            # stash + publish BEFORE the commit guard below: a
            # check_nan_inf raise must leave the provenance record
            # readable by the rollback tier (ResilientTrainStep)
            rec = numerics.NumericsRecord(
                list(named_params), aux,
                step=int(self.optimizer._global_step))
            numerics.publish(rec)
            self.last_numerics = rec
        else:
            new_params, new_states, new_buffers, loss = out
        # per-step sweep of the jitted tier (the eager per-op guard in
        # core.apply cannot see inside the fused step) — nan_inf_utils
        # role at step granularity; one scalar device->host sync.
        self._commit_step(loss, "TrainStep", named_params, new_params,
                          named_buffers, new_buffers, new_states)
        self.optimizer._global_step += 1
        # a step is the interval from one call's start to the next's:
        # under one step in flight the call itself is only its dispatch
        before, self._last_call_start = self._last_call_start, t_start
        if before is not None:
            step_ms = (t_start - before) * 1e3
            monitor.observe("train_step_ms", step_ms)
            health.observe("train_step_ms", step_ms)
        monitor.stat_add("train_steps_total")
        health.maybe_sample_memory(lambda: {
            "params": sum(int(p._data.nbytes)
                          for p in named_params.values()),
            "opt_state": sum(int(x.nbytes) for x in
                             jax.tree_util.tree_leaves(self._opt_states)),
            "buffers": sum(int(b._data.nbytes)
                           for b in named_buffers.values())})
        # replica-parity probe (FLAGS_replica_parity): a SEPARATE tiny
        # jitted check over replicated multi-device leaves — the step's
        # own cache/signature stays byte-identical armed or not, and
        # single-device state makes it a no-op after one flag lookup
        from paddle_tpu.parallel import parity
        parity.maybe_observe(self, mesh=getattr(self, "mesh", None))
        return loss

    def analyze(self, *example_inputs, **analyze_kwargs):
        """Static analysis of the fused step (framework.analysis jaxpr
        passes) on aval stand-ins — no device step is executed.  The
        step body is traced UNJITTED so dead-code liveness sees real
        equations, and the donation pass is fed the exact buffers
        ``donate_argnums`` hands XLA (params, opt states, buffers), so
        PTA104 audits the same aliasing contract the compiled step
        runs under."""
        import jax.tree_util as jtu

        from paddle_tpu.framework import numerics
        from paddle_tpu.framework.analysis import analyze_jaxpr
        _, _, params, buffers, arrs, key, lr = \
            self._prepare_dispatch(example_inputs)
        # analyze what would actually dispatch: with FLAGS_numerics
        # armed the traced step carries the aux reductions too
        one_step = self._build_one_step(numerics_aux=numerics.enabled())

        def step(params, opt_states, buffers, key, lr, *inputs):
            return one_step(params, opt_states, buffers, key, lr,
                            list(inputs))

        aval = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
            a.shape, a.dtype)
        tree_avals = [jtu.tree_map(aval, t)
                      for t in (params, self._opt_states, buffers)]
        labels, n_donated = [], 0
        for prefix, tree in zip(("params", "opt", "buffers"), tree_avals):
            flat, _ = jtu.tree_flatten_with_path(tree)
            labels += [prefix + jtu.keystr(path) for path, _ in flat]
        n_donated = len(labels) if self.donate else 0
        labels += ["rng_key", "lr"] + [f"input[{i}]"
                                       for i in range(len(arrs))]
        closed = jax.make_jaxpr(step)(
            *tree_avals, aval(key), jax.ShapeDtypeStruct((), jnp.float32),
            *[aval(x) for x in arrs])
        return analyze_jaxpr(
            closed, name="TrainStep", invar_labels=labels,
            donate_argnums=tuple(range(n_donated)), **analyze_kwargs)

    def compiled_text(self) -> str:
        """Backend-optimized HLO of the most recent step signature (fusion
        inspection).
        lower().compile() builds a fresh executable — the XLA compile
        cache usually makes it fast, but budget a compile on first use."""
        if getattr(self, "_last_fn", None) is None:
            raise RuntimeError("compiled_text() needs one executed step")
        aval = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa:E731
        params = {n: aval(p._data) for n, p in
                  self.model.named_parameters()}
        buffers = {n: aval(b._data) for n, b in self.model.named_buffers()
                   if b is not None}
        states = jax.tree_util.tree_map(aval, self._opt_states)
        key = self._last_key_aval
        lr = jax.ShapeDtypeStruct((), jnp.float32)
        return self._last_fn.lower(
            params, states, buffers, key, lr,
            *self._last_input_avals).compile().as_text()


# ---------------------------------------------------------------------------
# jit.save / jit.load
# ---------------------------------------------------------------------------


class TranslatedLayer(Layer):
    """Loaded inference layer (parity: fluid/dygraph/io.py TranslatedLayer).

    Wraps a deserialized StableHLO executable + params; call like a Layer.
    """

    def __init__(self, exported, params):
        super().__init__()
        self._exported = exported
        self._params = params

    def forward(self, *inputs):
        arrs = [i._data if isinstance(i, Tensor) else jnp.asarray(i)
                for i in inputs]
        out = self._exported.call(*self._params, *arrs)
        if isinstance(out, (tuple, list)):
            outs = [Tensor(o) for o in out]
            return outs[0] if len(outs) == 1 else outs
        return Tensor(out)


def _cipher_for(key):
    """(AESCipher, key_bytes) for a user-supplied key.  Raw 16/24/32-byte
    keys (cipher_utils-style key files, the reference's
    framework/io/crypto/cipher_utils.cc loading) are used verbatim at
    their own AES strength; any str passphrase or other length is
    sha256-hashed to a full 32-byte AES-256 key — one rule, no
    length-dependent forks."""
    import hashlib

    from paddle_tpu.framework.crypto import AESCipher
    if isinstance(key, (bytes, bytearray)) and len(key) in (16, 24, 32):
        kb = bytes(key)
    else:
        if isinstance(key, str):
            key = key.encode()
        kb = hashlib.sha256(bytes(key)).digest()
    return AESCipher(len(kb)), kb


def save(layer, path, input_spec=None, encrypt_key=None, **configs):
    """paddle.jit.save parity: state dict + StableHLO export.

    Writes ``path.pdparams`` (weights) and — when ``input_spec`` is given —
    ``path.pdmodel`` (serialized StableHLO).

    ``encrypt_key``: encrypt both artifacts (AES-CTR + HMAC-SHA256,
    framework.crypto — the reference predictor's encrypted-model
    deployment path, inference/api/analysis_predictor.cc:145).  Load
    with ``jit.load(path, decrypt_key=...)`` or
    ``inference.Config(..., decrypt_key=...)``.
    """
    from paddle_tpu.framework.io import dumps as _dumps
    from paddle_tpu.framework.io import save as _save
    if isinstance(layer, StaticFunction):
        sf = layer
        layer = sf._layer
    if encrypt_key is not None:
        # serialize in memory and write ciphertext only — plaintext
        # weights must never hit the filesystem, even transiently
        cipher, kb = _cipher_for(encrypt_key)
        blob = cipher.encrypt(_dumps(layer.state_dict()), kb)
        with open(path + ".pdparams", "wb") as f:
            f.write(blob)
    else:
        _save(layer.state_dict(), path + ".pdparams")
    if input_spec:
        from jax import export as jax_export
        named_params = [(n, p) for n, p in layer.named_parameters()]
        named_buffers = [(n, b) for n, b in layer.named_buffers()
                         if b is not None]
        was_training = layer.training
        layer.eval()

        def pure(*flat):
            n_p = len(named_params)
            n_b = len(named_buffers)
            params = dict((named_params[i][0], flat[i]) for i in range(n_p))
            buffers = dict((named_buffers[i][0], flat[n_p + i])
                           for i in range(n_b))
            arr_inputs = flat[n_p + n_b:]
            with layer._swapped_state(params, buffers):
                with no_grad():
                    out = layer.forward(*[Tensor(a) for a in arr_inputs])
            flat_out = jax.tree_util.tree_leaves(
                out, is_leaf=lambda x: isinstance(x, Tensor))
            return tuple(o._data if isinstance(o, Tensor) else o
                         for o in flat_out)

        def spec_shapes(symbolic):
            out = []
            n_sym = 0
            for spec in input_spec:
                dt = jnp.dtype(spec.dtype)
                dims, dyn = [], False
                for s in spec.shape:
                    if s is None or s == -1:
                        dims.append(f"_d{n_sym}")
                        n_sym += 1
                        dyn = True
                    else:
                        dims.append(str(int(s)))
                if symbolic and dyn:
                    out.append(jax.ShapeDtypeStruct(
                        jax_export.symbolic_shape(",".join(dims)), dt))
                else:
                    out.append(jax.ShapeDtypeStruct(
                        tuple(1 if s in (None, -1) else int(s)
                              for s in spec.shape), dt))
            return out

        param_shapes = [jax.ShapeDtypeStruct(tuple(p.shape), p.dtype)
                        for _, p in named_params]
        buffer_shapes = [jax.ShapeDtypeStruct(tuple(b.shape), b.dtype)
                         for _, b in named_buffers]
        try:
            # dynamic dims export as shape-polymorphic symbols so the
            # loaded Predictor accepts any batch size (the reference's
            # -1 dims); ops that can't trace polymorphically fall back
            # to a concrete batch-1 export
            try:
                exp = jax_export.export(jax.jit(pure))(
                    *param_shapes, *buffer_shapes, *spec_shapes(True))
            except Exception as e:             # noqa: BLE001
                import warnings
                warnings.warn(
                    f"jit.save: shape-polymorphic export failed ({e!r}); "
                    "falling back to a CONCRETE batch-1 export — the "
                    "loaded model will only accept the saved shapes",
                    stacklevel=2)
                exp = jax_export.export(jax.jit(pure))(
                    *param_shapes, *buffer_shapes, *spec_shapes(False))
            blob = bytes(exp.serialize())
            if encrypt_key is not None:
                cipher, kb = _cipher_for(encrypt_key)
                blob = cipher.encrypt(blob, kb)
            with open(path + ".pdmodel", "wb") as f:
                f.write(blob)
        finally:
            if was_training:
                layer.train()


def _read_artifact(path, decrypt_key):
    """Read a saved artifact, decrypting in memory when it carries the
    crypto magic (plaintext never touches disk on load)."""
    from paddle_tpu.framework import crypto
    with open(path, "rb") as f:
        data = f.read()
    if data.startswith(crypto._MAGIC):
        if decrypt_key is None:
            raise ValueError(
                f"{path} is encrypted — pass decrypt_key= (jit.load) or "
                "Config(decrypt_key=...) (inference)")
        cipher, kb = _cipher_for(decrypt_key)
        data = cipher.decrypt(data, kb)
    return data


def load(path, decrypt_key=None, **configs):
    """paddle.jit.load parity.  ``decrypt_key`` loads artifacts written
    with ``jit.save(..., encrypt_key=...)``; HMAC failure (wrong key or
    tampered file) raises instead of returning garbage weights."""
    from paddle_tpu.framework.io import loads as _loads
    state = _loads(_read_artifact(path + ".pdparams", decrypt_key))
    if os.path.exists(path + ".pdmodel"):
        from jax import export as jax_export
        exp = jax_export.deserialize(
            _read_artifact(path + ".pdmodel", decrypt_key))
        params = [np.asarray(v._data if isinstance(v, Tensor) else v)
                  for v in state.values()]
        return TranslatedLayer(exp, [jnp.asarray(p) for p in params])
    raise FileNotFoundError(f"{path}.pdmodel not found")

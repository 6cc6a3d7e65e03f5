"""Shared Pallas kernel helpers."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def backend_is_tpu() -> bool:
    """The one backend gate behind every kernel module's ``supported()``:
    Mosaic lowers for the TPU only (tests flip the module's ``_INTERPRET``
    instead)."""
    return jax.default_backend() == "tpu"


def no_x64():
    """Context manager forcing 32-bit trace semantics for a kernel call.

    The package enables jax_enable_x64 globally (paddle parity), but
    Mosaic rejects 64-bit types — index maps and iotas traced under x64
    would emit i64."""
    return jax.enable_x64(False)


def dot_nt(a, b):
    """a (m, d) · b (n, d) → (m, n): contraction over the trailing dim with
    f32 accumulation — keeps bf16 inputs on the MXU's fast path instead of
    casting to f32 first (which quarters MXU throughput on v5e)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def traced_once(static_argnums=(), inline: bool = True):
    """Decorator: the function traced once a process for each distinct
    (static arguments, shapes and dtypes of the others), and that trace
    replayed at every call.  A stack of unrolled, checkpointed layers
    calls a kernel, or a piece of a layer built of kernels, at many
    sites, and tracing a kernel body is Python by the tenth of a second
    (PERF.md section 6, PR 26, PR 29).

    ``jax.jit``'s own trace cache does this for the sites of one trace
    (``flash_attention._traced_once``) but keys on more than the
    computation: a ``custom_vjp``'s forward rule is traced under another
    context than the function itself, and every process traces its step
    a second time with arguments whose types name a mesh (the outputs of
    the first call carry a ``NamedSharding``), so each body was traced
    three times a process.  The jaxpr is therefore kept here, under a key
    that holds what the computation depends on and ``pl.pallas_call``
    itself: ``framework.analysis`` swaps that for a recorder, whose trace
    must not be found again, nor be served a kept one.

    ``inline`` replays the equations into the caller, whose jaxpr is then
    what a direct call would have left: right for one kernel.  A piece of
    many equations stays one equation of its caller (``inline=False``, a
    ``jax.jit`` around the replay), so that jax's passes over the caller
    (differentiation, ``jax.checkpoint``'s partial evaluation and
    transposition) and the lowering walk it once.  Scope paths are the
    call site's either way."""
    def wrap(fun):
        kept = {}

        @functools.wraps(fun)
        def call(*args):
            from jax.experimental import pallas as pl
            leaves, tree = jax.tree_util.tree_flatten(
                [a for i, a in enumerate(args) if i not in static_argnums])
            key = (pl.pallas_call, tree,
                   tuple(args[i] for i in static_argnums),
                   tuple((leaf.shape, leaf.dtype) for leaf in leaves))
            if key not in kept:
                def flat(*flat_args):
                    rest = iter(jax.tree_util.tree_unflatten(tree, flat_args))
                    return fun(*(args[i] if i in static_argnums
                                 else next(rest) for i in range(len(args))))

                closed, shape = jax.make_jaxpr(flat, return_shape=True)(
                    *(jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
                      for leaf in leaves))

                def replay(*flat_args):
                    return jax.core.eval_jaxpr(closed.jaxpr, closed.consts,
                                               *flat_args)

                replay.__name__ = fun.__name__
                kept[key] = (replay if inline else jax.jit(replay),
                             jax.tree_util.tree_structure(shape))
            replay, out_tree = kept[key]
            return jax.tree_util.tree_unflatten(out_tree, replay(*leaves))
        return call
    return wrap

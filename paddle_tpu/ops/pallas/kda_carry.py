"""The state pass of the KDA chunked delta rule: the state carried across
its chunks (Pallas, TPU).

``nn/functional/kda.py`` computes, for every chunk ``n`` at once, what the
chunk takes from the state entering it: ``W`` and ``U`` of the WY form,
``QG = Q o Gamma``, ``A`` (the chunk's lower-triangular ``A_qk``, with its
diagonal), ``Kt = K o Gamma_C / Gamma`` and ``Gamma_C``, the chunk's whole
decay.  What is left is sequential:

    S_0 = 0
    Delta_n = U_n - W_n S_n
    O_n = QG_n S_n + A_n Delta_n
    S_{n+1} = Diag(Gamma_C,n) S_n + Kt_n^T Delta_n

``state_pass`` returns ``O`` by the kernels, ``scan_pass`` by a
``lax.scan`` (where ``supported`` declines); ``chunk`` is one step of it,
which the forward kernel runs for each head and chunk of a block and the
scan for all heads at once: one algebra, two executors.  The state is held
transposed, ``S^T`` (d_v, d_k), so that ``Gamma_C``, one factor per key
channel, scales its lanes and its gradient sums its rows; ``W S`` and
``QG S`` are one product, ``[W; QG] S``, which loads the state into the
MXU once.

The forward grid walks the chunks in order, innermost, two chunks a step
where their number is even (``_chunks_per_step``), a block of heads at a
time (``_heads_per_block``); the state stays in VMEM from the first chunk
to the last, in the dtype the caller names (``kda._STATE_DTYPE``), and
every product takes it in float32.  No per-chunk ``(d_k, d_k)`` matrix is
built: each chunk costs one read of its inputs and one write of ``O_n``,
and, in the call that keeps the backward's residual, of ``entering_n =
S_n^T``.

The gradient is a kernel of its own (``jax.custom_vjp``): from ``dO``, the
inputs and ``entering``, it walks the chunks in reverse through its index
maps and carries ``c``, the cotangent of ``S_{n+1}`` (transposed, in
float32), in VMEM from zero (the last state is no output):

    Delta = U - W S                         (again, from entering)
    dDelta = A^T dO + Kt c
    [dW; dQG] = [-dDelta; dO] S^T;  dU = dDelta;  dA = dO Delta^T
    dKt = Delta c^T;  dGamma_C = sum_v c o S
    c <- Diag(Gamma_C) c + [-dDelta; dO]^T [W; QG]

Every product is float32 at the highest precision, as in the rest of the
rule.  Each kernel is traced once a process for each distinct shape
(``common.traced_once``): six layers call it in three passes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.common import (backend_is_tpu, no_x64,
                                          traced_once)

__all__ = ["supported", "chunk", "scan_pass", "state_pass"]

_INTERPRET = False

_HIGHEST = jax.lax.Precision.HIGHEST
# the float32 states of a grid step (its chunks times its heads); the
# backward keeps some fifteen (C, width) blocks of each double-buffered
_BLOCK_BYTES = 1 << 20
_VMEM_LIMIT_BYTES = 32 << 20


def supported(dk: int, dv: int, dtype) -> bool:
    """Whether a state ``(d_k, d_v)`` of ``dtype`` is carried by these
    kernels: both widths lane multiples, a float dtype the MXU takes."""
    if not (backend_is_tpu() or _INTERPRET):
        return False
    return (dk % 128 == 0 and dv % 128 == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def _chunks_per_step(n: int) -> int:
    """Two chunks a grid step where ``n`` is even, else one."""
    return 2 if n % 2 == 0 else 1


def _heads_per_block(heads: int, chunks: int, dk: int, dv: int) -> int:
    """The most heads, a divisor of ``heads``, whose float32 states of
    ``chunks`` chunks stay inside ``_BLOCK_BYTES``; one at least."""
    width = chunks * dv * dk * 4
    return max(d for d in range(1, heads + 1)
               if heads % d == 0 and (d == 1 or d * width <= _BLOCK_BYTES))


def _mm(a, b, ta: bool = False, tb: bool = False):
    """``a @ b`` over the last two axes (``a^T`` where ``ta``, ``b^T``
    where ``tb``), any leading axes a batch, in float32 at the highest
    precision."""
    lead = tuple(range(a.ndim - 2))
    ca, cb = a.ndim - (2 if ta else 1), b.ndim - (1 if tb else 2)
    return jax.lax.dot_general(
        a, b, (((ca,), (cb,)), (lead, lead)), precision=_HIGHEST,
        preferred_element_type=jnp.float32)


def chunk(st, w, u, qg, a, kt, gc):
    """One chunk of the state pass: ``(O, S_{n+1}^T)`` from ``st =
    S_n^T`` (.., d_v, d_k), ``w``, ``qg``, ``kt`` (.., C, d_k), ``u``
    (.., C, d_v), ``a`` (.., C, C) and ``gc`` (.., 1, d_k), all float32."""
    c = u.shape[-2]
    ws_qs = _mm(jnp.concatenate([w, qg], axis=-2), st, tb=True)
    delta = u - ws_qs[..., :c, :]
    o = ws_qs[..., c:, :] + _mm(a, delta)
    return o, st * gc + _mm(delta, kt, ta=True)


def scan_pass(state_dtype, w, u, qg, a, kt, gc):
    """``state_pass`` by a ``lax.scan`` over the chunk axis, differentiated
    by jax."""
    zero = jnp.zeros(w.shape[:1] + w.shape[2:3] + (u.shape[-1], w.shape[-1]),
                     state_dtype)

    def step(st, now):
        o, st = chunk(st.astype(jnp.float32), *now)
        return st.astype(state_dtype), o

    _, o = jax.lax.scan(step, zero, tuple(
        jnp.moveaxis(x, 1, 0) for x in (w, u, qg, a, kt, gc)))
    return jnp.moveaxis(o, 0, 1)


def _forward_kernel(w_ref, u_ref, qg_ref, a_ref, kt_ref, gc_ref, o_ref,
                    *rest):
    from jax.experimental import pallas as pl
    *e_ref, s_ref = rest

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    for p in range(o_ref.shape[1]):
        for j in range(s_ref.shape[0]):
            st = s_ref[j]
            if e_ref:
                e_ref[0][0, p, j] = st
            o, st_next = chunk(st.astype(jnp.float32), *(
                r[0, p, j] for r in (w_ref, u_ref, qg_ref, a_ref, kt_ref,
                                     gc_ref)))
            o_ref[0, p, j] = o
            s_ref[j] = st_next.astype(st.dtype)


def _backward_kernel(do_ref, w_ref, u_ref, qg_ref, a_ref, kt_ref, gc_ref,
                     e_ref, dw_ref, du_ref, dqg_ref, da_ref, dkt_ref,
                     dgc_ref, c_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        c_ref[...] = jnp.zeros_like(c_ref)

    c = u_ref.shape[-2]
    for p in reversed(range(do_ref.shape[1])):
        for j in range(c_ref.shape[0]):
            ct = c_ref[j]
            st = e_ref[0, p, j].astype(jnp.float32)
            do, w, u, qg, a, kt, gc = (
                r[0, p, j] for r in (do_ref, w_ref, u_ref, qg_ref, a_ref,
                                     kt_ref, gc_ref))
            delta = u - _mm(w, st, tb=True)
            ddelta = _mm(a, do, ta=True) + _mm(kt, ct, tb=True)
            x = jnp.concatenate([-ddelta, do], axis=0)
            dwq = _mm(x, st)
            dw_ref[0, p, j] = dwq[:c]
            dqg_ref[0, p, j] = dwq[c:]
            du_ref[0, p, j] = ddelta
            da_ref[0, p, j] = _mm(do, delta, tb=True)
            dkt_ref[0, p, j] = _mm(delta, ct)
            dgc_ref[0, p, j] = jnp.sum(ct * st, axis=0, keepdims=True)
            c_ref[j] = ct * gc + _mm(
                x, jnp.concatenate([w, qg], axis=0), ta=True)


def _layout(w, u):
    """The grid (batch, head blocks, chunk blocks), the chunks and heads
    of a block, and the transposed state's shape (d_v, d_k)."""
    bsz, n, heads, _, dk = w.shape
    dv = u.shape[-1]
    p = _chunks_per_step(n)
    hb = _heads_per_block(heads, p, dk, dv)
    return (bsz, heads // hb, n // p), p, hb, (dv, dk)


def _specs(shapes, p: int, hb: int, reverse: bool):
    """A block spec for each of ``shapes`` (batch, chunks, heads, rows,
    columns): ``p`` chunks of ``hb`` heads, block ``t`` of the grid
    reading block ``last - t`` where ``reverse``."""
    from jax.experimental import pallas as pl

    last = shapes[0][1] // p - 1

    def at(i, g, t):
        return (i, last - t if reverse else t, g, 0, 0)

    return [pl.BlockSpec((1, p, hb) + s[-2:], at) for s in shapes]


def _params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


@traced_once(static_argnums=(6, 7, 8))
def _forward(w, u, qg, a, kt, gc, state_dtype, keep, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid, p, hb, state = _layout(w, u)
    ins = (w, u, qg, a, kt, gc)
    outs = [jax.ShapeDtypeStruct(u.shape, jnp.float32)]
    if keep:
        outs.append(jax.ShapeDtypeStruct(w.shape[:3] + state, state_dtype))
    with no_x64():
        return pl.pallas_call(
            _forward_kernel, grid=grid,
            in_specs=_specs([x.shape for x in ins], p, hb, reverse=False),
            out_specs=_specs([o.shape for o in outs], p, hb, reverse=False),
            out_shape=outs,
            scratch_shapes=[pltpu.VMEM((hb,) + state, state_dtype)],
            compiler_params=_params(), interpret=interpret,
            name="kda_state_pass",
        )(*ins)


@traced_once(static_argnums=(8,))
def _backward(do, w, u, qg, a, kt, gc, entering, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid, p, hb, state = _layout(w, u)
    ins = (do, w, u, qg, a, kt, gc, entering)
    grads = (w, u, qg, a, kt, gc)
    with no_x64():
        return pl.pallas_call(
            _backward_kernel, grid=grid,
            in_specs=_specs([x.shape for x in ins], p, hb, reverse=True),
            out_specs=_specs([x.shape for x in grads], p, hb, reverse=True),
            out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32)
                       for x in grads],
            scratch_shapes=[pltpu.VMEM((hb,) + state, jnp.float32)],
            compiler_params=_params(), interpret=interpret,
            name="kda_state_pass_bwd",
        )(*ins)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def state_pass(state_dtype, w, u, qg, a, kt, gc):
    """``O`` (batch, chunks, heads, C, d_v) of the state pass over ``w``,
    ``qg``, ``kt`` (.., C, d_k), ``u`` (.., C, d_v), ``a`` (.., C, C) and
    ``gc`` (.., 1, d_k), all float32, the state held in ``state_dtype``."""
    return _forward(w, u, qg, a, kt, gc, state_dtype, False, _INTERPRET)[0]


def _state_pass_fwd(state_dtype, w, u, qg, a, kt, gc):
    o, entering = _forward(w, u, qg, a, kt, gc, state_dtype, True,
                           _INTERPRET)
    return o, (w, u, qg, a, kt, gc, entering)


def _state_pass_bwd(state_dtype, res, do):
    return tuple(_backward(do, *res, _INTERPRET))


state_pass.defvjp(_state_pass_fwd, _state_pass_bwd)

"""Pipeline parallelism — microbatch schedule as a differentiable collective
program.

Parity target: the reference's PipelineOptimizer + SectionWorker (reference:
python/paddle/fluid/optimizer.py:3718 program-splitting,
paddle/fluid/framework/section_worker.cc:98 — schedule_mode 0 = F-then-B,
1 = 1F1B; P2P via send_v2/recv_v2 ops).  On TPU there are no per-device
program counters or streams to schedule, so the schedule is expressed as a
single SPMD program: a ``lax.scan`` over clock ticks inside ``shard_map``
over the ``pp`` mesh axis, with ``lax.ppermute`` as the send/recv pair.
``jax.grad`` through the scan replays the ticks in reverse — the backward
pipeline (F-then-B order, the reference's schedule_mode 0) falls out of
autodiff instead of being hand-scheduled; activation memory is bounded with
``jax.checkpoint`` inside the stage function.

Layout contract:
- ``stacked_params``: pytree whose leaves have leading dim = number of
  layers L, sharded over ``pp`` (each stage holds L/P consecutive layers).
- ``stage_fn(local_params, x) -> x`` consumes its (L/P, ...) slice, must be
  shape-preserving (embedding/head live outside the pipeline trunk).
- ``x``: (B, ...) activations; batch may additionally be sharded over data
  axes — each data-parallel group runs its own pipeline.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.parallel.mesh import get_mesh

__all__ = ["pipeline_forward", "make_pipeline_train_1f1b"]


def _shard_map(f, mesh, in_specs, out_specs, manual_axes=None):
    """shard_map with optional partial-manual mode: axes in ``manual_axes``
    are mapped explicitly, the rest stay 'auto' so GSPMD keeps partitioning
    them inside the body (tensor parallelism composes under the pipeline)."""
    kwargs = {}
    if manual_axes is not None:
        kwargs["axis_names"] = set(manual_axes)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def _psum(x, axis):
    """psum with a CPU-only bf16→f32 boundary: XLA:CPU's
    AllReducePromotion pass crashes on bf16 all-reduce ("Invalid binary
    instruction opcode copy", hlo_instruction.cc) — promote by hand there.
    On TPU the bf16 reduce rides ICI at half the bytes, untouched."""
    if jax.default_backend() == "cpu" and x.dtype == jnp.bfloat16:
        return lax.psum(x.astype(jnp.float32), axis).astype(jnp.bfloat16)
    return lax.psum(x, axis)


def _pmean(x, axis):
    if jax.default_backend() == "cpu" and x.dtype == jnp.bfloat16:
        return lax.pmean(x.astype(jnp.float32), axis).astype(jnp.bfloat16)
    return lax.pmean(x, axis)


def _pvary(x, axis_names):
    """Mark a replicated value as device-varying along ``axis_names`` (newer
    jax tracks varying-manual-axes through shard_map scans).  Axes are cast
    one at a time — pcast rejects mixed varying/invarying axis sets."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    for a in axis_names:
        already = getattr(getattr(x, "aval", None), "vma", ())
        if a in already:
            continue
        try:
            x = lax.pcast(x, (a,), to="varying")
            continue
        except (AttributeError, TypeError, ValueError):
            pass
        try:
            x = lax.pvary(x, (a,))
        except (AttributeError, TypeError, ValueError):
            pass
    return x


def pipeline_forward(stage_fn: Callable, stacked_params, x,
                     n_microbatches: int, mesh: Optional[Mesh] = None,
                     pp_axis: str = "pp", data_axes=("dp",),
                     seq_axis: Optional[str] = None):
    """Run ``x`` through a pipelined layer stack; returns activations with
    the same global shape as ``x``.  Mesh axes other than pp/data stay
    GSPMD-auto inside the region (tensor parallelism composes).  With
    ``seq_axis`` set (sp×pp composition), dim 1 of ``x`` is sharded over
    that axis and it joins the manual set — the stage function must then
    handle sequence-sharded activations itself (e.g. ring attention via
    ``ring_attention_manual``, which runs inside this region's manual
    axes rather than opening a nested shard_map)."""
    mesh = mesh or get_mesh()
    n_stages = mesh.shape.get(pp_axis, 1)

    if n_stages <= 1:
        # no pipeline axis: the trunk is just the stage function on the
        # whole stack (scan over layers inside stage_fn)
        return stage_fn(stacked_params, x)

    data_axes = tuple(a for a in data_axes if mesh.shape.get(a, 1) > 1)
    seq = seq_axis if (seq_axis and mesh.shape.get(seq_axis, 1) > 1) else None
    if seq:
        batch_spec = P(data_axes if data_axes else None, seq)
    else:
        batch_spec = P(data_axes if data_axes else None)

    param_specs = jax.tree_util.tree_map(
        lambda _: P(pp_axis), stacked_params)

    manual = {pp_axis} | set(data_axes) | ({seq} if seq else set())
    fn = partial(_pipeline_body, stage_fn, n_stages, n_microbatches, pp_axis,
                 tuple(sorted(manual)))
    mapped = _shard_map(fn, mesh, in_specs=(param_specs, batch_spec),
                        out_specs=batch_spec, manual_axes=manual)
    return mapped(stacked_params, x)


def _pipeline_body(stage_fn, n_stages, n_micro, axis_name, manual_axes,
                   local_params, x):
    stage = lax.axis_index(axis_name)
    batch = x.shape[0]
    if batch % n_micro:
        raise ValueError(
            f"local batch {batch} not divisible by {n_micro} microbatches")
    mb = batch // n_micro
    mbs = x.reshape((n_micro, mb) + x.shape[1:])

    shift_perm = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(carry, t):
        state, outputs = carry
        mb_idx = t - stage
        clipped = jnp.clip(mb_idx, 0, n_micro - 1)
        first_stage_in = lax.dynamic_index_in_dim(mbs, clipped, 0,
                                                  keepdims=False)
        inp = jnp.where(stage == 0, first_stage_in, state)
        y = stage_fn(local_params, inp)
        valid_out = (stage == n_stages - 1) & (mb_idx >= 0) & (
            mb_idx < n_micro)
        prev = lax.dynamic_index_in_dim(outputs, clipped, 0, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(valid_out, y, prev), clipped, 0)
        state = lax.ppermute(y, axis_name, shift_perm)
        return (state, outputs), None

    state0 = _pvary(jnp.zeros((mb,) + x.shape[1:], x.dtype), manual_axes)
    out0 = _pvary(jnp.zeros_like(mbs), manual_axes)
    (_, outputs), _ = lax.scan(tick, (state0, out0),
                               jnp.arange(n_micro + n_stages - 1))
    # result lives on the last stage; broadcast (masked psum) so every stage
    # returns the same shard — out_specs treats pp as replicated
    outputs = _psum(
        jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs)),
        axis_name)
    return outputs.reshape((batch,) + x.shape[1:])


# ---------------------------------------------------------------------------
# 1F1B (schedule_mode 1)
# ---------------------------------------------------------------------------


def _f_sched(stage, t, n_stages, n_micro):
    """1F1B forward timetable: stage s runs F(m) at t = s + m during warmup
    (m < P-1-s) and at t = 2m + s in steady state.  Returns (m, valid)."""
    warm_m = t - stage
    warm_ok = (warm_m >= 0) & (warm_m < jnp.minimum(
        n_stages - 1 - stage, n_micro))
    rel = t - stage
    steady_m = rel // 2
    steady_ok = (rel >= 0) & (rel % 2 == 0) & \
        (steady_m >= n_stages - 1 - stage) & (steady_m < n_micro)
    m = jnp.where(warm_ok, warm_m, steady_m)
    return m, warm_ok | steady_ok


def _b_sched(stage, t, n_stages, n_micro):
    """1F1B backward timetable: stage s runs B(m) at t = 2P-1-s+2m."""
    rel = t - (2 * n_stages - 1 - stage)
    m = rel // 2
    ok = (rel >= 0) & (rel % 2 == 0) & (m < n_micro)
    return m, ok


def make_pipeline_train_1f1b(stage_fn: Callable, head_loss_fn: Callable,
                             n_microbatches: int,
                             mesh: Optional[Mesh] = None,
                             pp_axis: str = "pp", data_axes=("dp",),
                             seq_axis: Optional[str] = None,
                             unconditional: Optional[bool] = None):
    """Build a differentiable 1F1B pipelined loss (reference:
    paddle/fluid/framework/section_worker.cc:115-160, schedule_mode 1).

    Unlike ``pipeline_forward`` (F-then-B via autodiff, schedule_mode 0),
    the backward here is hand-interleaved with the forward on a clock
    schedule, so each stage keeps at most P (= pp degree) live microbatch
    activations instead of M — activation memory is O(P·mb), independent
    of the microbatch count.  The loss/head must live on the LAST stage
    (that is what makes interleaving possible), so the head is a separate
    callable rather than running outside the trunk.

    Args:
      stage_fn(local_params, x) -> y        shape-preserving trunk stage.
      head_loss_fn(head_params, y, labels) -> scalar mean loss of one
        microbatch (runs only on the last stage at B-time).
      n_microbatches: M, microbatches per local (per-dp-group) batch.

    Returns ``loss_fn(stacked_params, head_params, x, labels) -> scalar``
    wrapped in a custom_vjp whose gradients were computed *during* the
    schedule (self-computed-gradient pattern), so it composes with
    ``jax.grad`` of the surrounding training step.

    Composition (beyond the reference PipelineOptimizer's pp×dp scope,
    sharding_optimizer.py:115-138 reaches pp×mp by program rewrite):
    - Tensor parallelism: mesh axes not listed here (e.g. ``mp``) stay
      GSPMD-auto inside the region, so stage-internal matmuls may be
      mp-sharded.
    - Sequence parallelism: with ``seq_axis``, dim 1 of x/labels is
      sharded over it and the stage/head functions run on sequence
      shards (ring attention via ``ring_attention_manual``).  The
      head_loss_fn contract under sp: return local-sum over its
      sequence shard divided by the GLOBAL per-microbatch denominator —
      the schedule psums the shards, so the same callable computes the
      true loss both inside the region (local slice) and in the eval
      primal (full sequence).

    Two scheduler implementations, auto-selected (``unconditional``):
    - cond-based (dp/sharding-only meshes): each tick runs at most one
      op under ``lax.cond`` — minimum FLOPs, but collectives must not
      appear inside the conds: different pp stages take different
      branches, so devices would issue collectives in divergent global
      orders, which corrupts or deadlocks the matched-instance
      collective runtime (measured on XLA:CPU: auto-mp inserted
      allgathers deadlock the pp ppermute rendezvous; manual sp ring
      ppermutes silently mispair instances and corrupt activations).
    - branch-free/masked (any mesh with in-stage collectives — mp, sp):
      EVERY stage runs one F and one B every tick on clipped indices,
      with invalid slots masked out of the accumulators (``jnp.where``,
      never ``lax.cond``), so every device issues the identical
      collective sequence — the schedule that actually fits SPMD
      hardware.  Costs the bubble twice ((M+2P-2) double-ticks vs
      2(M+P-1) single-ticks) and an unconditional per-tick head eval;
      still O(P·mb) activation memory (a 2P-1-slot buffer).
    ``labels`` are feed data and are never differentiated through; their
    cotangent is zero by construction.
    """
    mesh = mesh or get_mesh()
    P_ = mesh.shape.get(pp_axis, 1)
    M = n_microbatches
    data = tuple(a for a in data_axes if mesh.shape.get(a, 1) > 1)
    seq = seq_axis if (seq_axis and mesh.shape.get(seq_axis, 1) > 1) else None
    dp_size = 1
    for a in data:
        dp_size *= mesh.shape[a]
    if seq:
        batch_spec = P(data if data else None, seq)
    else:
        batch_spec = P(data if data else None)
    if unconditional is None:
        # any mesh axis with in-region collectives (auto axes like mp, or
        # manual seq) forces the branch-free scheduler — see docstring
        extra = [a for a, s in mesh.shape.items()
                 if s > 1 and a != pp_axis and a not in data and a != seq]
        unconditional = bool(extra) or seq is not None
    elif not unconditional and seq is not None:
        raise ValueError(
            "make_pipeline_train_1f1b: the cond-based scheduler "
            "(unconditional=False) cannot carry a seq_axis — in-stage ring "
            "collectives inside divergent lax.cond branches mispair "
            "collective instances and silently corrupt activations; use "
            "the branch-free scheduler (unconditional=True/None)")

    def _microbatch_loss(head_params, y, labels):
        """mean over dp_size*M of per-microbatch head loss — the exact
        quantity the schedule accumulates (each dp shard cuts its LOCAL
        batch into M microbatches), so eval-mode loss matches train-mode
        loss even for losses that couple elements within a microbatch."""
        groups = dp_size * M
        if y.shape[0] % groups:
            raise ValueError(
                f"global batch {y.shape[0]} not divisible by dp_size*"
                f"n_microbatches = {dp_size}*{M}")
        mb = y.shape[0] // groups
        ys = y.reshape((groups, mb) + y.shape[1:])
        ls = labels.reshape((groups, mb) + labels.shape[1:])
        per = jax.vmap(lambda yi, li: head_loss_fn(head_params, yi, li))(
            ys, ls)
        return jnp.mean(per.astype(jnp.float32))

    if P_ <= 1:
        # no pipeline axis: plain differentiable composition (mirrors
        # pipeline_forward's single-stage fallback)
        def dense(stacked_params, head_params, x, labels):
            y = stage_fn(stacked_params, x)
            return _microbatch_loss(head_params, y, labels)
        return dense

    @jax.jit
    def _impl(stacked_params, head_params, x, labels):
        param_specs = jax.tree_util.tree_map(
            lambda _: P(pp_axis), stacked_params)
        repl = jax.tree_util.tree_map(lambda _: P(), head_params)

        def finalize(dparams, dhead, dx_all, loss_acc, batch, xb_shape):
            """Shared tail: collect loss/grads onto every device with the
            normalisations both schedulers share."""
            loss = _psum(loss_acc, pp_axis) / M
            dhead = jax.tree_util.tree_map(
                lambda g: _psum(g, pp_axis), dhead)
            if seq:
                # head_loss returns local-sum/global-denominator per shard
                # (see docstring): the shard losses SUM to the true loss,
                # and trunk/head grads from disjoint sequence slices sum
                # likewise (params are seq-replicated)
                loss = _psum(loss, seq)
                dparams = jax.tree_util.tree_map(
                    lambda g: _psum(g, seq), dparams)
                dhead = jax.tree_util.tree_map(
                    lambda g: _psum(g, seq), dhead)
            # dx was only written on stage 0 (zeros elsewhere): the psum
            # both collects it and proves pp-replication for the out_spec
            dx = _psum(dx_all.reshape((batch,) + xb_shape[1:]), pp_axis)
            # dx stays per-dp-shard (no pmean), so fold the 1/dp factor of
            # the dp-mean loss in here explicitly
            dx = dx / dp_size
            scale = 1.0 / M
            dparams = jax.tree_util.tree_map(lambda g: g * scale, dparams)
            dhead = jax.tree_util.tree_map(lambda g: g * scale, dhead)
            dx = dx * scale
            for a in data:
                loss = _pmean(loss, a)
                dparams = jax.tree_util.tree_map(
                    lambda g: _pmean(g, a), dparams)
                dhead = jax.tree_util.tree_map(
                    lambda g: _pmean(g, a), dhead)
            return loss, dparams, dhead, dx

        def body_masked(local_params, head_p, xb, yb):
            """Branch-free 1F1B: every stage runs one F and one B every
            tick on index-clipped data; invalid results are masked out of
            the accumulators with jnp.where.  No lax.cond anywhere, so
            every device issues the identical collective sequence — safe
            for in-stage mp (auto) and sp (ring) collectives.

            Timetable: F(m) on stage s at tick u = s + m; B(m) on stage s
            at u = 2(P-1) - s + m (cooldown mirror of warmup).  The F
            input needs no buffering — stage s-1 produced it last tick
            and the unconditional ppermute lands it exactly on time; a
            (2P-1)-slot ring buffer keeps activations alive until B.
            """
            stage = lax.axis_index(pp_axis)
            batch = xb.shape[0]
            mb = batch // M
            axes = (pp_axis,) + data + ((seq,) if seq else ())
            vary = lambda t: jax.tree_util.tree_map(
                lambda a: _pvary(a, axes), t)
            local_params = vary(local_params)
            head_p = vary(head_p)
            mbs = vary(xb.reshape((M, mb) + xb.shape[1:]))
            lbs = vary(yb.reshape((M, mb) + yb.shape[1:]))

            fwd_perm = [(i, i + 1) for i in range(P_ - 1)]
            bwd_perm = [(i + 1, i) for i in range(P_ - 1)]
            act_shape = (mb,) + xb.shape[1:]
            Q = 2 * P_ - 1

            dparams0 = jax.tree_util.tree_map(jnp.zeros_like, local_params)
            dhead0 = jax.tree_util.tree_map(jnp.zeros_like, head_p)
            is_last = stage == P_ - 1

            def tick(carry, u):
                buf, fwd_in, bwd_in, dparams, dhead, dx_all, loss_acc = carry

                # ---- forward op (always) ----
                mF = u - stage
                okF = (mF >= 0) & (mF < M)
                mFc = jnp.clip(mF, 0, M - 1)
                val = jnp.where(
                    stage == 0,
                    lax.dynamic_index_in_dim(mbs, mFc, 0, False), fwd_in)
                slotF = mFc % Q
                prev = lax.dynamic_index_in_dim(buf, slotF, 0, False)
                buf = lax.dynamic_update_index_in_dim(
                    buf, jnp.where(okF, val, prev), slotF, 0)
                y = stage_fn(local_params, val)

                # ---- backward op (always) ----
                mB = u - (2 * (P_ - 1) - stage)
                okB = (mB >= 0) & (mB < M)
                mBc = jnp.clip(mB, 0, M - 1)
                inp_b = lax.dynamic_index_in_dim(buf, mBc % Q, 0, False)
                lab_mb = lax.dynamic_index_in_dim(lbs, mBc, 0, False)
                y_b, svjp = jax.vjp(
                    lambda p, i: stage_fn(p, i), local_params, inp_b)

                def head_fn(hp, yy):
                    # f32 boundary keeps the seed dtype stable for bf16
                    return head_loss_fn(hp, yy, lab_mb).astype(jnp.float32)
                loss_m, hvjp = jax.vjp(head_fn, head_p, y_b)
                dhp_t, dy_head = hvjp(vary(jnp.ones((), jnp.float32)))
                seed = jnp.where(is_last, dy_head, bwd_in)
                dp_t, dinp = svjp(seed)

                okB_last = okB & is_last
                dparams = jax.tree_util.tree_map(
                    lambda acc, g: acc + jnp.where(okB, g, 0), dparams, dp_t)
                dhead = jax.tree_util.tree_map(
                    lambda acc, g: acc + jnp.where(okB_last, g, 0),
                    dhead, dhp_t)
                loss_acc = loss_acc + jnp.where(okB_last, loss_m, 0.0)
                dxprev = lax.dynamic_index_in_dim(dx_all, mBc, 0, False)
                dx_all = lax.dynamic_update_index_in_dim(
                    dx_all, jnp.where(okB & (stage == 0), dinp, dxprev),
                    mBc, 0)

                # ---- ring sends (always) ----
                fwd_next = lax.ppermute(y, pp_axis, fwd_perm)
                bwd_next = lax.ppermute(dinp, pp_axis, bwd_perm)
                return (buf, fwd_next, bwd_next, dparams, dhead, dx_all,
                        loss_acc), None

            n_ticks = M + 2 * (P_ - 1)
            zero_act = jnp.zeros(act_shape, xb.dtype)
            carry0 = (
                vary(jnp.zeros((Q,) + act_shape, xb.dtype)),
                vary(zero_act),
                vary(zero_act),
                vary(dparams0),
                vary(dhead0),
                vary(jnp.zeros((M,) + act_shape, xb.dtype)),
                vary(jnp.zeros((), jnp.float32)),
            )
            (_, _, _, dparams, dhead, dx_all, loss_acc), _ = lax.scan(
                tick, carry0, jnp.arange(n_ticks))
            return finalize(dparams, dhead, dx_all, loss_acc, batch,
                            xb.shape)

        def body(local_params, head_p, xb, yb):
            stage = lax.axis_index(pp_axis)
            batch = xb.shape[0]
            mb = batch // M
            axes = (pp_axis,) + data + ((seq,) if seq else ())
            vary = lambda t: jax.tree_util.tree_map(
                lambda a: _pvary(a, axes), t)
            # promote every input to fully-varying on the manual axes:
            # differentiating w.r.t. a replicated (invarying) value makes
            # jax insert an implicit psum for the cotangent INSIDE the
            # runtime conds below — a collective only some devices would
            # execute, which deadlocks the ring.  Varying inputs keep all
            # collectives at the (unconditional) tick boundary.
            local_params = vary(local_params)
            head_p = vary(head_p)
            mbs = vary(xb.reshape((M, mb) + xb.shape[1:]))
            lbs = vary(yb.reshape((M, mb) + yb.shape[1:]))

            fwd_perm = [(i, i + 1) for i in range(P_ - 1)]
            bwd_perm = [(i + 1, i) for i in range(P_ - 1)]
            act_shape = (mb,) + xb.shape[1:]

            dparams0 = jax.tree_util.tree_map(jnp.zeros_like, local_params)
            dhead0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros_like(p), head_p)

            def tick(carry, t):
                buf, fwd_in, bwd_in, dparams, dhead, dx_all, loss_acc = carry
                mF, doF = _f_sched(stage, t, P_, M)
                mB, doB = _b_sched(stage, t, P_, M)
                m_recv, ok_recv = _f_sched(stage - 1, t - 1, P_, M)

                # 1. land incoming activation (stage 0 sources from x at
                #    its own F tick; others from the fwd ppermute carry)
                is0 = stage == 0
                slot = jnp.where(is0, mF % P_, m_recv % P_)
                val = jnp.where(is0,
                                lax.dynamic_index_in_dim(
                                    mbs, jnp.clip(mF, 0, M - 1), 0, False),
                                fwd_in)
                ok_land = jnp.where(is0, doF, ok_recv & (stage > 0))
                buf = lax.cond(
                    ok_land,
                    lambda b: lax.dynamic_update_index_in_dim(
                        b, val, slot, 0),
                    lambda b: b, buf)

                # 2. forward op
                def run_f(_):
                    inp = lax.dynamic_index_in_dim(buf, mF % P_, 0, False)
                    return stage_fn(local_params, inp)
                y = lax.cond(doF, run_f,
                             lambda _: vary(jnp.zeros(act_shape, xb.dtype)),
                             0)

                # 3. backward op (vjp with recomputed stage forward; last
                #    stage instead differentiates stage+head+loss)
                lab_mb = lax.dynamic_index_in_dim(
                    lbs, jnp.clip(mB, 0, M - 1), 0, False)

                def run_b(_):
                    inp = lax.dynamic_index_in_dim(buf, mB % P_, 0, False)

                    def b_last(_):
                        def last_fn(p, hp, i):
                            # f32 boundary: keeps the vjp seed and the cond
                            # zero-branches dtype-consistent for bf16 heads
                            return head_loss_fn(
                                hp, stage_fn(p, i), lab_mb).astype(
                                    jnp.float32)
                        loss_m, vjp = jax.vjp(last_fn, local_params,
                                              head_p, inp)
                        dp, dhp, dinp = vjp(
                            vary(jnp.ones((), jnp.float32)))
                        return dp, dhp, dinp, loss_m

                    def b_mid(_):
                        _, vjp = jax.vjp(
                            lambda p, i: stage_fn(p, i), local_params, inp)
                        dp, dinp = vjp(bwd_in)
                        return (vary(dp), vary(dhead0), dinp,
                                vary(jnp.zeros((), jnp.float32)))

                    return lax.cond(stage == P_ - 1,
                                    lambda u: vary(b_last(u)),
                                    b_mid, 0)

                def no_b(_):
                    return vary((dparams0, dhead0,
                                 jnp.zeros(act_shape, xb.dtype),
                                 jnp.zeros((), jnp.float32)))

                dp_t, dhp_t, dinp, loss_m = lax.cond(doB, run_b, no_b, 0)
                dparams = jax.tree_util.tree_map(jnp.add, dparams, dp_t)
                dhead = jax.tree_util.tree_map(jnp.add, dhead, dhp_t)
                loss_acc = loss_acc + loss_m
                # stage 0's input-cotangent feeds the (outside) embedding
                dx_all = lax.cond(
                    doB & (stage == 0),
                    lambda b: lax.dynamic_update_index_in_dim(
                        b, dinp, jnp.clip(mB, 0, M - 1), 0),
                    lambda b: b, dx_all)

                # 4. ring sends — unconditional, outside every cond
                fwd_next = lax.ppermute(y, pp_axis, fwd_perm)
                bwd_next = lax.ppermute(dinp, pp_axis, bwd_perm)
                return (buf, fwd_next, bwd_next, dparams, dhead, dx_all,
                        loss_acc), None

            n_ticks = 2 * (M + P_ - 1)
            zero_act = jnp.zeros(act_shape, xb.dtype)
            carry0 = (
                vary(jnp.zeros((P_,) + act_shape, xb.dtype)),
                vary(zero_act),
                vary(zero_act),
                vary(dparams0),
                vary(dhead0),
                vary(jnp.zeros((M,) + act_shape, xb.dtype)),
                vary(jnp.zeros((), jnp.float32)),
            )
            (_, _, _, dparams, dhead, dx_all, loss_acc), _ = lax.scan(
                tick, carry0, jnp.arange(n_ticks))

            return finalize(dparams, dhead, dx_all, loss_acc, batch,
                            xb.shape)

        manual = {pp_axis} | set(data) | ({seq} if seq else set())
        mapped = _shard_map(
            body_masked if unconditional else body, mesh,
            in_specs=(param_specs, repl, batch_spec, batch_spec),
            out_specs=(P(), param_specs, repl, batch_spec),
            manual_axes=manual)
        return mapped(stacked_params, head_params, x, labels)

    @jax.custom_vjp
    def loss_1f1b(stacked_params, head_params, x, labels):
        # eval-only primal: F-only pipeline + head — the full interleaved
        # schedule (with its recompute-backward) runs only under jax.grad
        if x.shape[0] % (dp_size * M):
            raise ValueError(
                f"global batch {x.shape[0]} not divisible by dp_size*"
                f"n_microbatches = {dp_size}*{M}")
        y = pipeline_forward(stage_fn, stacked_params, x, M, mesh=mesh,
                             pp_axis=pp_axis, data_axes=data_axes,
                             seq_axis=seq_axis)
        return _microbatch_loss(head_params, y, labels)

    def fwd(stacked_params, head_params, x, labels):
        if x.shape[0] % (dp_size * M):
            raise ValueError(
                f"global batch {x.shape[0]} not divisible by dp_size*"
                f"n_microbatches = {dp_size}*{M}")
        loss, dparams, dhead, dx = _impl(stacked_params, head_params, x,
                                         labels)
        return loss, (dparams, dhead, dx, labels)

    def bwd(res, g):
        import numpy as _np
        dparams, dhead, dx, labels = res
        scale_t = lambda t: jax.tree_util.tree_map(lambda a: a * g, t)
        # labels are feed data, never differentiated through (matching the
        # reference PipelineOptimizer, where labels enter via feed ops):
        # integer leaves get float0 (jax's "no tangent space" marker);
        # inexact leaves get real zeros so downstream dtype logic holds.
        dlabels = jax.tree_util.tree_map(
            lambda l: (jnp.zeros_like(l)
                       if jnp.issubdtype(jnp.asarray(l).dtype, jnp.inexact)
                       else _np.zeros(l.shape, jax.dtypes.float0)),
            labels)
        return scale_t(dparams), scale_t(dhead), dx * g, dlabels

    loss_1f1b.defvjp(fwd, bwd)
    return loss_1f1b

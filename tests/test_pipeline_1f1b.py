"""1F1B pipeline schedule tests (VERDICT round-1 item #4).

Reference: paddle/fluid/framework/section_worker.cc:115-160 schedule_mode 1.
Checks: timetable closed forms, loss/grad parity vs a non-pipelined dense
reference, composition with jax.grad, and the memory bound (live
activations ~P microbatches, not M).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.parallel import make_mesh, set_mesh
from paddle_tpu.parallel.pipeline import (_b_sched, _f_sched,
                                          make_pipeline_train_1f1b,
                                          pipeline_forward)

L, D = 8, 16   # layers, width


def _stage_fn(local_params, x):
    w, b = local_params

    def layer(h, wb):
        wi, bi = wb
        return jnp.tanh(h @ wi + bi), None
    h, _ = jax.lax.scan(layer, x, (w, b))
    return h


def _head_loss(head_params, y, labels):
    wo = head_params["w"]
    logits = y @ wo
    return ((logits - labels) ** 2).mean()


def _make_params(seed=0):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((L, D, D)).astype(np.float32) * 0.2)
    b = jnp.asarray(np.zeros((L, D), np.float32))
    wo = jnp.asarray(rng.standard_normal((D, 4)).astype(np.float32) * 0.2)
    return (w, b), {"w": wo}


def _dense_loss(stacked, head, x, labels):
    y = _stage_fn(stacked, x)
    return _head_loss(head, y, labels)


class TestSchedule:
    @pytest.mark.parametrize("P_,M", [(2, 4), (4, 8), (4, 3), (8, 8)])
    def test_timetable_is_a_valid_1f1b(self, P_, M):
        """Every (stage, microbatch) F and B happens exactly once, in causal
        order, with at most one op per stage per tick, and per-stage live
        activations bounded by P (not M)."""
        T = 2 * (M + P_ - 1)
        f_time = {}
        b_time = {}
        for s in range(P_):
            live = 0
            max_live = 0
            for t in range(T):
                mF, okF = _f_sched(jnp.int32(s), jnp.int32(t), P_, M)
                mB, okB = _b_sched(jnp.int32(s), jnp.int32(t), P_, M)
                assert not (bool(okF) and bool(okB)), (s, t)
                if bool(okF):
                    f_time[(s, int(mF))] = t
                    live += 1
                if bool(okB):
                    b_time[(s, int(mB))] = t
                    live -= 1
                max_live = max(max_live, live)
            assert max_live <= P_, f"stage {s} holds {max_live} > P live"
        for s in range(P_):
            for m in range(M):
                assert (s, m) in f_time and (s, m) in b_time
                if s > 0:
                    # causal: consumed at or after arrival (the warmup→
                    # steady bubble buffers the activation for a few ticks)
                    assert f_time[(s, m)] >= f_time[(s - 1, m)] + 1
                    # backward has no bubble: cotangents chain tick-by-tick
                    assert b_time[(s - 1, m)] == b_time[(s, m)] + 1
                assert b_time[(s, m)] > f_time[(s, m)]
        # P-slot buffer safety: slot m%P must not be rewritten (by m+P's
        # arrival) before B(m) has consumed it
        for s in range(P_):
            for m in range(M):
                recv = (f_time[(s, m)] if s == 0
                        else f_time[(s - 1, m)] + 1)
                assert recv <= f_time[(s, m)]
                if (s, m + P_) in f_time or m + P_ < M:
                    recv_next = (f_time[(s, m + P_)] if s == 0
                                 else f_time[(s - 1, m + P_)] + 1)
                    assert recv_next > b_time[(s, m)], (s, m)


class Test1F1BNumerics:
    @pytest.fixture(autouse=True)
    def mesh(self):
        mesh = make_mesh({"pp": 4, "dp": 2}, devices=jax.devices()[:8])
        set_mesh(mesh)
        self.mesh = mesh
        yield

    def _data(self, B=8, seed=1):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))
        labels = jnp.asarray(
            rng.standard_normal((B, 4)).astype(np.float32))
        return x, labels

    @pytest.mark.parametrize("M", [2, 4])
    def test_loss_and_grad_parity_vs_dense(self, M):
        stacked, head = _make_params()
        x, labels = self._data(B=8)
        fn = make_pipeline_train_1f1b(_stage_fn, _head_loss, M,
                                      mesh=self.mesh)
        loss = fn(stacked, head, x, labels)

        # dense reference: mean over microbatches of per-microbatch loss
        # == plain mean when microbatches are equal-sized
        ref = _dense_loss(stacked, head, x, labels)
        np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)

        g = jax.grad(lambda s, h: fn(s, h, x, labels), argnums=(0, 1))(
            stacked, head)
        gr = jax.grad(lambda s, h: _dense_loss(s, h, x, labels),
                      argnums=(0, 1))(stacked, head)
        for a, b in zip(jax.tree_util.tree_leaves(g),
                        jax.tree_util.tree_leaves(gr)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-6)

    def test_dx_flows_to_upstream_embedding(self):
        stacked, head = _make_params()
        x, labels = self._data(B=8)
        fn = make_pipeline_train_1f1b(_stage_fn, _head_loss, 4,
                                      mesh=self.mesh)
        emb = jnp.asarray(np.random.default_rng(3).standard_normal(
            (D, D)).astype(np.float32) * 0.3)

        def with_embed(e):
            return fn(stacked, head, x @ e, labels)

        de = jax.grad(with_embed)(emb)

        def with_embed_ref(e):
            return _dense_loss(stacked, head, x @ e, labels)

        de_ref = jax.grad(with_embed_ref)(emb)
        np.testing.assert_allclose(np.asarray(de), np.asarray(de_ref),
                                   rtol=2e-4, atol=1e-6)

    @pytest.mark.slow      # heavy for the 870 s tier-1 cap (PR 21): -m slow
    def test_loss_parity_vs_fthenb_pipeline(self):
        """Same trunk through schedule_mode 0 (pipeline_forward + autodiff)
        and schedule_mode 1 (1F1B) must agree in loss and grads."""
        stacked, head = _make_params()
        x, labels = self._data(B=8)
        M = 4
        f1 = make_pipeline_train_1f1b(_stage_fn, _head_loss, M,
                                      mesh=self.mesh)

        def f0(s, h):
            y = pipeline_forward(_stage_fn, s, x, M, mesh=self.mesh)
            return _head_loss(h, y, labels)

        l1 = float(f1(stacked, head, x, labels))
        l0 = float(f0(stacked, head))
        np.testing.assert_allclose(l1, l0, rtol=1e-5)
        g1 = jax.grad(lambda s, h: f1(s, h, x, labels), argnums=(0, 1))(
            stacked, head)
        g0 = jax.grad(f0, argnums=(0, 1))(stacked, head)
        for a, b in zip(jax.tree_util.tree_leaves(g1),
                        jax.tree_util.tree_leaves(g0)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-6)


class TestMemoryBound:
    def test_carry_activation_buffer_is_P_not_M(self):
        """The structural memory claim: the scan carry holds a P-slot
        activation buffer; growing M must not grow the carry (only the
        number of ticks grows).  Compare compiled temp memory at M=4 vs
        M=16 — F-then-B autodiff residuals scale ~linearly with M, the
        1F1B carry must not."""
        mesh = make_mesh({"pp": 4}, devices=jax.devices()[:4])
        set_mesh(mesh)
        stacked, head = _make_params()
        B = 32
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))
        labels = jnp.asarray(rng.standard_normal((B, 4)).astype(np.float32))

        def temp_bytes(M):
            fn = make_pipeline_train_1f1b(_stage_fn, _head_loss, M,
                                          mesh=mesh)
            jitted = jax.jit(lambda s, h: fn(s, h, x, labels))
            compiled = jitted.lower(stacked, head).compile()
            ma = compiled.memory_analysis()
            if ma is None:
                pytest.skip("backend reports no memory analysis")
            return ma.temp_size_in_bytes

        t4, t16 = temp_bytes(4), temp_bytes(16)
        # allow slack for the dx/labels buffers that do scale with M (they
        # are O(batch), not O(layers*batch)); the per-stage activation
        # store must not multiply by 4
        assert t16 <= t4 * 2.5 + 64 * 1024, (t4, t16)


class TestNoPipelineFallback:
    def test_dense_fallback_without_pp_axis(self):
        mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
        set_mesh(mesh)
        stacked, head = _make_params()
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.standard_normal((8, D)).astype(np.float32))
        labels = jnp.asarray(rng.standard_normal((8, 4)).astype(np.float32))
        fn = make_pipeline_train_1f1b(_stage_fn, _head_loss, 4, mesh=mesh)
        loss = fn(stacked, head, x, labels)
        ref = _dense_loss(stacked, head, x, labels)
        np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)
        g = jax.grad(lambda s: fn(s, head, x, labels))(stacked)
        gr = jax.grad(lambda s: _dense_loss(s, head, x, labels))(stacked)
        for a, b in zip(jax.tree_util.tree_leaves(g),
                        jax.tree_util.tree_leaves(gr)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)

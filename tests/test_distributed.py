"""Distributed stack tests on the 8-device virtual CPU mesh.

Mirrors the reference's test tiers (SURVEY.md §4):
- collective numeric tests (reference: test_collective_base.py
  check_with_place — rank outputs vs numpy) become shard_map numeric tests;
- meta-optimizer compile-only tests (test_fleet_sharding_meta_optimizer.py
  — inspect the rewritten Program for inserted ops) become HLO-text
  assertions;
- dist-train parity tests (test_dist_base.py — 2-trainer loss ≈ 1-proc
  loss) become sharded-step vs single-device-step loss parity.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import nn, optimizer
from paddle_tpu.parallel import (ShardedTrainStep, get_mesh, make_mesh,
                                 set_mesh, HybridTopology)
from paddle_tpu.parallel.pipeline import pipeline_forward
from paddle_tpu.parallel.ring_attention import (ring_attention,
                                                ring_attention_local)


@pytest.fixture(autouse=True)
def reset_mesh():
    set_mesh(make_mesh({"dp": 8}))
    yield
    set_mesh(make_mesh({"dp": 8}))


def shard_map_call(fn, mesh, in_specs, out_specs, *args):
    from paddle_tpu.parallel.pipeline import _shard_map
    return _shard_map(fn, mesh, in_specs, out_specs)(*args)


# ---------------------------------------------------------------------------
# mesh / topology
# ---------------------------------------------------------------------------


def test_make_mesh_axes_order_and_infer():
    mesh = make_mesh({"dp": -1, "mp": 2})
    assert mesh.shape["dp"] == 4 and mesh.shape["mp"] == 2
    assert mesh.axis_names.index("dp") < mesh.axis_names.index("mp")


def test_hybrid_topology_coordinates():
    mesh = make_mesh({"pp": 2, "dp": 2, "mp": 2})
    topo = HybridTopology(mesh)
    assert topo.world_size() == 8
    assert topo.get_degree("mp") == 2
    # rank 0 groups along each axis
    mp_group = topo.group_ranks(0, "mp")
    assert len(mp_group) == 2 and 0 in mp_group
    dp_group = topo.group_ranks(0, "dp")
    assert len(dp_group) == 2
    # coordinates round-trip
    for r in range(8):
        assert topo.rank_of(topo.coordinate(r)) == r


# ---------------------------------------------------------------------------
# collectives (numeric tier, in-trace regime)
# ---------------------------------------------------------------------------


def test_all_reduce_in_shard_map():
    mesh = get_mesh()
    x = jnp.arange(8.0)

    def body(x):
        return dist.all_reduce(x, op=dist.ReduceOp.SUM)

    out = shard_map_call(body, mesh, (P("dp"),), P("dp"), x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 28.0))


def test_all_reduce_max_in_shard_map():
    mesh = get_mesh()
    x = jnp.arange(8.0)

    def body(x):
        return dist.all_reduce(x, op=dist.ReduceOp.MAX)

    out = shard_map_call(body, mesh, (P("dp"),), P("dp"), x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 7.0))


def test_all_gather_in_shard_map():
    mesh = get_mesh()
    x = jnp.arange(8.0)

    def body(x):
        return dist.all_gather(None, x)

    out = shard_map_call(body, mesh, (P("dp"),), P(None, "dp", None),
                         x.reshape(8, 1))
    assert np.asarray(out).size == 64


def test_broadcast_in_shard_map():
    mesh = get_mesh()
    x = jnp.arange(8.0).reshape(8, 1)

    def body(x):
        return dist.broadcast(x, src=3)

    out = shard_map_call(body, mesh, (P("dp"),), P("dp"), x)
    np.testing.assert_allclose(np.asarray(out).ravel(), np.full(8, 3.0))


def test_reduce_scatter_in_shard_map():
    mesh = get_mesh()
    x = jnp.ones((8, 8))

    def body(x):
        # x local: (1, 8); psum_scatter over rows
        return dist.reduce_scatter(None, x.reshape(8))

    out = shard_map_call(body, mesh, (P("dp", None),), P("dp"), x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 8.0))


def test_all_reduce_prod_with_negatives():
    mesh = get_mesh()
    x = jnp.asarray([-2.0, 3.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0])

    def body(x):
        return dist.all_reduce(x, op=dist.ReduceOp.PROD)

    out = shard_map_call(body, mesh, (P("dp"),), P("dp"), x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, -6.0), rtol=1e-5)
    # zero anywhere → 0
    x0 = x.at[2].set(0.0)
    out0 = shard_map_call(body, mesh, (P("dp"),), P("dp"), x0)
    np.testing.assert_allclose(np.asarray(out0), np.zeros(8))


def test_broadcast_multi_axis_mesh():
    mesh = make_mesh({"dp": 2, "mp": 4})
    set_mesh(mesh)
    x = jnp.arange(8.0).reshape(8, 1)

    def body(x):
        return dist.broadcast(x, src=5)

    out = shard_map_call(body, mesh, (P(("dp", "mp")),), P(("dp", "mp")), x)
    np.testing.assert_allclose(np.asarray(out).ravel(), np.full(8, 5.0))


def test_p2p_shift():
    mesh = make_mesh({"dp": 8})
    set_mesh(mesh)
    x = jnp.arange(8.0).reshape(8, 1)

    def body(x):
        return dist.p2p_shift(x, offset=1, wrap=True)

    out = shard_map_call(body, mesh, (P("dp"),), P("dp"), x)
    np.testing.assert_allclose(np.asarray(out).ravel(),
                               np.roll(np.arange(8.0), 1))
    with pytest.raises(NotImplementedError):
        dist.send(paddle.to_tensor([1.0]), dst=1)
    with pytest.raises(NotImplementedError):
        dist.recv(paddle.to_tensor([1.0]), src=0)


def test_eager_collectives_single_process_identity():
    t = paddle.to_tensor([1.0, 2.0])
    dist.all_reduce(t)
    np.testing.assert_allclose(t.numpy(), [1.0, 2.0])
    dist.broadcast(t, src=0)
    out = []
    dist.all_gather(out, t)
    assert len(out) == 1
    dist.barrier()
    assert dist.get_rank() == 0 and dist.get_world_size() == 1


def test_new_group_axis():
    g = dist.new_group(axis="dp")
    assert g.nranks == 8
    g2 = dist.new_group(ranks=[0, 1])
    assert g2.nranks == 2 and g2.get_group_rank(1) == 1


# ---------------------------------------------------------------------------
# DataParallel + sharded step: loss parity with single-device step
# (reference tier: test_dist_base.py two-trainer vs one-proc delta check)
# ---------------------------------------------------------------------------


class _MLP(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(16, 32)
        self.fc2 = nn.Linear(32, 4)

    def forward(self, x):
        return self.fc2(paddle.nn.functional.relu(self.fc1(x)))


def _loss_fn(model, x, y):
    out = model(x)
    return paddle.nn.functional.cross_entropy(out, y).mean()


def _mk(seed=0):
    paddle.seed(seed)
    model = _MLP()
    opt = optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
    return model, opt


def test_sharded_step_matches_single_device():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((16, 16)).astype(np.float32)
    y = rng.integers(0, 4, size=(16,)).astype(np.int64)

    model_a, opt_a = _mk()
    from paddle_tpu.jit import TrainStep
    step_a = TrainStep(model_a, _loss_fn, opt_a)

    model_b, opt_b = _mk()
    step_b = ShardedTrainStep(model_b, _loss_fn, opt_b,
                              mesh=make_mesh({"dp": 8}))

    losses_a = [float(step_a(paddle.to_tensor(x), paddle.to_tensor(y)))
                for _ in range(3)]
    losses_b = [float(step_b(paddle.to_tensor(x), paddle.to_tensor(y)))
                for _ in range(3)]
    np.testing.assert_allclose(losses_a, losses_b, rtol=2e-5, atol=2e-6)
    # params end up identical too
    for (n, pa), (_, pb) in zip(model_a.named_parameters(),
                                model_b.named_parameters()):
        np.testing.assert_allclose(np.asarray(pa._data),
                                   np.asarray(pb._data), rtol=2e-5,
                                   atol=2e-6)


def test_sharded_multi_step_matches_sequential():
    # regression: the multi_step refactor changed TrainStep._make_step to
    # zero-arg; ShardedTrainStep must track it AND shard the stacked
    # (K, B, ...) inputs with the data axis on dim 1, not dim 0
    rng = np.random.default_rng(1)
    K = 3
    xs = rng.standard_normal((K, 16, 16)).astype(np.float32)
    ys = rng.integers(0, 4, size=(K, 16)).astype(np.int64)

    model_a, opt_a = _mk()
    step_a = ShardedTrainStep(model_a, _loss_fn, opt_a,
                              mesh=make_mesh({"dp": 8}))
    losses_a = [float(step_a(paddle.to_tensor(xs[i]),
                             paddle.to_tensor(ys[i]))) for i in range(K)]

    model_b, opt_b = _mk()
    step_b = ShardedTrainStep(model_b, _loss_fn, opt_b,
                              mesh=make_mesh({"dp": 8}))
    multi = step_b.multi_step(paddle.to_tensor(xs), paddle.to_tensor(ys))
    assert tuple(multi.shape) == (K,)
    np.testing.assert_allclose(losses_a, np.asarray(multi._data),
                               rtol=2e-5, atol=2e-6)
    for (n, pa), (_, pb) in zip(model_a.named_parameters(),
                                model_b.named_parameters()):
        np.testing.assert_allclose(np.asarray(pa._data),
                                   np.asarray(pb._data), rtol=2e-5,
                                   atol=2e-6)


def test_sharded_step_zero_stages_match():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((16, 16)).astype(np.float32)
    y = rng.integers(0, 4, size=(16,)).astype(np.int64)
    losses = {}
    for stage in (0, 1, 3):
        model, opt = _mk(seed=7)
        mesh = make_mesh({"dp": 4, "sharding": 2})
        set_mesh(mesh)
        step = ShardedTrainStep(model, _loss_fn, opt, mesh=mesh,
                                sharding_stage=stage)
        losses[stage] = [float(step(paddle.to_tensor(x),
                                    paddle.to_tensor(y)))
                         for _ in range(2)]
    np.testing.assert_allclose(losses[0], losses[1], rtol=2e-5)
    np.testing.assert_allclose(losses[0], losses[3], rtol=2e-5)


def test_tp_layers_match_dense():
    paddle.seed(3)
    mesh = make_mesh({"dp": 2, "mp": 4})
    set_mesh(mesh)

    class TPBlock(nn.Layer):
        def __init__(self):
            super().__init__()
            self.col = dist.ColumnParallelLinear(16, 32,
                                                 gather_output=False)
            self.row = dist.RowParallelLinear(32, 4,
                                              input_is_parallel=True)

        def forward(self, x):
            return self.row(self.col(x))

    paddle.seed(11)
    tp = TPBlock()
    # dense twin with identical weights
    paddle.seed(11)
    dense = nn.Sequential(nn.Linear(16, 32), nn.Linear(32, 4))

    rng = np.random.default_rng(2)
    x = rng.standard_normal((8, 16)).astype(np.float32)
    y = rng.integers(0, 4, size=(8,)).astype(np.int64)

    opt_tp = optimizer.SGD(learning_rate=0.05, parameters=tp.parameters())
    opt_d = optimizer.SGD(learning_rate=0.05, parameters=dense.parameters())
    step_tp = ShardedTrainStep(tp, _loss_fn, opt_tp, mesh=mesh)
    from paddle_tpu.jit import TrainStep
    step_d = TrainStep(dense, _loss_fn, opt_d)
    for _ in range(2):
        lt = float(step_tp(paddle.to_tensor(x), paddle.to_tensor(y)))
        ld = float(step_d(paddle.to_tensor(x), paddle.to_tensor(y)))
        np.testing.assert_allclose(lt, ld, rtol=2e-5, atol=2e-6)


def test_sharded_step_hlo_contains_collectives():
    """Compile-only tier: the dp-sharded step must contain a grad
    all-reduce (the op the reference's pass inserted)."""
    model, opt = _mk()
    mesh = make_mesh({"dp": 8})
    set_mesh(mesh)
    step = ShardedTrainStep(model, _loss_fn, opt, mesh=mesh)
    x = np.zeros((16, 16), np.float32)
    y = np.zeros((16,), np.int64)
    hlo = step.lower_hlo(paddle.to_tensor(x), paddle.to_tensor(y))
    assert "all-reduce" in hlo or "all_reduce" in hlo


def test_data_parallel_wrapper():
    model = _MLP()
    dp = paddle.DataParallel(model)
    x = paddle.to_tensor(np.ones((4, 16), np.float32))
    out = dp(x)
    assert out.shape == [4, 4]
    assert len(dp.state_dict()) == len(model.state_dict())
    with dp.no_sync():
        pass


# ---------------------------------------------------------------------------
# fleet facade
# ---------------------------------------------------------------------------


def test_fleet_strategy_roundtrip(tmp_path):
    s = dist.fleet.DistributedStrategy()
    s.amp = True
    s.amp_configs = {"init_loss_scaling": 1024.0}
    s.recompute = True
    s.gradient_merge = True
    s.gradient_merge_configs = {"k_steps": 4, "avg": True}
    with pytest.raises(ValueError):
        s.amp = "yes"
    with pytest.raises(ValueError):
        s.amp_configs = {"bogus_key": 1}
    p = str(tmp_path / "strategy.json")
    s.save_to_prototxt(p)
    s2 = dist.fleet.DistributedStrategy()
    s2.load_from_prototxt(p)
    assert s2.amp and s2.gradient_merge_configs["k_steps"] == 4


def test_fleet_meta_optimizer_chain():
    s = dist.fleet.DistributedStrategy()
    s.amp = True
    s.recompute = True
    s.sharding = True
    s.sharding_configs = {"sharding_degree": 2, "stage": 1}
    s.gradient_merge = True
    s.gradient_merge_configs = {"k_steps": 2}
    dist.fleet.init(is_collective=True, strategy=s)
    applied = dist.fleet.applied_meta_list()
    for name in ("AMPOptimizer", "RecomputeOptimizer", "ShardingOptimizer",
                 "GradientMergeOptimizer"):
        assert name in applied, applied
    hcg = dist.fleet.get_hybrid_communicate_group()
    assert hcg.get_sharding_parallel_world_size() == 2


def test_fleet_train_step_runs():
    s = dist.fleet.DistributedStrategy()
    s.amp = True
    dist.fleet.init(is_collective=True, strategy=s)
    model = _MLP()
    opt = optimizer.Adam(learning_rate=1e-3,
                         parameters=model.parameters())
    dopt = dist.fleet.distributed_optimizer(opt)
    step = dist.fleet.train_step(model, _loss_fn, dopt)
    rng = np.random.default_rng(5)
    x = rng.standard_normal((16, 16)).astype(np.float32)
    y = rng.integers(0, 4, size=(16,)).astype(np.int64)
    l0 = float(step(paddle.to_tensor(x), paddle.to_tensor(y)))
    l1 = float(step(paddle.to_tensor(x), paddle.to_tensor(y)))
    assert np.isfinite(l0) and l1 < l0


def test_fleet_worker_queries():
    dist.fleet.init(is_collective=True)
    assert dist.fleet.worker_index() == 0
    assert dist.fleet.worker_num() >= 1
    assert dist.fleet.is_worker()
    dist.fleet.barrier_worker()


# ---------------------------------------------------------------------------
# pipeline
# ---------------------------------------------------------------------------


def test_pipeline_forward_matches_sequential():
    mesh = make_mesh({"pp": 4})
    set_mesh(mesh)
    L, B, D = 8, 8, 16
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((L, D, D)).astype(np.float32) * 0.1)
    x = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))

    def stage_fn(local_w, h):
        def layer(h, wi):
            return jnp.tanh(h @ wi), None
        out, _ = jax.lax.scan(layer, h, local_w)
        return out

    out = pipeline_forward(stage_fn, w, x, n_microbatches=4, mesh=mesh)

    ref = x
    for i in range(L):
        ref = jnp.tanh(ref @ w[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.slow      # heavy for the 870 s tier-1 cap (PR 21): -m slow
def test_pipeline_forward_differentiable():
    mesh = make_mesh({"pp": 2})
    set_mesh(mesh)
    L, B, D = 4, 4, 8
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((L, D, D)).astype(np.float32) * 0.1)
    x = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))

    def stage_fn(local_w, h):
        def layer(h, wi):
            return jnp.tanh(h @ wi), None
        out, _ = jax.lax.scan(layer, h, local_w)
        return out

    def loss(w):
        return jnp.sum(pipeline_forward(stage_fn, w, x, 2, mesh=mesh) ** 2)

    def ref_loss(w):
        h = x
        for i in range(L):
            h = jnp.tanh(h @ w[i])
        return jnp.sum(h ** 2)

    g = jax.grad(loss)(w)
    g_ref = jax.grad(ref_loss)(w)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-4,
                               atol=1e-5)


def test_pipeline_no_pp_axis_fallback():
    mesh = make_mesh({"dp": 8})
    set_mesh(mesh)
    w = jnp.ones((2, 4, 4), jnp.float32) * 0.1
    x = jnp.ones((4, 4), jnp.float32)

    def stage_fn(local_w, h):
        def layer(h, wi):
            return h @ wi, None
        out, _ = jax.lax.scan(layer, h, local_w)
        return out

    out = pipeline_forward(stage_fn, w, x, 2, mesh=mesh)
    ref = x @ w[0] @ w[1]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)


# ---------------------------------------------------------------------------
# ring attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_local(causal):
    mesh = make_mesh({"sp": 4})
    set_mesh(mesh)
    B, S, H, D = 2, 16, 2, 8
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
    out = ring_attention(q, k, v, causal=causal, mesh=mesh)
    ref = ring_attention_local(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.slow      # heavy for the 870 s tier-1 cap (PR 21): -m slow
def test_ring_attention_grad():
    mesh = make_mesh({"sp": 2})
    set_mesh(mesh)
    B, S, H, D = 1, 8, 1, 4
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))

    g = jax.grad(lambda q: jnp.sum(
        ring_attention(q, k, v, causal=True, mesh=mesh) ** 2))(q)
    g_ref = jax.grad(lambda q: jnp.sum(
        ring_attention_local(q, k, v, causal=True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-3,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# env / launch protocol
# ---------------------------------------------------------------------------


def test_parallel_env_reads_protocol(monkeypatch):
    monkeypatch.setenv("PADDLE_TRAINER_ID", "2")
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")
    monkeypatch.setenv("PADDLE_TRAINER_ENDPOINTS",
                       "10.0.0.1:6070,10.0.0.2:6070,"
                       "10.0.0.3:6070,10.0.0.4:6070")
    env = dist.ParallelEnv()
    assert env.rank == 2
    assert env.world_size == 4
    assert len(env.trainer_endpoints) == 4


def test_role_maker(monkeypatch):
    monkeypatch.setenv("PADDLE_TRAINER_ID", "1")
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "2")
    from paddle_tpu.distributed.fleet.role_maker import PaddleCloudRoleMaker
    rm = PaddleCloudRoleMaker(is_collective=True)
    assert rm.worker_index() == 1
    assert rm.worker_num() == 2
    assert rm.is_worker() and not rm.is_first_worker()

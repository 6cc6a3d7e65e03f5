"""Flagship model tests (GPT/BERT) on the 8-device virtual mesh.

Reference tier mapping (SURVEY.md §4): dist_transformer.py loss-parity
tests become "same model, different mesh layouts, same losses".
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import (Bert, GPT, bert_pretrain_loss, bert_tiny,
                               gpt_loss, gpt_tiny)
from paddle_tpu.parallel import ShardedTrainStep, make_mesh, set_mesh


@pytest.fixture(autouse=True)
def reset_mesh():
    set_mesh(make_mesh({"dp": 8}))
    yield
    set_mesh(make_mesh({"dp": 8}))


def _batch(vocab, B=8, S=32, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, size=(B, S)).astype(np.int32)


def test_gpt_forward_shapes():
    set_mesh(make_mesh({"dp": 1}))
    cfg = gpt_tiny(remat=False)
    model = GPT(cfg)
    ids = paddle.to_tensor(_batch(cfg.vocab_size, B=2, S=16))
    logits = model(ids)
    assert logits.shape == [2, 16, cfg.vocab_size]


def test_gpt_trains_eager_backward():
    set_mesh(make_mesh({"dp": 1}))
    cfg = gpt_tiny(num_layers=2, remat=False)
    model = GPT(cfg)
    ids = paddle.to_tensor(_batch(cfg.vocab_size, B=2, S=16))
    loss = gpt_loss(model, ids, ids)
    loss.backward()
    g = model.qkv_w.grad
    assert g is not None and np.isfinite(g.numpy()).all()


def test_gpt_flash_kernel_runs_per_device_under_a_mesh(monkeypatch):
    """GSPMD cannot partition a Mosaic custom call, so under dp > 1 the
    flash kernel runs in a manual region on each device's batch shard —
    and trains like one-device XLA attention."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    monkeypatch.setattr(fa, "_INTERPRET", True)
    local_batches = []
    kernel = fa.flash_attention

    def spy(q, *a, **kw):
        local_batches.append(q.shape[0])
        return kernel(q, *a, **kw)

    monkeypatch.setattr(fa, "flash_attention", spy)
    kw = dict(hidden_size=128, num_heads=2, num_layers=1, max_seq_len=128,
              remat=False)                     # head_dim 64: kernel shape
    ids = _batch(256, B=2, S=128)

    def losses(mesh_axes, flash):
        mesh = make_mesh(mesh_axes)
        set_mesh(mesh)
        model = GPT(gpt_tiny(use_flash_attention=flash, **kw))
        opt = optimizer.Adam(learning_rate=1e-3,
                             parameters=model.parameters())
        step = ShardedTrainStep(model, gpt_loss, opt, mesh=mesh,
                                sharding_stage=1)
        x = paddle.to_tensor(ids)
        return [float(step(x, x)) for _ in range(2)]

    want = losses({"dp": 1}, flash=False)
    assert not local_batches
    got = losses({"dp": 2}, flash=True)
    assert local_batches and set(local_batches) == {1}   # 2 rows / dp=2
    np.testing.assert_allclose(got, want, rtol=2e-3)


def _train_losses(mesh_axes, steps=3, sharding_stage=0, n_micro=1,
                  seed=0, remat=False):
    mesh = make_mesh(mesh_axes)
    set_mesh(mesh)
    cfg = gpt_tiny(seed=seed, remat=remat, n_microbatches=n_micro)
    model = GPT(cfg)
    opt = optimizer.Adam(learning_rate=1e-3,
                         parameters=model.parameters())
    step = ShardedTrainStep(model, gpt_loss, opt, mesh=mesh,
                            sharding_stage=sharding_stage)
    ids = paddle.to_tensor(_batch(cfg.vocab_size, B=8, S=32, seed=1))
    return [float(step(ids, ids)) for _ in range(steps)]


@pytest.mark.skip(
    reason="installed jax shard_map lacks partial-auto axes: the "
           "dp×pp×mp hybrid leg hits 'Axis: dp ... also found in "
           "manual_axes' from with_sharding_constraint in mesh.constrain")
def test_gpt_mesh_layouts_loss_parity():
    base = _train_losses({"dp": 8})
    for axes in ({"dp": 2, "mp": 4}, {"dp": 2, "pp": 2, "mp": 2},
                 {"dp": 4, "sharding": 2}):
        other = _train_losses(axes)
        np.testing.assert_allclose(base, other, rtol=5e-3,
                                   err_msg=f"mesh {axes}")
    assert base[-1] < base[0]


@pytest.mark.slow      # heavy for the 870 s tier-1 cap (PR 21): -m slow
def test_gpt_sp_ring_attention_parity():
    base = _train_losses({"dp": 8})
    sp = _train_losses({"dp": 2, "sp": 4})
    np.testing.assert_allclose(base, sp, rtol=5e-3)


@pytest.mark.slow      # heavy for the 870 s tier-1 cap (PR 21): -m slow
def test_gpt_remat_parity():
    base = _train_losses({"dp": 8}, remat=False)
    remat = _train_losses({"dp": 8}, remat=True)
    np.testing.assert_allclose(base, remat, rtol=1e-4)


@pytest.mark.skip(
    reason="installed jaxlib XLA spmd partitioner rejects the scan "
           "transpose of the zero-3 gather (s64 vs s32 compare inside "
           "dynamic_update_slice after spmd-partitioning, gpt.py remat "
           "scan); needs a jaxlib with the partitioner index-cast fix")
def test_gpt_zero3_parity():
    base = _train_losses({"dp": 8})
    z3 = _train_losses({"dp": 4, "sharding": 2}, sharding_stage=3)
    np.testing.assert_allclose(base, z3, rtol=5e-3)


def test_bert_forward_and_train():
    set_mesh(make_mesh({"dp": 8}))
    cfg = bert_tiny(remat=False)
    model = Bert(cfg)
    B, S = 8, 32
    ids = _batch(cfg.vocab_size, B=B, S=S)
    mlm_logits, nsp_logits = model(paddle.to_tensor(ids))
    assert mlm_logits.shape == [B, S, cfg.vocab_size]
    assert nsp_logits.shape == [B, 2]

    rng = np.random.default_rng(0)
    mlm_labels = np.where(rng.random((B, S)) < 0.15, ids, -100).astype(
        np.int32)
    nsp_labels = rng.integers(0, 2, size=(B,)).astype(np.int32)
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    mesh = make_mesh({"dp": 4, "mp": 2})
    set_mesh(mesh)
    step = ShardedTrainStep(model, bert_pretrain_loss, opt, mesh=mesh)
    losses = [float(step(paddle.to_tensor(ids),
                         paddle.to_tensor(mlm_labels),
                         paddle.to_tensor(nsp_labels))) for _ in range(3)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


def test_bert_remat_scan_flash_kernel_matches_xla_attention(monkeypatch):
    """BERT's layers run under ``lax.scan`` with per-layer remat, so the
    flash kernels are called from a scanned, checkpointed body: forward,
    forward again, dq, dk/dv.  The loss and every gradient agree with
    the XLA attention path (the kernels interpreted, non-causal, on the
    two-level nest)."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    set_mesh(make_mesh({"dp": 1}))
    calls = []
    kernel = fa.flash_attention

    def spy(q, *a, **kw):
        calls.append(tuple(q.shape))
        return kernel(q, *a, **kw)

    monkeypatch.setattr(fa, "flash_attention", spy)
    B, S = 2, 128
    rng = np.random.default_rng(3)
    ids = _batch(256, B=B, S=S)
    mlm = np.where(rng.random((B, S)) < 0.15, ids, -100).astype(np.int32)
    nsp = rng.integers(0, 2, size=(B,)).astype(np.int32)

    def loss_and_grads(interpret):
        monkeypatch.setattr(fa, "_INTERPRET", interpret)
        model = Bert(bert_tiny(hidden_size=128, num_heads=2, num_layers=2,
                               max_seq_len=S, remat=True, seed=5))
        loss = bert_pretrain_loss(model, paddle.to_tensor(ids),
                                  paddle.to_tensor(mlm),
                                  paddle.to_tensor(nsp))
        loss.backward()
        return float(loss), {n: p.grad.numpy()
                             for n, p in model.named_parameters()}

    want, want_grads = loss_and_grads(False)
    assert not calls                         # the CPU: XLA's attention
    got, got_grads = loss_and_grads(True)
    assert calls and set(calls) == {(B, S, 2, 64)}
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert got_grads.keys() == want_grads.keys()
    for name, g in want_grads.items():
        np.testing.assert_allclose(got_grads[name], g, rtol=2e-3,
                                   atol=2e-5 * max(1.0, np.abs(g).max()),
                                   err_msg=name)


def test_bert_flash_kernel_runs_per_device_under_a_mesh(monkeypatch):
    """Since PR 31 BERT's mask-free call at S = 512 goes to the kernels on
    the chip, so under dp > 1 it must run on each device's batch shard,
    as GPT's does, and train like one-device XLA attention."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    monkeypatch.setattr(fa, "_INTERPRET", True)
    local_batches = []
    kernel = fa.flash_attention

    def spy(q, *a, **kw):
        local_batches.append(q.shape[0])
        return kernel(q, *a, **kw)

    monkeypatch.setattr(fa, "flash_attention", spy)
    B, S = 4, 128
    rng = np.random.default_rng(4)
    ids = _batch(256, B=B, S=S)
    mlm = np.where(rng.random((B, S)) < 0.15, ids, -100).astype(np.int32)
    nsp = rng.integers(0, 2, size=(B,)).astype(np.int32)

    def losses(mesh_axes, flash):
        mesh = make_mesh(mesh_axes)
        set_mesh(mesh)
        model = Bert(bert_tiny(hidden_size=128, num_heads=2, num_layers=1,
                               max_seq_len=S, remat=True,
                               use_flash_attention=flash))
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=model.parameters())
        step = ShardedTrainStep(model, bert_pretrain_loss, opt, mesh=mesh)
        return [float(step(paddle.to_tensor(ids), paddle.to_tensor(mlm),
                           paddle.to_tensor(nsp))) for _ in range(2)]

    want = losses({"dp": 1}, flash=False)
    assert not local_batches
    got = losses({"dp": 2}, flash=True)
    assert local_batches and set(local_batches) == {B // 2}
    np.testing.assert_allclose(got, want, rtol=2e-3)


def test_gpt_hlo_has_hybrid_collectives():
    mesh = make_mesh({"dp": 2, "mp": 4})
    set_mesh(mesh)
    cfg = gpt_tiny(num_layers=2, remat=False)
    model = GPT(cfg)
    opt = optimizer.Adam(learning_rate=1e-3,
                         parameters=model.parameters())
    step = ShardedTrainStep(model, gpt_loss, opt, mesh=mesh)
    ids = _batch(cfg.vocab_size, B=8, S=32)
    hlo = step.lower_hlo(paddle.to_tensor(ids), paddle.to_tensor(ids))
    assert "all-reduce" in hlo

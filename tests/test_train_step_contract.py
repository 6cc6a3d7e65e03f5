"""What ``TrainStep.__call__`` promises, on the models and options the
benchmark's cells run: three calls train as three steps of the eager loop
(``loss.backward()``, ``opt.step()``), every option that keeps the
mathematics (``recompute``, ``accumulate_steps``, ``donate``) keeps the
trajectory, AMP O2 trains and repeats itself, one compile serves all three
calls, the live ``Layer`` and the optimizer's step count follow, and
building or calling a step writes no flag."""
import functools

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.framework import flags
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.bert import Bert, bert_pretrain_loss, bert_tiny
from paddle_tpu.models.gpt import GPT, gpt_loss, gpt_tiny
from paddle_tpu.models.nemotron_h import (NemotronH, nemotron_h_loss,
                                          nemotron_h_tiny)
from paddle_tpu.parallel import get_mesh, make_mesh, set_mesh

STEPS = 3
REL = 1e-5
BATCH, SEQ, VOCAB = 4, 32, 256


def _ids(rng):
    return rng.integers(0, VOCAB, (BATCH, SEQ)).astype(np.int32)


def _mlp():
    rng = np.random.default_rng(1)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    batch = (rng.standard_normal((BATCH, 8)).astype(np.float32),
             rng.standard_normal((BATCH, 4)).astype(np.float32))
    return net, lambda m, x, y: ((m(x) - y) ** 2).mean(), batch


def _gpt():
    ids = _ids(np.random.default_rng(2))
    return GPT(gpt_tiny(num_layers=2, remat=False)), gpt_loss, (ids, ids)


def _bert():
    rng = np.random.default_rng(3)
    ids = _ids(rng)
    # the same positions masked in every row: both halves of the batch
    # hold as many MLM targets, so the mean of two micro-batch losses is
    # the batch's loss (accumulate_steps=2)
    mlm = np.where(np.arange(SEQ) % 7 == 3, ids, -100).astype(np.int32)
    nsp = rng.integers(0, 2, (BATCH,)).astype(np.int32)
    return Bert(bert_tiny(remat=False)), bert_pretrain_loss, (ids, mlm, nsp)


def _nemotron_h():
    ids = _ids(np.random.default_rng(4))
    config = nemotron_h_tiny(hybrid_override_pattern="ME*")
    return NemotronH(config), nemotron_h_loss, (ids, ids)


MODELS = {"mlp": _mlp, "gpt": _gpt, "bert": _bert, "nemotron_h": _nemotron_h}

VARIANTS = {"plain": {}, "amp_o2": {"amp_level": "O2"},
            "recompute": {"recompute": True},
            "accumulate2": {"accumulate_steps": 2},
            "no_donate": {"donate": False}}


def _build(model, amp):
    """Model, loss, batch and optimizer from one seed.  Momentum for the
    float32 variants: its update is linear in the gradient, so a
    parameter whose gradient is rounding noise (a key bias under softmax)
    stays comparable; AdamW, the cells' optimizer, under O2."""
    paddle.seed(0)
    net, loss_fn, batch = MODELS[model]()
    if amp:
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=net.parameters())
    else:
        opt = paddle.optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                                        parameters=net.parameters())
    return net, loss_fn, [paddle.to_tensor(b) for b in batch], opt


@pytest.fixture(autouse=True)
def one_chip_mesh():
    """A one-device mesh, as the one-chip cells have; the mesh is global
    state and is put back."""
    mesh = get_mesh()
    set_mesh(make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    try:
        yield
    finally:
        set_mesh(mesh)


def _snapshot(net):
    return {n: np.array(p._data) for n, p in net.named_parameters()}


@functools.lru_cache(maxsize=None)
def _eager(model):
    """Losses and final parameters of STEPS steps of the eager loop."""
    net, loss_fn, batch, opt = _build(model, amp=False)
    losses = []
    for _ in range(STEPS):
        loss = loss_fn(net, *batch)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    return losses, _snapshot(net)


def _run(model, variant):
    """STEPS calls of a fresh step: (initial parameters, the arrays the
    first call was given, net, optimizer, step, losses)."""
    net, loss_fn, batch, opt = _build(model, amp=variant == "amp_o2")
    initial = _snapshot(net)
    first_arrays = {n: p._data for n, p in net.named_parameters()}
    step = TrainStep(net, loss_fn, opt, **VARIANTS[variant])
    losses = [float(step(*batch)) for _ in range(STEPS)]
    return initial, first_arrays, net, opt, step, losses


def _assert_close(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    err = np.max(np.abs(got - want))
    assert err <= REL * np.max(np.abs(want)) + 1e-9, (what, err)


@pytest.mark.parametrize("variant", list(VARIANTS))
@pytest.mark.parametrize("model", list(MODELS))
def test_three_calls_of_a_train_step(model, variant):
    flags_before = dict(flags._registry)
    initial, first_arrays, net, opt, step, losses = _run(model, variant)

    assert len(step._cache) == 1
    assert int(opt._global_step) == STEPS
    # the live Layer holds the step's outputs: readable (not donated
    # away), float32 master weights, moved
    final = _snapshot(net)
    for name, value in final.items():
        assert value.dtype == np.float32 and np.all(np.isfinite(value))
    assert any(np.any(final[n] != initial[n]) for n in final)

    if variant == "amp_o2":
        assert all(np.isfinite(losses))
        assert losses[2] < losses[1] < losses[0]
        _, _, again, _, _, losses_again = _run(model, variant)
        assert losses_again == losses
        for name, value in _snapshot(again).items():
            np.testing.assert_array_equal(value, final[name], err_msg=name)
    else:
        want_losses, want_params = _eager(model)
        _assert_close(losses, want_losses, "losses")
        for name, value in final.items():
            _assert_close(value, want_params[name], name)

    if variant == "no_donate":
        for name, array in first_arrays.items():
            np.testing.assert_array_equal(np.asarray(array), initial[name],
                                          err_msg=name)
    assert dict(flags._registry) == flags_before

"""Fused Pallas ops: blockwise linear+softmax-CE and fused adam.

Reference roles: softmax_with_cross_entropy_op.*, the operators/fused/
tier, and operators/optimizers/adam_op.* — kernels run in interpreter
mode on the CPU mesh, numerically checked against unfused XLA.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.ops.pallas import fused_adam, fused_ce

rng = np.random.default_rng(11)


@pytest.fixture(autouse=True)
def _interpret():
    fused_ce._INTERPRET = True
    fused_adam._INTERPRET = True
    yield
    fused_ce._INTERPRET = False
    fused_adam._INTERPRET = False


# -- fused CE ---------------------------------------------------------------

def test_ce_forward_matches_xla():
    # V=1000 is not a lane multiple → exercises the pad + iota mask
    N, H, V = 256, 256, 1000
    h = jnp.asarray(rng.standard_normal((N, H)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((V, H)) * 0.05, jnp.float32)
    lab = jnp.asarray(rng.integers(0, V, size=(N,)), jnp.int32)
    out = fused_ce.fused_linear_cross_entropy(h, w, lab)
    ref = fused_ce.xla_reference(h, w, lab)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ce_grads_match_xla():
    N, H, V = 256, 128, 777
    h = jnp.asarray(rng.standard_normal((N, H)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((V, H)) * 0.05, jnp.float32)
    lab = jnp.asarray(rng.integers(0, V, size=(N,)), jnp.int32)
    # non-uniform upstream cotangent (per-token mask-weighted mean)
    mask = jnp.asarray(rng.integers(0, 2, size=(N,)), jnp.float32)

    def loss(fn, h, w):
        return (fn(h, w, lab) * mask).sum() / mask.sum()

    gf = jax.grad(lambda h, w: loss(
        fused_ce.fused_linear_cross_entropy, h, w), argnums=(0, 1))(h, w)
    gr = jax.grad(lambda h, w: loss(
        fused_ce.xla_reference, h, w), argnums=(0, 1))(h, w)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_ce_negative_labels_zero_grad_when_masked():
    N, H, V = 128, 128, 384
    h = jnp.asarray(rng.standard_normal((N, H)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((V, H)) * 0.05, jnp.float32)
    lab = np.full((N,), -1, np.int32)
    lab[: N // 2] = rng.integers(0, V, size=(N // 2,))
    lab = jnp.asarray(lab)

    def loss(h):
        per_tok = fused_ce.fused_linear_cross_entropy(h, w, lab)
        m = (lab >= 0).astype(jnp.float32)
        return (per_tok * m).sum() / m.sum()

    dh = jax.grad(loss)(h)
    # masked rows must receive exactly zero gradient
    np.testing.assert_array_equal(np.asarray(dh[N // 2:]), 0.0)
    assert float(jnp.abs(dh[: N // 2]).max()) > 0


def test_gpt_loss_fused_path_matches_xla_path():
    from paddle_tpu.framework import flags
    from paddle_tpu.models import GPT, gpt_loss, gpt_tiny

    from paddle_tpu.parallel.mesh import get_mesh, make_mesh, set_mesh

    # hidden_size must satisfy fused_ce.supported (H % 128 == 0) or the
    # flag silently falls through to the unfused path and the test
    # compares XLA with itself
    cfg = gpt_tiny(num_layers=2, remat=False, hidden_size=128)
    model = GPT(cfg)
    assert fused_ce.supported(2 * 128, cfg.hidden_size)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, size=(2, 128)).astype(np.int32))
    prev = get_mesh()
    set_mesh(make_mesh({"dp": 1}))       # fused path is single-device-only
    try:
        base = float(gpt_loss(model, ids, ids))
        old = flags.flag("gpt_fused_ce")
        flags.set_flags({"gpt_fused_ce": True})
        try:
            fused = float(gpt_loss(model, ids, ids))
        finally:
            flags.set_flags({"gpt_fused_ce": old})
    finally:
        set_mesh(prev)
    assert abs(base - fused) < 1e-3, (base, fused)


# -- fused adam -------------------------------------------------------------

def test_fused_adam_matches_reference():
    shape = (317, 53)        # awkward size → both pad paths
    p = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    g = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    m = jnp.asarray(rng.standard_normal(shape) * 0.1, jnp.float32)
    v = jnp.asarray(np.abs(rng.standard_normal(shape)) * 0.01, jnp.float32)
    kw = dict(lr_t=1e-3, beta1=0.9, beta2=0.999, eps=1e-8, wd_lr=1e-4)
    out = fused_adam.fused_adam_update(p, g, m, v, **kw)
    ref = fused_adam.xla_reference(p, g, m, v, **kw)
    for a, b in zip(out, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("cls", [
    pytest.param("Adam", marks=pytest.mark.slow),    # 12-16 s each: AdamW,
    "AdamW"])                                        # the superset, stays
def test_optimizer_use_fused_converges_like_unfused(cls):
    from paddle_tpu import optimizer

    def train(use_fused):
        np.random.seed(0)
        paddle.seed(0)
        net = nn.Linear(4, 1)
        opt_cls = getattr(optimizer, cls)
        opt = opt_cls(learning_rate=0.1, parameters=net.parameters(),
                      use_fused=use_fused)
        x = np.random.randn(64, 4).astype("float32")
        y = x @ np.ones((4, 1), "float32")
        for _ in range(40):
            loss = ((net(paddle.to_tensor(x)) - paddle.to_tensor(y)) ** 2
                    ).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
        return float(loss)

    l_fused = train(True)
    l_plain = train(False)
    assert l_fused < 0.05
    assert abs(l_fused - l_plain) < 1e-3, (l_fused, l_plain)


# -- non-divisible / zero-length token axis ---------------------------------

@pytest.mark.parametrize("n", [
    pytest.param(300, marks=pytest.mark.slow), 257, 1])
def test_ce_non_divisible_tokens_match_xla(n):
    """N that doesn't divide the block rides zero-padded rows (the
    PTA601 fix) — loss and both grads pinned against the reference."""
    H, V = 128, 1000
    h = jnp.asarray(rng.standard_normal((n, H)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((V, H)).astype(np.float32))
    lab = jnp.asarray(rng.integers(0, V, size=(n,)), dtype=jnp.int32)
    assert fused_ce.supported(n, H)
    out = fused_ce.fused_linear_cross_entropy(h, w, lab)
    ref = fused_ce.xla_reference(h, w, lab)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    gf = jax.grad(lambda h, w: fused_ce.fused_linear_cross_entropy(
        h, w, lab).mean(), argnums=(0, 1))(h, w)
    gr = jax.grad(lambda h, w: fused_ce.xla_reference(
        h, w, lab).mean(), argnums=(0, 1))(h, w)
    for a, b, name in zip(gf, gr, ["dh", "dw"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4, err_msg=name)


def test_ce_zero_length_rows():
    """N=0 short-circuits before the kernels: empty loss, zero grads."""
    H, V = 128, 260
    h = jnp.zeros((0, H), jnp.float32)
    w = jnp.asarray(rng.standard_normal((V, H)).astype(np.float32))
    lab = jnp.zeros((0,), jnp.int32)
    assert fused_ce.supported(0, H)
    out = fused_ce.fused_linear_cross_entropy(h, w, lab)
    assert out.shape == (0,)
    gf = jax.grad(lambda h, w: fused_ce.fused_linear_cross_entropy(
        h, w, lab).sum(), argnums=(0, 1))(h, w)
    assert gf[0].shape == (0, H)
    np.testing.assert_array_equal(np.asarray(gf[1]), 0.0)

"""The routed experts over the expert-sorted row buffer
(``nn/functional/moe.py`` ``_sorted_experts``, kernels in
``ops/pallas/grouped_matmul.py`` in interpret mode) against the dense
mask, at lane-aligned toy widths that the shape gate accepts: values and
the gradients of ``z``, ``w1``, ``w2`` and the gates at every routing the
buffer can meet, its two extremes included; the layer through
``latent_moe`` with its counters; how often a stack of layers traces a
kernel body; what the analysis recorder sees; what ``import paddle_tpu``
leaves unimported.
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework import monitor
from paddle_tpu.nn.functional import moe
from paddle_tpu.ops.pallas import grouped_matmul as gmm

HELD, LATENT, INNER, TOP_K, N_ROUTED = 4, 128, 256, 4, 16


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(gmm, "_INTERPRET", True)


def assert_close(got, want, tol=2e-5, what=""):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, (what, got.shape, want.shape)
    worst = np.abs(got - want).max() / max(np.abs(want).max(), 1e-12)
    assert worst <= tol, (what, worst)


def _weights(rng):
    return (jnp.asarray(0.1 * rng.standard_normal((HELD, LATENT, INNER)),
                        jnp.float32),
            jnp.asarray(0.1 * rng.standard_normal((HELD, INNER, LATENT)),
                        jnp.float32))


def _routing(rng, tokens, which):
    """``sel`` (tokens, TOP_K) distinct experts of N_ROUTED, the held
    ones being 0..HELD-1, and ``g`` (tokens, TOP_K)."""
    absent = np.arange(HELD, N_ROUTED)
    if which == "all_to_all_held":
        sel = np.tile(np.arange(HELD), (tokens, 1))
    elif which == "all_to_one":
        sel = np.stack([np.r_[2, rng.permutation(absent)[:TOP_K - 1]]
                        for _ in range(tokens)])
    elif which == "none_held":
        sel = np.stack([rng.permutation(absent)[:TOP_K]
                        for _ in range(tokens)])
    else:
        sel = np.stack([rng.permutation(N_ROUTED)[:TOP_K]
                        for _ in range(tokens)])
    g = rng.random((tokens, TOP_K)) + 0.5
    return jnp.asarray(sel, jnp.int32), jnp.asarray(g, jnp.float32)


# (routing, tokens): every token to one held expert fills four tiles of
# 256 rows and leaves the three other experts a tile of padding each; every
# token to all four held experts fills the buffer to its last row; 600
# tokens end in the middle of a tile
CASES = [("as_drawn", 1024), ("all_to_one", 1024), ("none_held", 1024),
         ("all_to_all_held", 1024), ("as_drawn", 600)]


@pytest.mark.parametrize(
    "which,tokens", CASES,
    ids=["as_drawn", "all_to_one", "none_held", "all_to_all_held",
         "no_multiple_of_the_row_tile"])
def test_sorted_rows_equal_the_dense_mask(interpret, which, tokens):
    rng = np.random.default_rng(tokens + len(which))
    w1, w2 = _weights(rng)
    z = jnp.asarray(rng.standard_normal((tokens, LATENT)), jnp.float32)
    weight = jnp.asarray(rng.standard_normal((tokens, LATENT)), jnp.float32)
    sel, g = _routing(rng, tokens, which)
    hit = moe.held_gates(sel, jnp.ones_like(g), HELD, 0) > 0
    load = np.asarray(hit).sum(0).tolist()
    assert load == {"all_to_one": [0, 0, tokens, 0],
                    "none_held": [0] * HELD,
                    "all_to_all_held": [tokens] * HELD}.get(which, load)
    used = moe._row_plan(hit, moe.held_gates(sel, g, HELD, 0), gmm.TILE_ROWS,
                         moe._buffer_tiles(tokens, HELD, TOP_K,
                                           gmm.TILE_ROWS))[1]
    assert int(used[0]) == sum(max(1, -(-n // gmm.TILE_ROWS)) for n in load)

    def sorted_rows(z, w1, w2, g):
        y = moe._sorted_experts(z, w1, w2, moe.held_gates(sel, g, HELD, 0),
                                hit, TOP_K)
        return jnp.sum(y * weight), y

    def dense_mask(z, w1, w2, g):
        y = moe._dense_experts(z, w1, w2, moe.held_gates(sel, g, HELD, 0))
        return jnp.sum(y * weight), y

    (_, got), got_grads = jax.jit(jax.value_and_grad(
        sorted_rows, argnums=(0, 1, 2, 3), has_aux=True))(z, w1, w2, g)
    (_, want), want_grads = jax.jit(jax.value_and_grad(
        dense_mask, argnums=(0, 1, 2, 3), has_aux=True))(z, w1, w2, g)
    assert_close(got, want, what="y")
    if which != "none_held":
        assert np.abs(np.asarray(want)).max() > 0
    for name, a, b in zip(("z", "w1", "w2", "gates"), got_grads, want_grads):
        assert_close(a, b, what=name)


def _layer(rng, tokens, hidden=64):
    shapes = {"router_w": (N_ROUTED, hidden), "down_w": (hidden, LATENT),
              "up_w": (LATENT, hidden), "shared_w1": (hidden, 96),
              "shared_w2": (96, hidden)}
    p = {n: jnp.asarray(0.2 * rng.standard_normal(s), jnp.float32)
         for n, s in shapes.items()}
    p["w1"], p["w2"] = _weights(rng)
    u = jnp.asarray(rng.standard_normal((1, tokens, hidden)), jnp.float32)
    return p, u


def _latent_moe(u, p):
    return moe.latent_moe(
        u, p["router_w"], jnp.zeros(N_ROUTED), p["down_w"], p["w1"],
        p["w2"], p["up_w"], p["shared_w1"], p["shared_w2"], top_k=TOP_K,
        scale=2.5, expert_offset=0)


def test_the_layer_takes_the_sorted_path_where_the_gate_accepts(monkeypatch):
    """``latent_moe`` at widths the gate accepts equals itself on the
    dense mask (the gate closed: no TPU, no interpret mode), output and
    every gradient, and counts the rows of its own path."""
    p, u = _layer(np.random.default_rng(11), 512)
    weight = jnp.asarray(np.random.default_rng(12).standard_normal(u.shape),
                         jnp.float32)

    def run():
        monitor.reset_all_stats()
        out = jax.jit(jax.value_and_grad(
            lambda u, p: jnp.sum(_latent_moe(u, p) * weight),
            argnums=(0, 1)))(u, p)
        return out, dict(monitor.all_stats())

    (want, want_grads), dense_stats = run()
    monkeypatch.setattr(gmm, "_INTERPRET", True)
    (got, got_grads), stats = run()
    assert_close(got, want, what="loss")
    assert_close(got_grads[0], want_grads[0], what="du")
    for name in p:
        assert_close(got_grads[1][name], want_grads[1][name], what=name)
    calls = stats["moe_calls_traced_total"]
    assert calls == dense_stats["moe_calls_traced_total"] >= 1
    assert dense_stats["moe_expert_rows_computed_total"] / calls == \
        HELD * 512                                  # the dense mask
    # 512 tokens x 4 of 16: 128 rows an expert, one tile of 256 each
    assert stats["moe_expert_rows_computed_total"] / calls == \
        HELD * gmm.TILE_ROWS
    assert stats["moe_expert_rows_expected_total"] / calls == \
        512 * TOP_K * HELD / N_ROUTED


def test_the_gate_declines_what_the_kernels_cannot_tile(interpret):
    assert gmm.supported(4096, 1024, 2688, jnp.bfloat16)
    assert gmm.supported(256, 128, 256, jnp.float32)
    # a token copy too wide for VMEM is held a block of columns at a time
    assert gmm.supported(8192, 1024, 2688, jnp.bfloat16)
    assert gmm.supported(8192, 2560, 768, jnp.bfloat16)
    assert not gmm.supported(65536, 256, 256, jnp.bfloat16)  # no block fits
    assert not gmm.supported(255, 128, 256, jnp.float32)    # no row tile
    assert not gmm.supported(4096, 32, 48, jnp.float32)     # nemotron_h_tiny
    assert not gmm.supported(4096, 1024, 2688, jnp.float16)


def test_the_gate_is_closed_off_the_tpu():
    assert jax.default_backend() == "cpu"
    assert not gmm.supported(4096, 1024, 2688, jnp.bfloat16)


KERNELS = ("_gather_kernel", "_rows_kernel", "_weights_kernel",
           "_scatter_kernel")


@pytest.mark.parametrize("layers", [3, 5])
def test_a_stack_traces_each_kernel_body_once(interpret, monkeypatch,
                                              layers):
    """Value-and-gradient of a stack of checkpointed expert layers enters
    a kernel body once for each distinct variant, whatever the number of
    layers, not 4 + 4 + 6 times for every layer.  Nine variants: the
    gather plain and gated, the grouped matmul four ways (into relu, from
    its square, and both with the weights transposed), the weight
    gradient two ways, the scatter.  Tokens that no other test uses, so
    that the trace cache starts empty."""
    tokens = 256 * (layers + 4)
    entered = []

    def counting(body):
        def kernel(*refs, **static):
            entered.append((body.__name__, tuple(sorted(static.items()))))
            return body(*refs, **static)
        return kernel

    for name in KERNELS:
        monkeypatch.setattr(gmm, name, counting(getattr(gmm, name)))
    p, u = _layer(np.random.default_rng(layers), tokens)

    def stack(u, p):
        for i in range(layers):
            # a function of its own for every layer, as the model's
            # blocks are: jax.checkpoint finds none of them traced
            u = u + jax.checkpoint(lambda u, p, i=i: _latent_moe(u, p))(u, p)
        return jnp.sum(u)

    jax.jit(jax.value_and_grad(stack, argnums=(0, 1))).trace(u, p)
    assert len(entered) == len(set(entered)) == 9, entered


def test_the_analysis_recorder_is_not_served_a_cached_trace(interpret):
    """``framework.analysis`` swaps ``pl.pallas_call`` for a recorder: it
    sees the forward's four kernels though the same shapes were traced
    for real just before, and the real call is found again afterwards."""
    from paddle_tpu.framework.analysis.pallas_kernels import trace_kernels
    p, u = _layer(np.random.default_rng(21), 768)
    want = jax.jit(_latent_moe)(u, p)
    seen = [m.kernel_name for m in trace_kernels(_latent_moe, u, p)]
    assert seen == ["_gather_kernel", "_rows_kernel", "_rows_kernel",
                    "_scatter_kernel"]
    assert np.array_equal(np.asarray(jax.jit(_latent_moe)(u, p)),
                          np.asarray(want))


def _jaxpr_digest(fn, *args):
    import hashlib
    return hashlib.sha256(str(jax.make_jaxpr(fn)(*args)).encode()).hexdigest()


def test_at_one_group_the_router_and_the_sorted_rows_trace_as_before(
        interpret):
    """The latent experts' program is what it was before the group limit
    and the SwiGLU path were added: the router at ``n_group`` 1 and the
    sorted rows' value and gradients give the jaxpr whose text hashed so
    on the tree before them (the same shapes, interpret mode)."""
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((1, 512, 64)), jnp.float32)
    router_w = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    bias = jnp.zeros(16)
    assert _jaxpr_digest(
        lambda u, w, b: moe.route_top_k(u, w, b, 4, 2.5), u, router_w,
        bias) == ("bac0a985ff86ad66f02e3dd5f63f88ac"
                  "65b732a405d423980132bf349320173f")
    z = jnp.asarray(rng.standard_normal((512, 128)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((4, 128, 256)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((4, 256, 128)), jnp.float32)
    gates = jnp.asarray(rng.random((512, 4)), jnp.float32)
    hit = gates > 0.5

    def f(z, w1, w2, gates):
        return jnp.sum(moe._sorted_experts(z, w1, w2, gates, hit, 4))

    assert _jaxpr_digest(jax.value_and_grad(f, argnums=(0, 1, 2, 3)), z, w1,
                         w2, gates) == ("ddd9d98c09312741244347784843f105"
                                        "ec8bdc6f9ce88614862ad5b23fb052e8")


def test_import_paddle_tpu_leaves_the_kernels_unimported():
    code = ("import sys, paddle_tpu; "
            "assert 'paddle_tpu.nn.functional.moe' in sys.modules; "
            "bad = [m for m in sys.modules if 'grouped_matmul' in m "
            "or m.startswith('jax.experimental.pallas')]; "
            "assert not bad, bad")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]

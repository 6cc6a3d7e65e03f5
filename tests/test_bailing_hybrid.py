"""BailingHybrid (``models/bailing_hybrid.py``, ``nn/functional/kda.py``,
``rotary.py`` and ``moe.py``'s SwiGLU experts) against its plain
reference (``benchmarks/reference/ling3_flash.py``): float32, seeded
weights, tiny sizes, on the CPU.  The chunked delta rule against the
token recurrence; the whole model, loss and every gradient under a bias
that decides; the expert shares; SwiGLU over the sorted rows against the
dense mask (kernels in interpret mode); the rotary embedding against its
formula; the latent-attention core on the flash kernels against XLA; the
group-limited router; ``TrainStep`` under AMP O2 with its scopes and
counters.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.reference import ling3_flash as ref
from paddle_tpu.framework import monitor
from paddle_tpu.jit import TrainStep, functional_loss_call
from paddle_tpu.models import (BailingHybrid, BailingHybridConfig,
                               bailing_hybrid_loss, bailing_hybrid_tiny)
from paddle_tpu.models import bailing_hybrid as bh
from paddle_tpu.nn.functional import kda, moe, rotary
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import grouped_matmul as gmm
from paddle_tpu.ops.pallas import kda_carry as kc
from paddle_tpu.parallel import get_mesh, make_mesh, set_mesh

SIZE_KEYS = ("num_hidden_layers", "layer_group_size", "first_k_dense_replace",
             "hidden_size", "vocab_size", "num_attention_heads", "head_dim",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "rope_theta", "intermediate_size",
             "moe_intermediate_size", "moe_shared_expert_intermediate_size",
             "num_experts_per_tok", "n_group", "topk_group",
             "routed_scaling_factor", "short_conv_kernel_size",
             "kda_lower_bound", "kda_chunk_size", "rms_norm_eps",
             "expert_offset")


def sizes_of(c: BailingHybridConfig) -> dict:
    """The reference's ``sizes`` of a program config (as in
    ``benchmarks/configs/ling3_flash.json``): ``n_routed_experts`` the
    experts held, ``router_width`` all of them."""
    return {**{k: getattr(c, k) for k in SIZE_KEYS},
            "n_routed_experts": c.experts_held, "router_width": c.num_experts}


def assert_close(got, want, tol=2e-4, what=""):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, (what, got.shape, want.shape)
    worst = np.abs(got - want).max() / max(np.abs(want).max(), 1e-12)
    assert worst <= tol, (what, worst)


def _normal(rng, shape, std=1.0):
    return jnp.asarray(std * rng.standard_normal(shape), jnp.float32)


@pytest.fixture
def one_device():
    """A mesh of one device, as the cell's (the kernels' calls are wrapped
    per device under a larger one); the global mesh put back after."""
    mesh = get_mesh()
    set_mesh(make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    yield
    set_mesh(mesh)


# -- the chunked delta rule against the token recurrence ----------------------

def _recurrence(q, k, v, g, beta):
    """``S_t = (I - b k k^T) Diag(exp g) S_{t-1} + b k v^T``, ``o_t =
    S_t^T q_t``, one position at a time."""
    bsz, _, heads, dk = q.shape

    def step(s, now):
        q_t, k_t, v_t, g_t, b_t = now
        s = jnp.exp(g_t)[..., None] * s
        error = v_t - jnp.einsum("bhk,bhkv->bhv", k_t, s)
        s = s + b_t[..., None, None] * k_t[..., :, None] * error[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((bsz, heads, dk, v.shape[-1])),
                        tuple(jnp.moveaxis(t, 1, 0)
                              for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


@pytest.mark.parametrize("seq, chunk, gate, width", [
    (64, 64, "at_the_bound", 0), (200, 64, "at_the_bound", 0),
    (96, 32, "near_zero", 0), (160, 32, "mixed", 0),
    (128, 64, "keys_alike", 0), (200, 64, "at_the_bound", 128),
    (320, 64, "keys_alike", 128)],
    ids=["one_chunk", "four_chunks_padded", "three_chunks_near_zero",
         "five_chunks_mixed", "two_chunks_keys_alike",
         "four_chunks_padded_carry_kernel",
         "five_chunks_keys_alike_carry_kernel"])
def test_chunked_delta_rule_equals_the_token_recurrence(seq, chunk, gate,
                                                        width, monkeypatch):
    """Output and the gradient of every input.  At the bound a chunk of
    64 decays by e^-320 from its first position to its last, which only
    the sub-chunks keep inside float32.  ``keys_alike``: keys of positive
    entries, as after the convolution's SiLU, whose dot products near 1
    make the within-chunk inverse's Neumann powers grow as binomial
    coefficients (a product of powers returned garbage there).  The state
    pass's kernels (interpret mode) take lane-wide heads (``width`` 128:
    then also against the ``lax.scan`` path); the narrow ones stay on
    the scan."""
    monkeypatch.setattr(kc, "_INTERPRET", True)
    rng = np.random.default_rng(seq + chunk)
    b, h, dk, dv = (2, 2, width, width) if width else (2, 3, 16, 8)
    sign = np.abs if gate == "keys_alike" else (lambda x: x)
    q, k = (kda.l2_norm(jnp.asarray(sign(rng.standard_normal(
        (b, seq, h, dk))), jnp.float32)) for _ in range(2))
    v = _normal(rng, (b, seq, h, dv))
    frac = {"at_the_bound": 1.0 - 1e-3 * rng.random((b, seq, h, dk)),
            "near_zero": 1e-3 * rng.random((b, seq, h, dk)),
            "mixed": rng.random((b, seq, h, dk)),
            "keys_alike": 1e-2 * rng.random((b, seq, h, dk))}[gate]
    g = jnp.asarray(-5.0 * frac, jnp.float32)
    beta = jnp.asarray(rng.random((b, seq, h)), jnp.float32)
    weight = _normal(rng, (b, seq, h, dv))

    def value_and_grads(fn):
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2, 3, 4)
            ))(q, k, v, g, beta)

    def chunked(*a):
        return kda.kda_chunked(*a, chunk=chunk)

    monitor.reset_all_stats()
    got, got_grads = value_and_grads(chunked)
    assert monitor.get_stat("kda_chunks_traced_total") \
        == b * h * -(-seq // chunk)
    assert monitor.get_stat("kda_carry_kernel_total") == (1 if width else 0)
    wants = [value_and_grads(_recurrence)]
    if width:
        monkeypatch.setattr(kc, "_INTERPRET", False)
        monitor.reset_all_stats()
        wants.append(value_and_grads(chunked))
        assert monitor.get_stat("kda_carry_kernel_total") == 0
    for want, want_grads in wants:
        assert float(got) == pytest.approx(float(want), rel=1e-5)
        for name, a, w in zip("q k v g beta".split(), got_grads, want_grads):
            assert np.isfinite(np.asarray(a)).all(), name
            assert_close(a, w, tol=5e-5, what=name)


def _state_pass_inputs(rng, bsz, n, heads, chunk, dk, dv):
    """Random ``W``, ``U``, ``QG``, ``A``, ``Kt`` and ``Gamma_C`` of the
    state pass: ``W`` and ``Kt`` small and ``Gamma_C`` in (0.5, 0.95), so
    that ``Diag(Gamma_C) - Kt^T W`` keeps the state bounded, as the rule's
    does; ``A`` lower triangular with its diagonal."""
    lead = (bsz, n, heads)
    lower = np.tril(np.ones((chunk, chunk), np.float32))
    return (_normal(rng, lead + (chunk, dk), 0.05),
            _normal(rng, lead + (chunk, dv)),
            _normal(rng, lead + (chunk, dk)),
            _normal(rng, lead + (chunk, chunk), chunk ** -0.5) * lower,
            _normal(rng, lead + (chunk, dk), 0.05),
            jnp.asarray(rng.uniform(0.5, 0.95, lead + (1, dk)), jnp.float32))


@pytest.mark.parametrize("heads, block_bytes, n",
                         [(2, None, 6), (3, 1, 5)],
                         ids=["heads_in_one_block", "a_head_a_block"])
@pytest.mark.parametrize("cotangent",
                         ["every_chunk", "last_chunk_only", "first_chunk_only"])
def test_state_pass_kernels_and_their_vjp_equal_the_scan(heads, block_bytes,
                                                         n, cotangent,
                                                         monkeypatch):
    """``kda_carry.state_pass`` (interpret mode) and its hand-written
    backward against ``kda_carry.scan_pass``, the same step in a
    ``lax.scan``, and jax's transpose of it, on random inputs and ``dO``;
    ``d_k`` 128 and ``d_v`` 256, so that a transposed state or product
    cannot pass; six chunks walk two a grid step, five one.
    ``last_chunk_only``: ``dO`` is zero but for the last
    chunk, read first by the reverse walk; its cotangent reaches ``dU``
    of the first chunk only through every state in between.
    ``first_chunk_only``: ``dO`` is zero but for the first chunk, read
    last, on the state entering it, which is zero whatever the inputs
    are: only that chunk's ``dU`` and ``dA`` are not zero."""
    monkeypatch.setattr(kc, "_INTERPRET", True)
    if block_bytes:
        monkeypatch.setattr(kc, "_BLOCK_BYTES", block_bytes)
    bsz, chunk, dk, dv = 2, 32, 128, 256
    p = kc._chunks_per_step(n)
    assert p == (2 if n == 6 else 1)
    assert kc._heads_per_block(heads, p, dk, dv) \
        == (1 if block_bytes else heads)
    rng = np.random.default_rng(heads)
    ins = _state_pass_inputs(rng, bsz, n, heads, chunk, dk, dv)
    do = _normal(rng, (bsz, n, heads, chunk, dv))
    if cotangent == "last_chunk_only":
        do = do.at[:, :-1].set(0.0)
    if cotangent == "first_chunk_only":
        do = do.at[:, 1:].set(0.0)
    got, got_vjp = jax.vjp(
        lambda *a: kc.state_pass(jnp.float32, *a), *ins)
    want, want_vjp = jax.vjp(
        lambda *a: kc.scan_pass(jnp.float32, *a), *ins)
    names = ("dW", "dU", "dQG", "dA", "dKt", "dGamma_C")
    grads, want_grads = got_vjp(do), want_vjp(do)
    assert_close(got, want, tol=1e-6, what="O")
    if cotangent == "first_chunk_only":
        for name, a in zip(names, grads):
            rest = a if name not in ("dU", "dA") else a[:, 1:]
            assert not np.asarray(rest).any(), name
    if cotangent == "last_chunk_only":
        assert np.abs(np.asarray(want_grads[1][:, 0])).max() > 0.1
    for name, a, w in zip(names, grads, want_grads):
        assert_close(a, w, tol=1e-6, what=name)


def test_a_bf16_state_rounds_alike_in_both_executors(monkeypatch):
    """The state's dtype reaches the kernels (``tools/ling3_check.py``'s
    bf16 control runs on them): with the state held in bf16, the kernel
    (interpret mode) and the scan agree, and both stand apart from the
    float32 state by more than the scan's limit against the recurrence
    there (1e-4)."""
    monkeypatch.setattr(kc, "_INTERPRET", True)
    ins = _state_pass_inputs(np.random.default_rng(7), 1, 6, 2, 32, 128,
                             128)
    got = kc.state_pass(jnp.bfloat16, *ins)
    assert_close(got, kc.scan_pass(jnp.bfloat16, *ins), tol=1e-6,
                 what="O, bf16 state")
    wide = kc.scan_pass(jnp.float32, *ins)
    worst = np.abs(np.asarray(got) - np.asarray(wide)).max()
    assert worst > 1e-4 * np.abs(np.asarray(wide)).max()


def test_the_kernel_path_builds_no_matrix_of_a_chunk(monkeypatch):
    """The traced forward and backward of ``kda_chunked`` on the kernels
    (interpret mode, heads 128 wide) hold no array shaped (batch, chunks,
    heads, d_k, d_k), the per-chunk ``M`` of a carry ``S <- M S + B``:
    the state pass takes ``Delta`` and ``O`` from the state in VMEM.
    ``d_v`` 256 keeps the backward's residual (.., d_v, d_k) apart."""
    monkeypatch.setattr(kc, "_INTERPRET", True)
    rng = np.random.default_rng(5)
    b, seq, h, dk, dv, chunk = 1, 256, 2, 128, 256, 64
    q, k = (kda.l2_norm(_normal(rng, (b, seq, h, dk))) for _ in range(2))
    v = _normal(rng, (b, seq, h, dv))
    g = jnp.asarray(-5.0 * rng.random((b, seq, h, dk)), jnp.float32)
    beta = jnp.asarray(rng.random((b, seq, h)), jnp.float32)

    def shapes(jaxpr):
        for eqn in jaxpr.eqns:
            yield from (getattr(x.aval, "shape", None) for x in eqn.outvars)
            for param in eqn.params.values():
                for sub in (param if isinstance(param, (tuple, list))
                            else (param,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from shapes(sub)

    monitor.reset_all_stats()
    traced = jax.make_jaxpr(jax.value_and_grad(
        lambda *a: jnp.sum(kda.kda_chunked(*a, chunk=chunk)),
        argnums=(0, 1, 2, 3, 4)))(q, k, v, g, beta)
    assert monitor.get_stat("kda_carry_kernel_total") == 1
    seen = set(shapes(traced.jaxpr))
    n = seq // chunk
    assert (b, n, h, dv, dk) in seen            # the state, as the residual
    assert (b, n, h, dk, dk) not in seen


def test_the_gate_keeps_each_step_within_its_bound():
    f = jnp.asarray(np.linspace(-50, 50, 24).reshape(1, 1, 2, 12),
                    jnp.float32)
    g = kda.kda_gate(f, jnp.log(jnp.asarray([1.0, 16.0])),
                     jnp.zeros((2, 12)), -5.0)
    assert float(g.min()) >= -5.0 and float(g.max()) <= 0.0
    assert float(g.min()) < -4.99 and float(g.max()) > -0.01
    with pytest.raises(ValueError, match="float32"):
        bailing_hybrid_tiny(kda_lower_bound=-6.0)


# -- the whole model against the reference ------------------------------------

def _drawn(model, seed):
    """The zero / one initial values hide faults: draw them; and raise the
    routed experts to the shared expert's size."""
    rng = np.random.default_rng(seed)
    for name in ("k_norm", "k_onorm_w", "a_norm", "a_kv_norm", "a_q_norm",
                 "a_k_norm", "d_norm", "e_norm", "norm_f"):
        t = model._parameters[name]
        t._data = t._data + _normal(rng, t.shape, 0.3)
    t = model._parameters["e_w2"]
    t._data = t._data * 10.0


def _program_loss(model):
    params = {n: t._data for n, t in model.named_parameters()}
    buffers = {n: t._data for n, t in model.named_buffers()}

    def program(params, ids):
        return functional_loss_call(
            model, bailing_hybrid_loss, params, buffers,
            jax.random.PRNGKey(0), [ids, ids])[0]

    return program, params, buffers


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_model_under_a_bias_agrees_with_the_reference(path, monkeypatch,
                                                      one_device):
    """Loss and every parameter's gradient against ``loss_and_grads`` with
    the buffer filled as the harness fills it: a bias that decides
    (expert 1, held, for every token; expert 6, absent, for none).
    ``kernels``: the routed experts over the sorted rows, the MLA core on
    the flash kernels and KDA's state pass on its kernels, all in
    interpret mode."""
    kernels = path == "kernels"
    monkeypatch.setattr(gmm, "_INTERPRET", kernels)
    monkeypatch.setattr(fa, "_INTERPRET", kernels)
    monkeypatch.setattr(kc, "_INTERPRET", kernels)
    # lane-wide experts for the sorted rows, MLA at the published widths
    # for the flash kernels, KDA heads at the published 128
    wide = dict(hidden_size=128, moe_intermediate_size=128,
                qk_nope_head_dim=128, qk_rope_head_dim=64,
                v_head_dim=128, head_dim=128) if kernels else {}
    c = bailing_hybrid_tiny(remat=kernels, seed=5, **wide)
    model = BailingHybrid(c)
    _drawn(model, 9)
    rng = np.random.default_rng(23)
    bias = (0.05 * rng.standard_normal((3, c.num_experts))).astype(np.float32)
    bias[:, 1], bias[:, 6] = 5.0, -5.0
    model.set_state_dict({"e_router_bias": bias})
    seq = 128 if kernels else 48
    ids = jnp.asarray(rng.integers(0, c.vocab_size, (2, seq)), jnp.int32)
    program, params, buffers = _program_loss(model)
    np.testing.assert_array_equal(buffers["e_router_bias"], bias)
    monitor.reset_all_stats()
    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.jit(jax.value_and_grad(program))(params, ids)
    stats = monitor.all_stats()
    rows = stats["moe_expert_rows_computed_total"] \
        / stats["moe_calls_traced_total"]
    assert rows == c.experts_held * (gmm.TILE_ROWS if kernels else 2 * seq)
    kda_layers = sum(c.kinds(i)[0] == "kda"
                     for i in range(c.num_hidden_layers))
    assert stats.get("kda_carry_kernel_total", 0) \
        == (kda_layers if kernels else 0)
    under_bias = {**params, "e_router_bias": jnp.asarray(bias)}
    sizes = sizes_of(c)
    want, want_grads = ref.loss_and_grads(under_bias, (ids, ids), sizes)
    assert float(got) == pytest.approx(want, rel=2e-6)
    assert ref.loss(under_bias, (ids, ids), sizes, 8) \
        == pytest.approx(want, rel=2e-6)
    # the bias decides the loss: without it the reference reads another
    assert ref.loss(params, (ids, ids), sizes, 8) \
        != pytest.approx(want, rel=1e-5)
    for name in params:
        a = np.asarray(got_grads[name], np.float64)
        b = np.asarray(want_grads[name], np.float64)
        assert np.abs(b).max() > 0, name
        assert np.abs(a - b).max() / np.abs(b).max() <= 5e-4, name
    assert not np.asarray(want_grads["e_router_bias"]).any()


def test_layer_kinds_follow_the_published_rules():
    c = BailingHybridConfig(num_hidden_layers=42)
    mixers = [c.kinds(i)[0] for i in range(42)]
    assert mixers.count("mla") == 7 and mixers.count("kda") == 35
    assert [i for i, m in enumerate(mixers) if m == "mla"] \
        == [5, 11, 17, 23, 29, 35, 41]
    assert [c.kinds(i)[1] for i in range(3)] == ["dense", "dense", "moe"]
    cut = BailingHybridConfig(num_hidden_layers=7, first_k_dense_replace=1)
    assert [cut.kinds(i) for i in (0, 5, 6)] == [
        ("kda", "dense"), ("mla", "moe"), ("kda", "moe")]
    assert all(ref.kinds(i, {"layer_group_size": 6,
                             "first_k_dense_replace": 1}) == cut.kinds(i)
               for i in range(7))


# -- the expert shares --------------------------------------------------------

def test_expert_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """16 experts in shares of 4: the routed parts of the four shares plus
    the shared expert, counted once, are the uncut reference's layer."""
    c = bailing_hybrid_tiny(experts_held=16, seed=6)
    model = BailingHybrid(c)
    _drawn(model, 7)
    p = {n: t._data for n, t in model.named_parameters()}
    own = {n: p[n][0] for n in bh._FFN["moe"]}
    own["e_router_bias"] = model._buffers["e_router_bias"]._data[0]
    u = _normal(np.random.default_rng(7), (2, 24, c.hidden_size))
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.experts(seq, own, sizes_of(c)) for seq in u])

        def layer(w13, w2, offset, shared_w13):
            return moe.swiglu_moe(
                u, own["e_router_w"], own["e_router_bias"], w13, w2,
                shared_w13, own["e_shared_w2"], top_k=c.num_experts_per_tok,
                scale=c.routed_scaling_factor, expert_offset=offset,
                n_group=c.n_group, topk_group=c.topk_group)

        no_shared = jnp.zeros_like(own["e_shared_w13"])
        routed = [layer(own["e_w13"][lo:lo + 4], own["e_w2"][lo:lo + 4], lo,
                        no_shared) for lo in range(0, 16, 4)]
        shared_once = layer(jnp.zeros_like(own["e_w13"][:4]),
                            own["e_w2"][:4], 0, own["e_shared_w13"])
    assert all(np.abs(np.asarray(part)).max() > 0 for part in routed)
    assert_close(sum(routed) + shared_once, want)
    assert np.abs(np.asarray(routed[0] + shared_once - want)).max() \
        > 0.1 * np.abs(np.asarray(want)).max()


# -- SwiGLU over the sorted rows ----------------------------------------------

HELD, HIDDEN, INNER, TOP_K, N_ROUTED = 4, 512, 128, 4, 16


@pytest.mark.parametrize("which, resident", [
    ("as_drawn", None), ("all_to_one", None), ("as_drawn", 600_000)],
    ids=["as_drawn", "all_to_one", "by_blocks_of_columns"])
def test_swiglu_sorted_rows_equal_the_dense_mask(monkeypatch, which,
                                                 resident):
    """Values and the gradients of ``x``, ``w13``, ``w2`` and the gates;
    ``by_blocks_of_columns``: the token copy held four blocks of 128
    columns at a time, as at 8192 tokens of 2560 on the chip."""
    monkeypatch.setattr(gmm, "_INTERPRET", True)
    if resident:
        monkeypatch.setattr(gmm, "_RESIDENT_BYTES", resident)
        assert gmm._resident_cols(512, HIDDEN) == 128
    rng = np.random.default_rng(len(which) + (resident or 0))
    tokens = 512
    w13 = _normal(rng, (HELD, HIDDEN, 2 * INNER), 0.1)
    w2 = _normal(rng, (HELD, INNER, HIDDEN), 0.1)
    x = _normal(rng, (tokens, HIDDEN))
    weight = _normal(rng, (tokens, HIDDEN))
    if which == "all_to_one":
        absent = np.arange(HELD, N_ROUTED)
        sel = np.stack([np.r_[2, rng.permutation(absent)[:TOP_K - 1]]
                        for _ in range(tokens)])
    else:
        sel = np.stack([rng.permutation(N_ROUTED)[:TOP_K]
                        for _ in range(tokens)])
    sel = jnp.asarray(sel, jnp.int32)
    g = jnp.asarray(rng.random((tokens, TOP_K)) + 0.5, jnp.float32)
    hit = moe.held_gates(sel, jnp.ones_like(g), HELD, 0) > 0

    def loss(fn):
        def f(x, w13, w2, g):
            y = fn(x, w13, w2, moe.held_gates(sel, g, HELD, 0))
            return jnp.sum(y * weight)
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3)))(
                x, w13, w2, g)

    got, got_grads = loss(lambda x, w13, w2, gates: moe._sorted_swiglu(
        x, w13, w2, gates, hit, TOP_K))
    want, want_grads = loss(moe._dense_swiglu)
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    for name, a, b in zip(("x", "w13", "w2", "gates"), got_grads,
                          want_grads):
        assert_close(a, b, tol=2e-5, what=name)


def test_the_gate_takes_the_cells_tokens_by_blocks_of_columns():
    assert gmm._resident_cols(4096, 1024) == 1024      # the hybrid's, whole
    assert gmm._resident_cols(8192, 2560) == 256       # ten blocks
    assert gmm._resident_cols(65536, 256) is None


# -- the rotary embedding and the latent-attention core -----------------------

def test_rotary_embedding_against_its_formula():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((1, 5, 2, 8)).astype(np.float32)
    got = np.asarray(rotary.rotary_interleaved(jnp.asarray(x), 1e4))
    want = np.empty_like(x)
    for t in range(5):
        for i in range(4):
            angle = t * 1e4 ** (-2 * i / 8)
            a, b = x[0, t, :, 2 * i], x[0, t, :, 2 * i + 1]
            want[0, t, :, 2 * i] = a * np.cos(angle) - b * np.sin(angle)
            want[0, t, :, 2 * i + 1] = b * np.cos(angle) + a * np.sin(angle)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # and the reference's complex products give the same
    np.testing.assert_allclose(np.asarray(ref._rotate(jnp.asarray(x[0]),
                                                      1e4)), want[0],
                               rtol=1e-5, atol=1e-5)


def test_mla_core_on_the_kernels_equals_xla(monkeypatch, one_device):
    """q.k 192 and v 128, v zero-padded to 192 inside the call: output
    and gradients of the flash kernels (interpret mode) against XLA's
    attention at the unpadded widths."""
    c = bailing_hybrid_tiny()
    rng = np.random.default_rng(4)
    q, k = (_normal(rng, (1, 128, 2, 192)) for _ in range(2))
    v = _normal(rng, (1, 128, 2, 128))
    weight = _normal(rng, (1, 128, 2, 128))

    def run(interpret):
        monkeypatch.setattr(fa, "_INTERPRET", interpret)
        monitor.reset_all_stats()
        with jax.default_matmul_precision("highest"):
            out = jax.jit(jax.value_and_grad(
                lambda q, k, v: jnp.sum(bh._mla_core(c, q, k, v) * weight),
                argnums=(0, 1, 2)))(q, k, v)
        return out, monitor.all_stats()

    (want, want_grads), _ = run(False)
    (got, got_grads), stats = run(True)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name, a, b in zip("qkv", got_grads, want_grads):
        assert a.shape == b.shape
        assert_close(a, b, tol=1e-5, what=name)


# -- the group-limited router -------------------------------------------------

def test_the_group_choice_stays_within_its_groups_with_ties():
    """Every token's 8 experts come from at most 4 of 8 groups, also where
    groups tie (whole groups of equal scores) and where a group's best
    expert outscores every expert of the groups kept."""
    rng = np.random.default_rng(5)
    tokens, experts = 64, 512
    s = rng.random((tokens, experts)).astype(np.float32)
    s[:8] = 0.5                                        # every group ties
    s[8:16, :64] = 2.0                                 # group 0 wins
    s[16:24, 3] = 5.0          # one expert alone lifts its group's sum
    biased = jnp.asarray(s)
    kept = moe._keep_groups(biased, 8, 4)
    _, sel = jax.lax.top_k(kept, 8)
    groups = np.asarray(sel) // 64
    assert all(len(set(row)) <= 4 for row in groups)
    assert (groups[8:16] == 0).all()
    assert (np.asarray(sel)[16:24] == 3).any(-1).all()
    marks = np.asarray(ref.expert_choice(biased, 8, {"n_group": 8,
                                                     "topk_group": 4}))
    mine = np.zeros_like(marks)
    np.put_along_axis(mine, np.asarray(sel), 1.0, -1)
    np.testing.assert_array_equal(mine, marks)


# -- the step -----------------------------------------------------------------

INNER_SCOPES = {"kda": ("ln", "qkv", "conv", "gate", "scan", "out_norm",
                        "out"),
                "attn": ("ln", "qkv", "core", "out"),
                "mlp": ("ln", "up", "down", "router", "dispatch", "experts",
                        "combine", "shared")}


def _pass_of(path):
    return ("recompute" if "rematted_computation" in path else
            "bwd" if "transpose(" in path else "fwd")


def test_train_step_amp_o2_trains_and_names_its_scopes():
    """Three steps under AMP O2 and remat: the loss falls; every inner
    scope of the three regions in the forward and the backward (in the
    recomputed forward all but the projections whose results only join
    the residual sum); the counters; the names in ``paddle.profiler``."""
    paddle.seed(0)
    c = bailing_hybrid_tiny(seed=2)
    model = BailingHybrid(c)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = TrainStep(model, bailing_hybrid_loss, opt, amp_level="O2")
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, 256, (2, 64)).astype(np.int32))
    monitor.reset_all_stats()
    losses = [float(step(ids, ids)) for _ in range(3)]
    assert all(np.isfinite(losses)) and losses[2] < losses[1] < losses[0]
    stats = monitor.all_stats()
    kinds = [c.kinds(i) for i in range(c.num_hidden_layers)]
    experts = sum(f == "moe" for _, f in kinds)
    kda_layers = sum(m == "kda" for m, _ in kinds)
    calls = stats["moe_calls_traced_total"]
    assert calls >= experts and calls % experts == 0
    assert stats["moe_router_kept_blocks_total"] == calls
    # each KDA layer: 2 sequences x 2 heads x 2 chunks of 32, a trace
    per_trace = 2 * 2 * (64 // c.kda_chunk_size) * kda_layers
    assert stats["kda_chunks_traced_total"] >= per_trace
    assert stats["kda_chunks_traced_total"] % per_trace == 0
    seen = {}
    for path in re.findall(r'op_name="([^"]*)"', step.compiled_text()):
        tokens = [t for t in re.split(r"[/()]", path) if t]
        region = next((t for t in tokens if t in INNER_SCOPES), None)
        if region:
            after = tokens[tokens.index(region) + 1:]
            seen.setdefault((_pass_of(path), region), set()).add(
                next((t for t in after if t in INNER_SCOPES[region]), ""))
    for region, inner in INNER_SCOPES.items():
        for which in ("fwd", "bwd", "recompute"):
            last = {"out", "down"} if which == "recompute" else set()
            assert set(inner) - last <= seen[(which, region)], \
                (which, region, seen[(which, region)])
    doc = paddle.profiler.__doc__
    assert all(f"``{name}``" in doc for names in INNER_SCOPES.values()
               for name in names)
    assert "``kda``" in doc and "``kda_chunks_traced_total``" in doc
    assert "``kda_carry_kernel_total``" in doc
    # the narrow heads of the tiny config stay on the scan
    assert stats.get("kda_carry_kernel_total", 0) == 0

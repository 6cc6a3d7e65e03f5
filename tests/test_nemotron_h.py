"""NemotronH (``models/nemotron_h.py``, ``nn/functional/ssm.py`` and
``moe.py``) against its plain reference
(``benchmarks/reference/nemotron3_super_120b.py``): float32, seeded
weights, tiny sizes, on the CPU.  Each block and the whole model, loss
and the gradient of every parameter; the chunked scan against the
sequential recurrence; skewed routing; the three share tests of the
``model-configs`` guide's section 4 (the shares add up to the uncut
layer); ``TrainStep`` under AMP O2; scopes and counters; what each
``E`` block's ``jax.checkpoint`` keeps of its router.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.reference import nemotron3_super_120b as ref
from paddle_tpu.framework import monitor
from paddle_tpu.jit import TrainStep, functional_loss_call
from paddle_tpu.models import (NemotronH, NemotronHConfig, nemotron_h_loss,
                               nemotron_h_tiny, routing_load)
from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.nn.functional import moe, ssm


def sizes_of(c: NemotronHConfig) -> dict:
    """The reference's ``sizes`` of a program config: the published keys,
    ``n_routed_experts`` the experts held, ``router_width`` all of them
    (as in ``benchmarks/configs/nemotron3_super_120b.json``)."""
    keys = ("hybrid_override_pattern", "hidden_size", "vocab_size",
            "mamba_num_heads", "mamba_head_dim", "n_groups",
            "ssm_state_size", "conv_kernel", "chunk_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "num_experts_per_tok", "moe_latent_size",
            "moe_intermediate_size", "moe_shared_expert_intermediate_size",
            "routed_scaling_factor", "norm_eps", "expert_offset")
    return {**{k: getattr(c, k) for k in keys},
            "n_routed_experts": c.experts_held,
            "router_width": c.n_routed_experts}


def arrays_of(model) -> dict:
    p = {n: t._data for n, t in model.named_parameters()}
    p["e_router_bias"] = model._buffers["e_router_bias"]._data
    return p


def row(p: dict, kind: str, i: int = 0) -> dict:
    names = nh._OF_KIND[kind] + (("e_router_bias",) if kind == "E" else ())
    return {n: p[n][i] for n in names}


def assert_close(got, want, tol=2e-4, what=""):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, (what, got.shape, want.shape)
    scale = max(np.abs(want).max(), 1e-12)
    worst = np.abs(got - want).max() / scale
    assert worst <= tol, (what, worst)


def batch_of(c, seed=0, shape=(2, 40)):
    return np.random.default_rng(seed).integers(
        0, c.vocab_size, shape).astype(np.int32)


def _louder_experts(p):
    """At initialisation the routed experts' part is a hundredth of the
    shared expert's (two rescaled matrices in a row): raise it to the
    same size, so that a fault in it shows."""
    for name in ("e_w2", "e_up_w"):
        p[name]._data = p[name]._data * 10.0


@pytest.fixture(scope="module")
def tiny():
    c = nemotron_h_tiny(remat=False, seed=3)
    model = NemotronH(c)
    # the zero / one initial values hide faults: draw them
    rng = np.random.default_rng(8)
    for name in ("m_conv_b", "m_d", "m_norm", "m_gnorm_w", "a_norm",
                 "e_norm", "norm_f"):
        t = model._parameters[name]
        t._data = t._data + jnp.asarray(
            0.3 * rng.standard_normal(t.shape), jnp.float32)
    _louder_experts(model._parameters)
    return c, model


# -- each block against the reference -----------------------------------------

@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_block_and_its_gradients_match_the_reference(tiny, kind):
    c, model = tiny
    sizes, own = sizes_of(c), row(arrays_of(model), kind, 1)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, 40, c.hidden_size)), jnp.float32)
    weight = jnp.asarray(np.random.default_rng(2).standard_normal(x.shape),
                         jnp.float32)

    def program(x, own):
        out = nh._BLOCK[kind](c, x, own)
        return jnp.sum(out * weight), out

    def reference(x, own):
        out = jnp.stack([ref._layer(kind, seq, own, sizes) for seq in x])
        return jnp.sum(out * weight), out

    (_, got), got_grads = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True))(x, own)
    (_, want), want_grads = jax.jit(jax.value_and_grad(
        reference, argnums=(0, 1), has_aux=True))(x, own)
    assert_close(got, want, what=kind)
    assert_close(got_grads[0], want_grads[0], what=f"{kind} dx")
    for name in nh._OF_KIND[kind]:
        assert_close(got_grads[1][name], want_grads[1][name], what=name)


def _loss_of_params(model, ids):
    """(``params -> loss`` on the batch ``ids``, the model's ``params``)."""
    params = {n: t._data for n, t in model.named_parameters()}
    buffers = {n: t._data for n, t in model.named_buffers()}

    def program(params):
        return functional_loss_call(
            model, nemotron_h_loss, params, buffers, jax.random.PRNGKey(0),
            [jnp.asarray(ids), jnp.asarray(ids)])[0]

    return program, params


def test_model_loss_and_every_gradient_match_the_reference(tiny):
    c, model = tiny
    ids = batch_of(c)
    program, params = _loss_of_params(model, ids)
    got, got_grads = jax.jit(jax.value_and_grad(program))(params)
    want, want_grads = ref.loss_and_grads(params, (ids, ids), sizes_of(c))
    assert float(got) == pytest.approx(want, rel=2e-6)
    assert ref.loss(params, (ids, ids), sizes_of(c), 1) == \
        pytest.approx(want, rel=2e-6)
    assert set(got_grads) == set(want_grads) == set(params)
    for name in params:
        assert np.abs(np.asarray(want_grads[name])).max() > 0, name
        assert_close(got_grads[name], want_grads[name], tol=5e-4, what=name)


# -- the chunked scan against the sequential recurrence -----------------------

def _sequential_ssd(x, dt, a, b_in, c_in):
    """h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t; y_t = C_t . h_t, one
    position at a time, in float64 numpy."""
    x, dt, a, b_in, c_in = (np.asarray(t, np.float64)
                            for t in (x, dt, a, b_in, c_in))
    bsz, seq, heads, dim = x.shape
    per = heads // b_in.shape[2]
    h = np.zeros((bsz, heads, dim, b_in.shape[3]))
    y = np.zeros_like(x)
    for t in range(seq):
        b_t, c_t = (np.repeat(m[:, t], per, axis=1) for m in (b_in, c_in))
        h = np.exp(dt[:, t] * a)[..., None, None] * h + np.einsum(
            "bh,bhp,bhn->bhpn", dt[:, t], x[:, t], b_t)
        y[:, t] = np.einsum("bhpn,bhn->bhp", h, c_t)
    return y


@pytest.mark.parametrize("seq", [16, 32, 48, 37],
                         ids=["one_chunk", "two_chunks", "three_chunks",
                              "no_multiple_padded"])
def test_chunked_scan_equals_the_sequential_recurrence(seq):
    rng = np.random.default_rng(seq)
    heads, dim, groups, state, chunk = 4, 8, 2, 16, 16
    x = rng.standard_normal((2, seq, heads, dim)).astype(np.float32)
    dt = rng.uniform(0.001, 0.5, (2, seq, heads)).astype(np.float32)
    a = -rng.uniform(1.0, 16.0, (heads,)).astype(np.float32)
    b_in, c_in = (rng.standard_normal((2, seq, groups, state)).astype(
        np.float32) for _ in range(2))
    before = monitor.get_stat("ssm_chunks_traced_total")
    got = ssm.ssd_chunked(*map(jnp.asarray, (x, dt, a, b_in, c_in)), chunk)
    assert got.shape == x.shape                     # the padding is cut off
    assert_close(got, _sequential_ssd(x, dt, a, b_in, c_in), tol=1e-5)
    assert monitor.get_stat("ssm_chunks_traced_total") - before == \
        2 * -(-seq // chunk)


def test_chunked_scan_is_differentiable_like_the_recurrence():
    """Gradients of every input through the chunks, against jax's own
    through a ``lax.scan`` over time."""
    rng = np.random.default_rng(5)
    seq, heads, dim, groups, state = 40, 4, 8, 2, 16
    args = [rng.standard_normal((1, seq, heads, dim)),
            rng.uniform(0.001, 0.5, (1, seq, heads)),
            -rng.uniform(1.0, 16.0, (heads,)),
            rng.standard_normal((1, seq, groups, state)),
            rng.standard_normal((1, seq, groups, state))]
    args = [jnp.asarray(t, jnp.float32) for t in args]
    weight = jnp.asarray(rng.standard_normal((1, seq, heads, dim)),
                         jnp.float32)

    def sequential(x, dt, a, b_in, c_in):
        b_in, c_in = (jnp.repeat(m[0], heads // groups, 1)
                      for m in (b_in, c_in))

        def step(h, now):
            x_t, b_t, c_t, dt_t = now
            h = jnp.exp(dt_t * a)[:, None, None] * h \
                + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            return h, jnp.einsum("hpn,hn->hp", h, c_t)

        return jax.lax.scan(step, jnp.zeros((heads, dim, state)),
                            (x[0], b_in, c_in, dt[0]))[1][None]

    want = jax.jit(jax.grad(lambda *t: jnp.sum(sequential(*t) * weight),
                            argnums=range(5)))(*args)
    got = jax.jit(jax.grad(
        lambda *t: jnp.sum(ssm.ssd_chunked(*t, 16) * weight),
        argnums=range(5)))(*args)
    for name, g, w in zip(("x", "dt", "a", "b", "c"), got, want):
        assert_close(g, w, what=name)


# -- norms --------------------------------------------------------------------

def test_rms_norm_and_the_gated_group_norm_against_numpy():
    rng = np.random.default_rng(0)
    x, z = rng.standard_normal((2, 3, 5, 24))
    w = rng.standard_normal(24)
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * w
    assert_close(ssm.rms_norm_array(jnp.asarray(x, jnp.float32),
                                    jnp.asarray(w, jnp.float32), 1e-5),
                 want, tol=1e-5)
    assert_close(paddle.nn.functional.rms_norm(
        paddle.to_tensor(x.astype(np.float32)),
        paddle.to_tensor(w.astype(np.float32))).numpy(), want, tol=1e-5)
    gated = (x * z / (1 + np.exp(-z))).reshape(3, 5, 4, 6)
    gated = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
    assert_close(ssm.gated_group_rms_norm(
        *(jnp.asarray(t, jnp.float32) for t in (x, z, w)), 4, 1e-5),
        gated.reshape(3, 5, 24) * w, tol=1e-5)


# -- routing ------------------------------------------------------------------

def test_skewed_routing_equals_the_reference_and_drops_nothing(tiny):
    """One held expert takes nearly every token, one takes none: the
    layer still equals the reference's plain loop."""
    c, model = tiny
    own = row(arrays_of(model), "E", 0)
    bias = np.zeros(c.n_routed_experts, np.float32)
    bias[1], bias[2] = 10.0, -10.0        # 1 always chosen, 2 never
    own["e_router_bias"] = jnp.asarray(bias)
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (1, 64, c.hidden_size)), jnp.float32)
    u = ssm.rms_norm_array(x, own["e_norm"], c.norm_eps)
    sel, g = moe.route_top_k(u, own["e_router_w"], own["e_router_bias"],
                             c.num_experts_per_tok,
                             c.routed_scaling_factor)
    load = np.asarray(moe.held_gates(sel, jnp.ones_like(g), c.experts_held,
                                     c.expert_offset).sum((0, 1)))
    assert load[1] == 64 and load[2] == 0, load
    # g is normalised over all top_k, held here or not
    assert_close(g.sum(-1), np.full((1, 64), c.routed_scaling_factor),
                 tol=1e-6)
    got = nh._e_block(c, x, own)
    assert_close(got[0], ref._layer("E", x[0], own, sizes_of(c)))
    # the correction bias chooses and does nothing else: no gradient
    grad = jax.grad(lambda b: jnp.sum(nh._e_block(
        c, x, {**own, "e_router_bias": b})))(own["e_router_bias"])
    assert not np.asarray(grad).any()


def test_routing_load_counts_every_choice_once():
    c = nemotron_h_tiny(experts_held=8, seed=1)          # all 8 held
    model = NemotronH(c)
    ids = batch_of(c, shape=(2, 32))
    load = routing_load(model, paddle.to_tensor(ids))
    assert load.shape == (c.hybrid_override_pattern.count("E"), 8)
    assert (load.sum(1) == 2 * 32 * c.num_experts_per_tok).all(), load
    half = NemotronH(nemotron_h_tiny(experts_held=4, expert_offset=4,
                                     seed=1))
    assert half._parameters["e_w1"].shape[1] == 4
    # the same router: the second half of the experts' load
    for name in ("embed", "e_router_w"):
        assert np.array_equal(half._parameters[name].numpy(),
                              model._parameters[name].numpy())
    assert np.array_equal(routing_load(half, ids)[0], load[0, 4:])


# -- the share tests ----------------------------------------------------------

def test_expert_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """8 experts in shares of 2: the routed parts of the four shares plus
    the shared expert, counted once, are the uncut reference's layer."""
    c = nemotron_h_tiny(experts_held=8, seed=6)
    model = NemotronH(c)
    _louder_experts(model._parameters)
    own = row(arrays_of(model), "E", 0)
    u = jnp.asarray(np.random.default_rng(7).standard_normal(
        (2, 24, c.hidden_size)), jnp.float32)
    want = jnp.stack([ref.experts(seq, own, sizes_of(c)) for seq in u])

    def layer(w1, w2, offset, shared_w1):
        return moe.latent_moe(
            u, own["e_router_w"], own["e_router_bias"], own["e_down_w"],
            w1, w2, own["e_up_w"], shared_w1, own["e_shared_w2"],
            top_k=c.num_experts_per_tok, scale=c.routed_scaling_factor,
            expert_offset=offset)

    no_shared = jnp.zeros_like(own["e_shared_w1"])
    routed = [layer(own["e_w1"][lo:lo + 2], own["e_w2"][lo:lo + 2], lo,
                    no_shared) for lo in range(0, 8, 2)]
    shared_once = layer(jnp.zeros_like(own["e_w1"][:2]), own["e_w2"][:2], 0,
                        own["e_shared_w1"])
    assert all(np.abs(np.asarray(part)).max() > 0 for part in routed)
    assert_close(sum(routed) + shared_once, want)
    # and a share alone is not the layer
    assert np.abs(np.asarray(routed[0] + shared_once - want)).max() \
        > 0.1 * np.abs(np.asarray(want)).max()


def _columns(lo, hi, *bases):
    return np.concatenate([np.arange(b + lo, b + hi) for b in bases])


@pytest.mark.parametrize("kind", ["M", "*"])
def test_eight_head_shares_add_up_to_the_uncut_block(kind):
    """``f(u)`` of the uncut block (16 Mamba-2 heads in 8 groups; 8 query
    heads on 2 KV heads) against the sum over 8 shares, each holding one
    group with its 2 heads, or one query head with the KV head it reads."""
    whole = nemotron_h_tiny(mamba_num_heads=16, n_groups=8,
                            num_attention_heads=8, num_key_value_heads=2,
                            seed=9)
    share = nemotron_h_tiny(mamba_num_heads=2, n_groups=1,
                            num_attention_heads=1, num_key_value_heads=1)
    own = row(arrays_of(NemotronH(whole)), kind, 1)
    own[{"M": "m_gnorm_w", "*": "a_norm"}[kind]] = jnp.asarray(
        np.random.default_rng(1).uniform(0.5, 1.5, own[
            {"M": "m_gnorm_w", "*": "a_norm"}[kind]].shape), jnp.float32)
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (2, 40, whole.hidden_size)), jnp.float32)
    u = ssm.rms_norm_array(x, own[{"M": "m_norm", "*": "a_norm"}[kind]],
                           whole.norm_eps)
    want = jnp.stack([ref.BLOCKS[kind](seq, own, sizes_of(whole))
                      for seq in u])
    total, block = 0, jax.jit(functools.partial(nh._BLOCK[kind], share))
    for i in range(8):
        if kind == "M":
            hp, n = 2 * whole.mamba_head_dim, whole.ssm_state_size
            inner = 16 * whole.mamba_head_dim
            chan = _columns(i * hp, (i + 1) * hp, 0)
            conv = np.concatenate([chan, _columns(i * n, (i + 1) * n, inner),
                                   _columns(i * n, (i + 1) * n,
                                            inner + 8 * n)])
            heads = np.arange(2 * i, 2 * i + 2)
            cols = np.concatenate([chan, inner + conv,
                                   2 * inner + 16 * n + heads])
            part = {"m_norm": own["m_norm"],
                    "m_in_w": own["m_in_w"][:, cols],
                    "m_conv_w": own["m_conv_w"][:, conv],
                    "m_conv_b": own["m_conv_b"][conv],
                    "m_dt_bias": own["m_dt_bias"][heads],
                    "m_a_log": own["m_a_log"][heads],
                    "m_d": own["m_d"][heads],
                    "m_gnorm_w": own["m_gnorm_w"][chan],
                    "m_out_w": own["m_out_w"][chan]}
        else:
            d = whole.head_dim
            q, kv = _columns(i * d, (i + 1) * d, 0), \
                _columns((i // 4) * d, (i // 4 + 1) * d, 0)
            part = {"a_norm": own["a_norm"], "a_q_w": own["a_q_w"][:, q],
                    "a_k_w": own["a_k_w"][:, kv],
                    "a_v_w": own["a_v_w"][:, kv], "a_o_w": own["a_o_w"][q]}
        total = total + block(x, part) - x
    assert_close(total, want)


def test_eight_vocabulary_slices_concatenate_to_the_uncut_logits(tiny):
    """Each share holds an eighth of the head's columns; the embedding is
    given whole (a vocabulary-parallel deployment sums the lookups)."""
    c, model = tiny
    ids = batch_of(c)
    want = model(paddle.to_tensor(ids)).numpy()
    p = arrays_of(model)
    names = [n for n in p if n != "e_router_bias"]
    forward = jax.jit(functools.partial(nh._forward, c, names, False))
    slices = [forward(*({**p, "head_w": p["head_w"][:, lo:lo + 32]}[n]
                        for n in names), p["e_router_bias"], ids)
              for lo in range(0, c.vocab_size, 32)]
    assert slices[0].shape == want.shape[:2] + (32,)
    assert_close(np.concatenate(slices, -1), want, tol=1e-6)


# -- the train step -----------------------------------------------------------

@pytest.fixture(scope="module")
def stepped():
    monitor.reset_all_stats()
    paddle.seed(0)
    c = nemotron_h_tiny(remat=True)
    model = NemotronH(c)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = TrainStep(model, nemotron_h_loss, opt, amp_level="O2")
    ids = paddle.to_tensor(batch_of(c, shape=(2, 64)))
    losses = [float(step(ids, ids)) for _ in range(3)]
    return c, step, losses, dict(monitor.all_stats())


def test_train_step_amp_o2_takes_three_steps_and_the_loss_falls(stepped):
    c, step, losses, _ = stepped
    assert all(np.isfinite(losses)) and losses[2] < losses[1] < losses[0]
    assert abs(losses[0] - np.log(c.vocab_size)) < 0.3
    for _, p in step.model.named_parameters():
        assert p._data.dtype == jnp.float32          # master weights


INNER = {"ssm": ("ln", "in_proj", "conv", "scan", "gate_norm", "out"),
         "attn": ("ln", "qkv", "core", "out"),
         "mlp": ("ln", "router", "latent_down", "dispatch", "experts",
                 "combine", "latent_up", "shared")}


def _pass_of(path):
    return ("recompute" if "rematted_computation" in path else
            "bwd" if "transpose(" in path else "fwd")


def test_scopes_of_every_block_in_every_pass(stepped):
    _, step, _, _ = stepped
    seen = {}
    for path in re.findall(r'op_name="([^"]*)"', step.compiled_text()):
        tokens = [t for t in re.split(r"[/()]", path) if t]
        which = _pass_of(path)
        region = next((t for t in tokens if t in INNER), None)
        if region:
            after = tokens[tokens.index(region) + 1:]
            seen.setdefault((which, region), set()).add(
                next((t for t in after if t in INNER[region]), ""))
    for region, inner in INNER.items():
        for which in ("fwd", "bwd", "recompute"):
            # a matmul whose result only joins the residual sum is not
            # run again: the backward reads its operands, not its result.
            # ``router`` stays in the recompute's set though its choice
            # and picked logits are kept: their sigmoid and the gates'
            # normalisation run again (what does not, the test below)
            last = {"out", "latent_up"} if which == "recompute" else set()
            assert set(inner) - last <= seen[(which, region)], \
                (which, region)
    doc = paddle.profiler.__doc__
    assert all(f"``{name}``" in doc for names in INNER.values()
               for name in names) and "``ssm``" in doc


def test_counters_of_the_traced_step(stepped):
    c, _, _, stats = stepped
    tokens, layers = 2 * 64, c.hybrid_override_pattern.count("E")
    calls = stats["moe_calls_traced_total"]
    assert calls >= layers and calls % layers == 0
    # the path this model takes: latent 32 and inner 48 are no lane
    # multiples, so the gate declines them and the dense mask counts held
    # rows a token (the sorted rows' count: tests/test_moe_sorted.py)
    assert stats["moe_expert_rows_computed_total"] / calls == \
        c.experts_held * tokens
    assert stats["moe_expert_rows_expected_total"] / calls == \
        tokens * c.num_experts_per_tok * c.experts_held / c.n_routed_experts
    assert stats["ssm_chunks_traced_total"] >= 2 * 2 * (64 // c.chunk_size)
    # every E block of the traced stack keeps its router under remat ...
    assert stats["moe_router_kept_blocks_total"] == calls
    # ... and a stack traced without remat wraps none
    before = dict(monitor.all_stats())
    NemotronH(nemotron_h_tiny(remat=False))(
        paddle.to_tensor(batch_of(c, shape=(1, 16))))
    after = monitor.all_stats()
    assert after["moe_calls_traced_total"] \
        - before["moe_calls_traced_total"] == layers
    assert after["moe_router_kept_blocks_total"] \
        == before["moe_router_kept_blocks_total"]
    text = monitor.export_prometheus()
    for name in ("moe_calls_traced_total", "moe_expert_rows_computed_total",
                 "moe_expert_rows_expected_total",
                 "moe_router_kept_blocks_total", "ssm_chunks_traced_total"):
        assert re.search(rf"# HELP \S*{name}", text), name
        assert f"``{name}``" in paddle.profiler.__doc__, name


# -- what the checkpoint keeps of the router ----------------------------------

def _router_ops(text):
    """{pass: the last token of every ``op_name`` under ``mlp/router``}
    of a compiled step's text."""
    ops = {}
    for path in re.findall(r'op_name="([^"]*)"', text):
        if "mlp/router/" in path or "mlp)/router/" in path:
            ops.setdefault(_pass_of(path), set()).add(path.rsplit("/", 1)[1])
    return ops


def test_recomputed_forward_neither_scores_nor_chooses_again(stepped):
    """The compiled step: the router's einsum over all experts and its
    top-k are in the forward, and the forward run again holds neither."""
    _, step, _, _ = stepped
    ops = _router_ops(step.compiled_text())
    assert {"dot_general", "top_k"} <= ops["fwd"]
    assert "dot_general" in ops["bwd"]           # the weight's gradient
    # what is left: the picked logits' sigmoid and the gates
    assert ops["recompute"] and not ops["recompute"] & {
        "dot_general", "top_k", "sort"}, ops["recompute"]


def _drop_the_policy(monkeypatch):
    """``_trunk``'s checkpoint as it was before the names: a bare
    ``jax.checkpoint(block)``, which keeps nothing."""
    real = jax.checkpoint
    monkeypatch.setattr(jax, "checkpoint", lambda f, **_: real(f))


def _loss_and_grads(c, router_w, bias, ids):
    """((loss, every parameter's gradient), the jaxpr that computes them,
    the model) of a new model of ``c`` under the given router."""
    model = NemotronH(c)
    _louder_experts(model._parameters)
    model._parameters["e_router_w"]._data = jnp.asarray(router_w)
    model.set_state_dict({"e_router_bias": bias})
    program, params = _loss_of_params(model, ids)
    fn = jax.value_and_grad(program)
    return jax.jit(fn)(params), str(jax.make_jaxpr(fn)(params)), model


@pytest.mark.parametrize("bias_of",
                         ["zero", "nonzero", "tie_at_the_last_place"])
@pytest.mark.parametrize("path", ["dense_mask", "sorted_rows"])
def test_kept_router_gives_the_bare_checkpoints_gradients(path, bias_of,
                                                          monkeypatch):
    """Loss and every gradient with ``sel`` and the picked logits kept:
    bit for bit those of a bare ``jax.checkpoint(block)``, which chooses
    a second time (float32, CPU), and within the parity tolerance those
    of ``remat=False``; one ``top_k`` an ``E`` layer in the gradient's
    jaxpr where the bare checkpoint has two, and one einsum fewer."""
    from paddle_tpu.ops.pallas import grouped_matmul as gmm
    monkeypatch.setattr(gmm, "_INTERPRET", path == "sorted_rows")
    sizes = dict(seed=5, hybrid_override_pattern="ME*E")
    if path == "sorted_rows":        # lane multiples, a row tile of tokens
        sizes.update(moe_latent_size=128, moe_intermediate_size=128)
    c = nemotron_h_tiny(remat=True, **sizes)
    layers = c.hybrid_override_pattern.count("E")
    rng = np.random.default_rng(23)
    ids = rng.integers(0, c.vocab_size, (2, 256 if path == "sorted_rows"
                                         else 40)).astype(np.int32)
    router_w = NemotronH(c)._parameters["e_router_w"].numpy().copy()
    bias = np.zeros((layers, c.n_routed_experts), np.float32)
    if bias_of != "zero":
        bias += (0.05 * rng.standard_normal(bias.shape)).astype(np.float32)
    if bias_of == "tie_at_the_last_place":
        # expert 1 comes first for every token; experts 2 (held) and 5
        # (absent) score alike to the bit and stand level for the last
        # of the top_k places: the lower index takes it, everywhere
        assert c.num_experts_per_tok == 2 and c.experts_held == 4
        bias[:, 1], bias[:, 2], bias[:, 5] = 5.0, 2.0, 2.0
        router_w[:, 5] = router_w[:, 2]

    monitor.reset_all_stats()
    (kept_loss, kept), kept_jaxpr, model = _loss_and_grads(
        c, router_w, bias, ids)
    stats = monitor.all_stats()
    assert stats["moe_expert_rows_computed_total"] \
        / stats["moe_calls_traced_total"] == c.experts_held * (
            gmm.TILE_ROWS if path == "sorted_rows" else ids.size)
    (plain_loss, plain), plain_jaxpr, _ = _loss_and_grads(
        nemotron_h_tiny(remat=False, **sizes), router_w, bias, ids)
    _drop_the_policy(monkeypatch)
    (bare_loss, bare), bare_jaxpr, _ = _loss_and_grads(c, router_w, bias, ids)

    def count(jaxpr, primitive):
        return len(re.findall(rf"\b{primitive}\[", jaxpr))

    assert count(plain_jaxpr, "top_k") == count(kept_jaxpr, "top_k") \
        == layers
    assert count(bare_jaxpr, "top_k") == 2 * layers
    assert count(bare_jaxpr, "dot_general") \
        - count(kept_jaxpr, "dot_general") == layers
    assert float(kept_loss) == float(bare_loss)
    assert float(kept_loss) == pytest.approx(float(plain_loss), rel=2e-6)
    assert set(kept) == set(bare) == set(plain)
    for name in kept:
        assert np.abs(np.asarray(kept[name])).max() > 0, name
        np.testing.assert_array_equal(kept[name], bare[name], err_msg=name)
        assert_close(kept[name], plain[name], what=name)
    if bias_of == "tie_at_the_last_place":
        load = routing_load(model, ids)
        assert (load[:, 1] == ids.size).all() \
            and (load[:, 2] == ids.size).all(), load


# -- the configuration --------------------------------------------------------

def test_config_says_what_it_refuses():
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        nemotron_h_tiny(hybrid_override_pattern="MXE")
    with pytest.raises(ValueError, match="held experts"):
        nemotron_h_tiny(experts_held=4, expert_offset=6)
    with pytest.raises(ValueError, match="divide"):
        nemotron_h_tiny(mamba_num_heads=6, n_groups=4)
    c = NemotronHConfig()
    assert c.num_layers == 88 and c.experts_held == 512
    assert [c.hybrid_override_pattern.count(k) for k in "ME*"] == [40, 40, 8]


@pytest.mark.parametrize("path", ["dense_mask", "sorted_rows"])
def test_nemotron_h_under_a_bias_agrees_with_the_reference(path, monkeypatch):
    """The whole model, its buffer filled the way the benchmark fills it:
    loss and every parameter's gradient against the reference's
    ``loss_and_grads`` under the same bias, on the dense mask and on the
    sorted rows (kernels in interpret mode)."""
    from paddle_tpu.ops.pallas import grouped_matmul as gmm
    monkeypatch.setattr(gmm, "_INTERPRET", path == "sorted_rows")
    c = nemotron_h_tiny(remat=False, seed=5, hybrid_override_pattern="ME*E",
                        moe_latent_size=128, moe_intermediate_size=128)
    model = NemotronH(c)
    for name in ("e_w2", "e_up_w"):      # as loud as the shared expert
        model._parameters[name]._data = model._parameters[name]._data * 10.0
    rng = np.random.default_rng(23)
    # a bias that decides: expert 1 (held) for every token, expert 6
    # (absent) for none, the rest nudged; another row for each layer
    bias = (0.05 * rng.standard_normal((2, c.n_routed_experts))
            ).astype(np.float32)
    bias[:, 1], bias[:, 6] = 5.0, -5.0
    model.set_state_dict({"e_router_bias": bias})
    ids = rng.integers(0, c.vocab_size, (2, 256)).astype(np.int32)
    program, params = _loss_of_params(model, ids)
    monitor.reset_all_stats()
    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.jit(jax.value_and_grad(program))(params)
    stats = monitor.all_stats()
    rows = stats["moe_expert_rows_computed_total"] \
        / stats["moe_calls_traced_total"]
    # 512 tokens, 2 of 8 experts each: the dense mask computes every held
    # expert on each token, the sorted rows one tile an expert
    assert rows == c.experts_held * (512 if path == "dense_mask"
                                     else gmm.TILE_ROWS)
    under_bias = {**params, "e_router_bias": jnp.asarray(bias)}
    want, want_grads = ref.loss_and_grads(under_bias, (ids, ids), sizes_of(c))
    assert float(got) == pytest.approx(want, rel=2e-6)
    assert ref.loss(params, (ids, ids), sizes_of(c), 1) \
        != pytest.approx(want, rel=1e-5)
    for name in params:
        assert np.abs(np.asarray(want_grads[name])).max() > 0, name
        assert_close(got_grads[name], want_grads[name], tol=5e-4, what=name)
    assert not np.asarray(want_grads["e_router_bias"]).any()

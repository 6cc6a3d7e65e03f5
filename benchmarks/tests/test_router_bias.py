"""What PR 33 added to the yardstick: the balancing bias as seeded data
(``benchmarks/inputs/balanced_router_bias.py``).  At the rehearsal sizes,
on the CPU: the solve ends inside its cap with every expert inside the
tolerance, on eight seeds; it is deterministic; a configuration without
the key runs as before and the cell's ``correct`` holds with the solved
bias; the reference's walk with a hook equals the walk with the same bias
among ``params``; and the program under a non-zero bias agrees with the
reference, gates from ``s`` and not from ``s + b``, on the dense mask and
on the sorted rows (kernels in interpret mode), the expert layer alone
and the whole ``NemotronH``; a solve that ends at its cap makes the
run's ``correct`` false; and the cell's feed: a fresh batch every step
from one draw of the seed, the first of them the batch the reference
reads, and a step that leaves its state unchanged is not ``correct``.
Since PR 38: the solve counts loads through the reference's own choice
where it gives one, and without one is bit for bit what it was; a
configuration whose router limits groups needs a reference that chooses
by them."""
import json
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import measure
from benchmarks.inputs import balanced_router_bias as brb
from benchmarks.reference import nemotron3_super_120b as ref
from benchmarks.tests import rules

ROOT = measure.ROOT
BENCH = rules.load(ROOT)
CELL = "nemotron3_super_120b.train_b1_s4096"
SEEDS = (3, 1200000007, 1300000021, 1400000033, 1500000041, 1600000057,
         2147483777, 4000000007)


def _built(seed):
    """The rehearsal's model parameters, batch and sizes under ``seed``."""
    cell = measure.load_cell(CELL, rehearse=True)
    config, traffic, sizes = cell["config"], cell["traffic"], cell["sizes"]
    model = measure.build_model(config, sizes, seed)
    arrays = measure.resolve(config["inputs"])(
        seed, traffic["batch"], traffic["seq"], sizes)
    params = {n: p.data for n, p in model.named_parameters()}
    return model, params, arrays, sizes


@pytest.mark.parametrize("seed", SEEDS)
def test_the_solve_balances_every_expert_inside_its_cap(seed):
    _, params, arrays, sizes = _built(seed)
    loss, solved, report, compared = brb.solve(ref, params, arrays, sizes, 1)
    bias = solved["e_router_bias"]
    experts = sizes["router_width"]
    assert bias.shape == (sizes["hybrid_override_pattern"].count("E"),
                          experts) and bias.dtype == np.float32
    mean = arrays[0].size * sizes["num_experts_per_tok"] / experts
    assert (report["mean_load"], report["tolerance"]) \
        == (mean, max(brb.TOLERANCE * mean, 1.0))
    worst, limit = compared["router_load_off_mean"]
    assert 0 <= worst <= limit == report["tolerance"]
    assert worst == max(max(mean - layer["loads"][0],
                            layer["loads"][1] - mean)
                        for layer in report["layers"])
    for layer in report["layers"]:
        assert 0 < layer["iterations"] < brb.CAP
        low, high = layer["loads"]
        assert mean - report["tolerance"] <= low <= high \
            <= mean + report["tolerance"]
        # the draw it mends was outside
        assert layer["loads_unbiased"][1] - layer["loads_unbiased"][0] \
            > high - low
        assert len(layer["held_rows"]) == sizes["n_routed_experts"]
    # the loss it hands on is the reference's under that bias, and the
    # bias moves the choice (so the loss) a little
    assert loss == ref.loss({**params, "e_router_bias": bias}, arrays,
                            sizes, 1)
    assert loss != ref.loss(params, arrays, sizes, 1)
    assert loss == pytest.approx(ref.loss(params, arrays, sizes, 1),
                                 rel=1e-3)


def test_the_solve_is_deterministic():
    first, second = (brb.solve(ref, *_built(SEEDS[1])[1:], 1)
                     for _ in range(2))
    assert first[0] == second[0]
    assert first[1]["e_router_bias"].tobytes() \
        == second[1]["e_router_bias"].tobytes()
    assert first[2:] == second[2:]
    other = brb.solve(ref, *_built(SEEDS[2])[1:], 1)
    assert other[1]["e_router_bias"].tobytes() \
        != first[1]["e_router_bias"].tobytes()


def test_balance_on_scores_with_a_heavy_common_part():
    """Scores as a fresh router gives them where the tokens share a
    direction: loads from 0 to several times the mean."""
    rng = np.random.default_rng(5)
    u = rng.standard_normal((512, 32)) + 0.5 * rng.standard_normal(32)
    w = rng.standard_normal((64, 32)) / np.sqrt(32)
    scores = jnp.asarray(1 / (1 + np.exp(-u @ w.T)), jnp.float32)
    before = np.asarray(brb.loads(scores, 0.0, 4))
    assert before.sum() == 512 * 4 and before.max() > 3 * 32
    bias, after, iterations = brb.balance(scores, 4)
    after = np.asarray(after)
    assert int(iterations) < brb.CAP and after.sum() == 512 * 4
    assert np.abs(after - 32).max() <= max(brb.TOLERANCE * 32, 1.0)
    np.testing.assert_array_equal(after, brb.loads(scores, bias, 4))
    # the count is the top-k's own
    _, sel = jax.lax.top_k(scores + bias, 4)
    np.testing.assert_array_equal(
        after, np.bincount(np.asarray(sel).ravel(), minlength=64))


def test_a_configuration_names_a_solve_exactly_when_it_routes():
    routed = rules.solves_follow_the_router(BENCH, ROOT)
    assert "nemotron3_super_120b" in routed
    assert not routed & {"gpt2_345m", "bert_base"}
    named = rules.configurations(BENCH, ROOT)
    config = named["nemotron3_super_120b"]
    assert measure.resolve(config["router_bias"]) is brb.solve
    for word in ("DeepSeek-V3", "checkpoint", "stays as solved", "176 +- 16"):
        assert word in config["assumed"]["router_bias"], word
    # the rate is the one the other configurations train at
    assert {named[name]["optimizer"]["kwargs"]["learning_rate"]
            for name in ("gpt2_345m", "bert_base", "nemotron3_super_120b")
            } == {1e-4}


@pytest.mark.parametrize("with_key", [True, False])
def test_the_cell_runs_correct_with_and_without_the_key(with_key,
                                                        monkeypatch):
    """With the key the model trains under the solved bias and ``correct``
    holds; without it the run is the one of before PR 33: no solve, the
    buffer zero, the reference asked for its loss the plain way."""
    load_cell, build, plain = measure.load_cell, measure._build, ref.loss
    seen = {"hooks": []}

    def cell_of(workload, rehearse):
        cell = load_cell(workload, rehearse)
        if not with_key:
            del cell["config"]["router_bias"]
        return cell

    def keep(cell, seed, devices):
        seen["model"], *rest = build(cell, seed, devices)
        return (seen["model"], *rest)

    def loss(*args, **kwargs):
        seen["hooks"].append(kwargs.get("router_bias"))
        return plain(*args, **kwargs)

    monkeypatch.setattr(measure, "load_cell", cell_of)
    monkeypatch.setattr(measure, "_build", keep)
    monkeypatch.setattr(ref, "loss", loss)
    said = []
    result = measure.measure(CELL, SEEDS[3], 0.6, False, True,
                             time.perf_counter(), said.append)
    assert result["correct"] is True and result["failed"] == 0
    compared = result["compared"]["first_loss_rel_diff"]
    assert compared["value"] <= compared["limit"] == ref.TOLERANCE_REL
    assert ("router_load_off_mean" in result["compared"]) == with_key
    bias = np.asarray(seen["model"]._buffers["e_router_bias"]._data)
    routing = [ln for ln in said if ln.startswith("routing: ")]
    assert len(seen["hooks"]) == 1
    if with_key:
        assert callable(seen["hooks"][0])
        off = result["compared"]["router_load_off_mean"]
        assert off["holds"] and off["value"] <= off["limit"] \
            == json.loads(routing[0][len("routing: "):])["tolerance"]
        assert np.abs(bias).max() > 0 and bias.dtype == np.float32
    else:
        assert seen["hooks"] == [None] and routing == []
        assert not bias.any()


def test_a_solve_that_ends_at_its_cap_is_not_a_correct_run(monkeypatch):
    """The cell's ``why`` says its loads are balanced as built: a run whose
    solve gave up is not that cell, and says so through ``correct``."""
    def gives_up(scores, top_k, choose=None):
        zero = jnp.zeros((scores.shape[1],), jnp.float32)
        return (zero, brb.loads(scores, zero, top_k, choose),
                jnp.float32(brb.CAP))

    monkeypatch.setattr(brb, "balance", gives_up)
    result = measure.measure(CELL, SEEDS[4], 0.6, False, True,
                             time.perf_counter(), lambda line: None)
    off = result["compared"]["router_load_off_mean"]
    assert off["value"] > off["limit"] and off["holds"] is False
    assert all(pair["holds"] for name, pair in result["compared"].items()
               if name != "router_load_off_mean")
    assert result["correct"] is False


# -- the router's choice is the reference's ----------------------------------

def _tilted(biased, top_k, sizes):
    """A choice other than the plain top-k: the even experts nudged up
    by ``sizes["tilt"]`` before the top-k is taken."""
    tilt = sizes["tilt"] * (jnp.arange(biased.shape[1]) % 2 == 0)
    kth = jax.lax.top_k(biased + tilt, top_k)[0][:, -1:]
    return (biased + tilt >= kth).astype(jnp.float32)


def _within_best_group(biased, top_k, sizes):
    """A group-limited choice: the ``top_k`` of the one group that holds
    the token's best expert."""
    tokens, experts = biased.shape
    n_group = sizes["n_group"]
    grouped = biased.reshape(tokens, n_group, experts // n_group)
    best = jnp.argmax(grouped.max(-1), -1)
    kept = jnp.where((jnp.arange(n_group) == best[:, None])[..., None],
                     grouped, -jnp.inf).reshape(tokens, experts)
    kth = jax.lax.top_k(kept, top_k)[0][:, -1:]
    return (kept >= kth).astype(jnp.float32)


def test_the_solve_counts_loads_by_the_references_own_choice():
    """A reference that gives ``expert_choice`` has its loads counted
    through it: every layer inside the tolerance by that choice, and a
    bias other than the one the plain top-k is balanced by."""
    _, params, arrays, sizes = _built(SEEDS[0])
    tilted = {**sizes, "tilt": 0.02}
    own = types.SimpleNamespace(loss=ref.loss, expert_choice=_tilted)
    scores = []

    def keep(scores_of_layer):
        scores.append(scores_of_layer)
        return brb.balance(scores_of_layer, 2,
                           lambda b, k: _tilted(b, k, tilted))[0]

    ref.loss(params, arrays, tilted, 1, router_bias=keep)
    _, solved, report, compared = brb.solve(own, params, arrays, tilted, 1)
    worst, limit = compared["router_load_off_mean"]
    assert worst <= limit == report["tolerance"]
    mean = report["mean_load"]
    for s, bias, layer in zip(scores, solved["e_router_bias"],
                              report["layers"]):
        assert 0 < layer["iterations"] < brb.CAP
        by_choice = np.asarray(_tilted(s + bias, 2, tilted)).sum(0)
        assert [by_choice.min(), by_choice.max()] == layer["loads"]
        assert np.abs(by_choice - mean).max() <= limit
    plain = brb.solve(ref, params, arrays, sizes, 1)[1]["e_router_bias"]
    assert solved["e_router_bias"].tobytes() != plain.tobytes()


def _routed_config(**published):
    named = rules.configurations(BENCH, ROOT)
    config = json.loads(json.dumps(named["nemotron3_super_120b"]))
    config.update(published)
    return config


def test_a_group_limited_router_needs_a_reference_that_chooses_by_it():
    """ISSUE 38's probe, the hybrid under ``n_group`` 2, is refused: its
    reference routes by the plain top-k, so the solve would balance a
    rule neither side runs.  So is a published ``n_group`` the sizes do
    not restate, and a choice that strays outside its groups; a
    reference whose choice keeps to them passes."""
    config = _routed_config()
    rules.router_is_the_references("hybrid", config)
    config["sizes"].update(n_group=2, topk_group=1)
    with pytest.raises(AssertionError, match="no expert_choice"):
        rules.router_is_the_references("probe", config)
    with pytest.raises(AssertionError, match="no expert_choice"):
        rules.router_is_the_references(
            "published", _routed_config(n_group=8, topk_group=4))
    strays = types.SimpleNamespace(expert_choice=_tilted)
    with pytest.raises(AssertionError, match="outside its groups"):
        rules.router_is_the_references(
            "probe", {**config, "sizes": {**config["sizes"], "tilt": 0.0}},
            strays)
    keeps = types.SimpleNamespace(expert_choice=_within_best_group)
    rules.router_is_the_references("probe", config, keeps)


def _loads_before(scores, bias, top_k: int):
    """``loads`` as PR 33 wrote it, kept as the reference the one-group
    path is held to bit for bit."""
    biased = scores + bias
    kth = jax.lax.top_k(biased, top_k)[0][:, -1:]
    return jnp.sum(biased >= kth, axis=0, dtype=jnp.float32)


@jax.jit
def _balance_before(scores):
    """``balance`` as PR 33 wrote it, at the hybrid's rehearsal top-k."""
    top_k = 2
    experts = scores.shape[1]
    mean, tolerance = brb.target(scores.shape[0], experts, top_k)

    def unbalanced(state):
        t, _, load = state
        return (t < brb.CAP) & (jnp.max(jnp.abs(load - mean)) > tolerance)

    def update(state):
        t, bias, load = state
        gamma = jnp.maximum(brb.GAMMA * brb.DECAY ** t, brb.GAMMA_FLOOR)
        bias = bias + gamma * jnp.sign(mean - load)
        return t + 1, bias, _loads_before(scores, bias, top_k)

    zero = jnp.zeros((experts,), jnp.float32)
    return jax.lax.while_loop(
        unbalanced, update,
        (jnp.float32(0), zero, _loads_before(scores, zero, top_k)))


def test_at_one_group_the_solve_is_what_it_was_bit_for_bit():
    """On the hybrid's rehearsal sizes, at every ``E`` layer of the walk:
    today's ``balance`` (no choice of the reference's, by default and
    named) against PR 33's, and the solved bias the model gets against
    the one PR 33's rule gives."""
    _, params, arrays, sizes = _built(SEEDS[6])
    assert sizes["num_experts_per_tok"] == 2
    assert not hasattr(ref, "expert_choice")
    before = []

    def at_expert_layer(scores):
        t, bias, load = _balance_before(scores)
        for got in (brb.balance(scores, 2), brb.balance(scores, 2, None)):
            assert np.asarray(got[0]).tobytes() == np.asarray(bias).tobytes()
            assert np.asarray(got[1]).tobytes() == np.asarray(load).tobytes()
            assert int(got[2]) == int(t)
        np.testing.assert_array_equal(brb.loads(scores, 0.0, 2),
                                      _loads_before(scores, 0.0, 2))
        before.append(np.asarray(bias))
        return bias

    loss = ref.loss(params, arrays, sizes, 1, router_bias=at_expert_layer)
    solved_loss, solved, _, _ = brb.solve(ref, params, arrays, sizes, 1)
    assert solved_loss == loss and list(solved) == ["e_router_bias"]
    assert solved["e_router_bias"].tobytes() == np.stack(before).tobytes()


# -- the cell's feed: a fresh batch every step ---------------------------------

def test_the_feed_hands_the_next_resident_batch_at_every_unpacking():
    feed = measure.Feed([["a0", "a1"], ["b0", "b1"], ["c0", "c1"]])
    seen = []
    for _ in range(7):
        (lambda *batch: seen.append(batch))(*feed)
    assert [b[0][0] for b in seen] == list("abcabca")
    assert all(b[0][0] == b[1][0] for b in seen)     # one batch at a time
    one = measure.Feed([["x", "y"]])
    assert [list(one), list(one)] == [["x", "y"], ["x", "y"]]


def test_the_cells_batches_are_one_draw_of_the_seed_and_all_differ():
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "train_b1_s4096.json")) as f:
        traffic = json.load(f)
    # more batches than a run has steps: 4 of warm-up, a 10 s window of
    # steps of 145 ms, 32 traced
    assert traffic["resident_batches"] >= 2 * (4 + 10 / 0.145 + 32)
    assert 0.94 < traffic["last_loss_over_first_limit"] < 0.998
    for name in os.listdir(os.path.join(ROOT, "benchmarks", "traffic")):
        if name != "train_b1_s4096.json":
            with open(os.path.join(ROOT, "benchmarks", "traffic",
                                   name)) as f:
                assert "resident_batches" not in json.load(f), name
    cell = measure.load_cell(CELL, rehearse=True)
    cell["traffic"]["resident_batches"] = 5
    _, _, arrays, feed = measure._build(cell, SEEDS[1], jax.devices()[:1])
    lone = measure.resolve(cell["config"]["inputs"])(
        SEEDS[1], cell["traffic"]["batch"], cell["traffic"]["seq"],
        cell["sizes"])
    # the reference reads the batch a cell of one batch would have drawn
    for got, want in zip(arrays, lone):
        np.testing.assert_array_equal(got, want)
    batches = [[np.asarray(t.data) for t in feed] for _ in range(6)]
    np.testing.assert_array_equal(batches[0][0], lone[0])
    np.testing.assert_array_equal(batches[5][0], batches[0][0])
    assert len({b[0].tobytes() for b in batches[:5]}) == 5


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(monkeypatch):
    """On fresh batches an unchanged state reads 1 give or take the
    batches' own difference, not exactly 1: the cell's own limit lies
    under that (``last_loss_over_first_limit``)."""
    load_cell = measure.load_cell

    def frozen(workload, rehearse):
        cell = load_cell(workload, rehearse)
        cell["config"]["optimizer"]["kwargs"]["learning_rate"] = 0.0
        return cell

    monkeypatch.setattr(measure, "load_cell", frozen)
    result = measure.measure(CELL, SEEDS[5], 0.6, False, True,
                             time.perf_counter(), lambda line: None)
    fall = result["compared"]["last_loss_over_first"]
    assert fall["limit"] == 0.99 < fall["value"] < 1.01
    assert fall["holds"] is False and result["correct"] is False
    assert all(pair["holds"] for name, pair in result["compared"].items()
               if name != "last_loss_over_first")


# -- the program under a non-zero bias against the reference ------------------

HELD, LATENT, INNER, TOP_K, N_ROUTED, HIDDEN, WIDE = 4, 128, 256, 4, 16, 64, 96
SIZES = {"num_experts_per_tok": TOP_K, "routed_scaling_factor": 2.5,
         "expert_offset": 0, "norm_eps": 1e-5}


def _layer(rng, tokens):
    def normal(*shape, std=0.1):
        return jnp.asarray(std * rng.standard_normal(shape), jnp.float32)
    p = {"e_router_w": normal(N_ROUTED, HIDDEN, std=0.3),
         "e_down_w": normal(HIDDEN, LATENT),
         "e_w1": normal(HELD, LATENT, INNER),
         "e_w2": normal(HELD, INNER, LATENT),
         "e_up_w": normal(LATENT, HIDDEN),
         "e_shared_w1": normal(HIDDEN, WIDE),
         "e_shared_w2": normal(WIDE, HIDDEN)}
    # a bias that decides: expert 1 (held) for every token, expert 9
    # (absent) for none, the rest nudged
    bias = 0.05 * rng.standard_normal(N_ROUTED)
    bias[1], bias[9] = 5.0, -5.0
    p["e_router_bias"] = jnp.asarray(bias, jnp.float32)
    return p, normal(tokens, HIDDEN, std=1.0)


def _program(u, p):
    from paddle_tpu.nn.functional import moe
    return moe.latent_moe(            # (batch, seq, hidden): one sequence
        u[None], p["e_router_w"], p["e_router_bias"], p["e_down_w"],
        p["e_w1"], p["e_w2"], p["e_up_w"], p["e_shared_w1"],
        p["e_shared_w2"], top_k=TOP_K,
        scale=SIZES["routed_scaling_factor"], expert_offset=0)[0]


@pytest.mark.parametrize("path", ["dense_mask", "sorted_rows"])
def test_the_program_under_a_bias_agrees_with_the_reference(path,
                                                            monkeypatch):
    from paddle_tpu.framework import monitor
    from paddle_tpu.ops.pallas import grouped_matmul as gmm
    monkeypatch.setattr(gmm, "_INTERPRET", path == "sorted_rows")
    p, u = _layer(np.random.default_rng(21), 256)
    weight = jnp.asarray(np.random.default_rng(22).standard_normal(u.shape),
                         jnp.float32)

    def value_and_grads(layer):
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                lambda u, p: jnp.sum(layer(u, p) * weight),
                argnums=(0, 1)))(u, p)

    monitor.reset_all_stats()
    got, (got_du, got_dp) = value_and_grads(_program)
    stats = monitor.all_stats()
    rows = stats["moe_expert_rows_computed_total"] \
        / stats["moe_calls_traced_total"]
    # 256 tokens: the dense mask computes every held expert on each; the
    # sorted rows one tile of 256 for expert 1's 256 rows and one each
    # for the three others
    assert rows == (HELD * 256 if path == "dense_mask"
                    else HELD * gmm.TILE_ROWS)
    want, (want_du, want_dp) = value_and_grads(
        lambda u, p: ref.experts(u, p, SIZES))

    def close(a, b, what):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        worst = np.abs(a - b).max() / max(np.abs(b).max(), 1e-12)
        assert worst <= 2e-4, (what, worst)        # test_nemotron_h's

    close(got, want, "loss")
    close(got_du, want_du, "du")
    for name in p:
        if name != "e_router_bias":
            close(got_dp[name], want_dp[name], name)
    # the bias chooses: expert 1 has every token, expert 9 none
    s = np.asarray(ref._scores(u, p))
    _, sel = jax.lax.top_k(jnp.asarray(s) + p["e_router_bias"], TOP_K)
    sel = np.asarray(sel)
    assert (sel == 1).any(-1).all() and not (sel == 9).any()
    # and only chooses: no gradient reaches it, and the gates are those of
    # s: with gates from s + b, expert 1's would be near the whole scale
    assert not np.asarray(got_dp["e_router_bias"]).any()
    picked = np.take_along_axis(s, sel, -1)
    gate_1 = (2.5 * picked / picked.sum(-1, keepdims=True))[sel == 1]
    z = u @ p["e_down_w"]
    only_1 = jnp.maximum(z @ p["e_w1"][1], 0.0) ** 2 @ p["e_w2"][1]
    others = {**p, "e_w1": p["e_w1"].at[1].set(0.0)}
    with jax.default_matmul_precision("highest"):
        part = _program(u, p) - _program(u, others)
        want_part = (gate_1[:, None] * only_1) @ p["e_up_w"]
    close(part, want_part, "expert 1's part, gated by s")
    assert gate_1.max() < 0.5 * 2.5


@pytest.mark.parametrize("path", ["dense_mask", "sorted_rows"])
def test_nemotron_h_under_a_bias_agrees_with_the_reference(path, monkeypatch):
    """The whole model, its buffer filled the way the harness fills it:
    loss and every parameter's gradient against ``loss_and_grads`` under
    the same bias.  (ISSUE 33 asked for this among the tier-1 tests; a
    ``benchmark`` PR adds no file outside the benchmark's directories.)"""
    from paddle_tpu.framework import monitor
    from paddle_tpu.jit import functional_loss_call
    from paddle_tpu.models import NemotronH, nemotron_h_loss, nemotron_h_tiny
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
    params = {n: t._data for n, t in model.named_parameters()}
    buffers = {n: t._data for n, t in model.named_buffers()}
    np.testing.assert_array_equal(buffers["e_router_bias"], bias)

    def program(params):
        return functional_loss_call(
            model, nemotron_h_loss, params, buffers, jax.random.PRNGKey(0),
            [jnp.asarray(ids), jnp.asarray(ids)])[0]

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
    keys = ("hybrid_override_pattern", "hidden_size", "vocab_size",
            "mamba_num_heads", "mamba_head_dim", "n_groups",
            "ssm_state_size", "conv_kernel", "chunk_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "num_experts_per_tok", "moe_latent_size",
            "moe_intermediate_size", "moe_shared_expert_intermediate_size",
            "routed_scaling_factor", "norm_eps", "expert_offset")
    sizes = {**{k: getattr(c, k) for k in keys},
             "n_routed_experts": c.experts_held,
             "router_width": c.n_routed_experts}
    under_bias = {**params, "e_router_bias": jnp.asarray(bias)}
    want, want_grads = ref.loss_and_grads(under_bias, (ids, ids), sizes)
    assert float(got) == pytest.approx(want, rel=2e-6)
    assert ref.loss(under_bias, (ids, ids), sizes, 1) \
        == pytest.approx(want, rel=2e-6)
    # the bias decides the loss: without it the reference reads another
    assert ref.loss(params, (ids, ids), sizes, 1) \
        != pytest.approx(want, rel=1e-5)
    for name in params:
        a = np.asarray(got_grads[name], np.float64)
        b = np.asarray(want_grads[name], np.float64)
        assert np.abs(b).max() > 0, name
        worst = np.abs(a - b).max() / np.abs(b).max()
        assert worst <= 5e-4, (name, worst)        # test_nemotron_h's
    # it only chooses: the reference's gradient for it is nought
    assert not np.asarray(want_grads["e_router_bias"]).any()

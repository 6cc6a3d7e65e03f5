"""The router's load-balancing correction bias, as seeded data.

A checkpoint of a model whose router scores with a sigmoid and chooses
with ``top_k(s + b)`` brings ``b``: during training it is moved after
every step by the auxiliary-loss-free rule, ``b_e <- b_e + gamma *
sign(mean load - load_e)`` (DeepSeek-V3 technical report, section 2.1.2,
"Auxiliary-Loss-Free Load Balancing"; ``b`` only chooses, the gates come
from ``s``), and it is what keeps the experts' loads even.  A freshly
drawn router has no such bias, and its loads are a draw of the seed: at
the hybrid cell's sizes 7 to 840 tokens an expert where the mean is 176.
The published values need the checkpoint, which is not in this
repository, so the benchmark makes a bias the way training would: that
same rule, run to convergence on the cell's one resident batch.

**The rule, its tolerance and its cap.**  At each ``E`` layer, in the
pattern's order and with the biases of the layers before it in place,
start from ``b = 0`` and repeat ``b <- b + gamma_t * sign(mean - load)``
with ``gamma_t = max(GAMMA * DECAY**t, GAMMA_FLOOR)``, where ``load_e``
counts the batch's tokens that have expert ``e`` among their ``top_k`` of
``s + b`` (or that the reference's own choice gives ``e``: see below)
and ``mean = tokens * top_k / experts``, until every expert's load lies
within ``TOLERANCE`` of the mean (176 +- 16 rows at the timed
sizes, where a row tile of the program's grouped matmuls holds 256: at
most 192 rows leaves a held expert 64 rows inside its one tile) or
``CAP`` iterations are over.  The steps add up to ``GAMMA / (1 - DECAY)``
= 1, the width of a sigmoid's range, before the floor is reached: no
expert is left behind the decay (at a sum of 0.4, one layer in forty
had an expert creep on at the floor's 2e-4 a step for hundreds of steps;
PR 33).  A solve that ends at its cap is not a balanced cell: ``solve``
hands the harness the worst load's distance from the mean beside the
tolerance, and the run's ``correct`` rests on it.  Deterministic: the
scores come from the seed's weights and batch through the float32
reference, and nothing here draws a number.

**A router that chooses otherwise.**  Where the configuration's
reference defines ``expert_choice(biased, top_k, sizes)``, the
(tokens, experts) marks, 1 where a token takes an expert, of the choice
its router makes from ``s + b`` (a group-limited router, DeepSeek-V3's
``noaux_tc``, chooses otherwise than the plain top-k), the loads are
counted through it, so the bias balances the choice the reference
routes by, and the rule is written in the reference alone.  Without it,
the plain ``top_k(s + b)`` above.  The solved bias goes to the buffer
``e_router_bias``, the name an expert model registers it under.

**What it does not do.**  The router keeps its seeded weights, its width
and its experts a token, and goes on training in the window; the program
has no rule that moves the bias, so it stays as solved, and balances the
loads for as long as the router stays near where it was solved.  On a
fresh batch every step (the cell's ``resident_batches``) that is the
whole run at the cell's rate of 1e-4: 40 tiles as built and 40 after 106
steps.  On one resident batch, memorised within four steps, it is not:
64-86 tiles after a window (PERF.md section 6, PR 33).

Nothing of the program is imported: the scores are the plain
reference's, on whose one walk the solve rides (``reference.loss`` asks
for each layer's bias as it reaches the layer).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

GAMMA, DECAY, GAMMA_FLOOR = 0.05, 0.95, 2e-4
TOLERANCE = 1 / 11      # of the mean load, and never under one row
CAP = 400


def target(tokens: int, experts: int, top_k: int) -> tuple:
    """(mean load, how far from it an expert's load may lie)."""
    mean = tokens * top_k / experts
    return mean, max(TOLERANCE * mean, 1.0)


def held_experts(sizes: dict) -> slice:
    """The experts the configuration holds here, among the router's."""
    return slice(sizes["expert_offset"],
                 sizes["expert_offset"] + sizes["n_routed_experts"])


def loads(scores, bias, top_k: int, choose=None):
    """(experts,): how many rows of ``scores`` (tokens, experts) have each
    expert among their ``top_k`` of ``scores + bias``, or among those
    ``choose(scores + bias, top_k)`` marks where it is given."""
    biased = scores + bias
    if choose is not None:
        return jnp.sum(choose(biased, top_k), axis=0, dtype=jnp.float32)
    kth = jax.lax.top_k(biased, top_k)[0][:, -1:]
    return jnp.sum(biased >= kth, axis=0, dtype=jnp.float32)


@functools.partial(jax.jit, static_argnames=("top_k", "choose"))
def balance(scores, top_k: int, choose=None):
    """``(bias, loads under it, iterations)`` for ``scores`` (tokens,
    experts) by the rule of the module's text."""
    experts = scores.shape[1]
    mean, tolerance = target(scores.shape[0], experts, top_k)

    def unbalanced(state):
        t, _, load = state
        return (t < CAP) & (jnp.max(jnp.abs(load - mean)) > tolerance)

    def update(state):
        t, bias, load = state
        gamma = jnp.maximum(GAMMA * DECAY ** t, GAMMA_FLOOR)
        bias = bias + gamma * jnp.sign(mean - load)
        return t + 1, bias, loads(scores, bias, top_k, choose)

    zero = jnp.zeros((experts,), jnp.float32)
    t, bias, load = jax.lax.while_loop(
        unbalanced, update,
        (jnp.float32(0), zero, loads(scores, zero, top_k, choose)))
    return bias, load, t


def solve(reference, params: dict, batch: tuple, sizes: dict, block: int):
    """``(reference loss, {"e_router_bias": (E layers, experts)}, report,
    compared)`` for the configuration's model under ``params`` on
    ``batch``: the reference's loss under the solved bias, the bias for
    the model's buffer of that name, what a ``routing:`` line says of it
    (the loads before and after, of every expert and of those held here,
    counted as the reference chooses), and the number the run's
    ``correct`` holds the solve to, beside its limit: the worst load's
    distance from the mean, and the tolerance."""
    top_k, held = sizes["num_experts_per_tok"], held_experts(sizes)
    choice = getattr(reference, "expert_choice", None)
    # one object for every layer, so ``balance`` compiles once a solve
    choose = choice and functools.partial(choice, sizes=sizes)
    solved, layers = [], []

    def at_expert_layer(scores):
        before = loads(scores, 0.0, top_k, choose)
        bias, after, iterations = balance(scores, top_k, choose)
        solved.append(np.asarray(bias))
        before, after = np.asarray(before), np.asarray(after)
        layers.append({
            "iterations": int(iterations),
            "loads": [int(after.min()), int(after.max())],
            "loads_unbiased": [int(before.min()), int(before.max())],
            "held_rows": after[held].astype(int).tolist(),
            "held_rows_unbiased": before[held].astype(int).tolist()})
        return bias

    loss = reference.loss(params, batch, sizes, block,
                          router_bias=at_expert_layer)
    mean, tolerance = target(int(np.asarray(batch[0]).size),
                             sizes["router_width"], top_k)
    worst = max(max(mean - row["loads"][0], row["loads"][1] - mean)
                for row in layers)
    report = {"mean_load": mean, "tolerance": tolerance, "cap": CAP,
              "layers": layers}
    return (loss, {"e_router_bias": np.stack(solved)}, report,
            {"router_load_off_mean": (worst, tolerance)})

"""A latent mixture-of-experts layer that is told which experts it holds.

The reference framework has no expert layer.  This is expert
parallelism's layer as one rank sees it (the ``model-configs`` guide,
section 4): the router scores **all** ``n_routed`` experts and picks
``top_k`` of them for every token, as published; the layer holds the
experts ``expert_offset .. expert_offset + held`` and computes their part
of the result.  What the absent experts would have added is left out; on
one chip there is no exchange, and nothing stands in for it.  The partial
results of all the shares, with the shared expert counted once, add up to
the uncut layer (``tests/test_nemotron_h.py``).

Raw arrays, ``jax.numpy`` only, differentiable by jax; no kernel.  The
device scopes ``router``, ``latent_down``, ``dispatch``, ``experts``,
``combine``, ``latent_up`` and ``shared`` are set here, the region around
them (``mlp``) by the caller.

**No token is dropped, at any load.**  The held experts' buffer is
``(held, tokens)`` rows: top-k picks distinct experts, so a token reaches
an expert at most once and an expert can be reached by every token;
``tokens`` rows an expert is therefore the bound at any routing, and the
layer computes all of them, weighting the rows a token was not routed to
by a gate of zero.  That is a dense mask: ``held`` rows a token where the
routed load needs ``top_k * held / n_routed`` on average
(``moe_expert_rows_*`` count both).  A grouped matmul whose rows follow
the load is a later change (ROADMAP, queue R).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.framework import monitor

__all__ = ["route_top_k", "held_gates", "latent_moe"]

# what the router's scores are computed in; the builder's check on the
# chip sets bfloat16 here to show that the hidden-state comparison sees
# it (PERF.md section 6, PR 27).  Not an option.
_ROUTER_DTYPE = jnp.float32

monitor.describe("moe_calls_traced_total",
                 "calls of latent_moe, added once per traced call (a "
                 "trace-time count)")
monitor.describe("moe_expert_rows_computed_total",
                 "rows (token x expert) that the held routed experts' "
                 "matmuls execute, added once per traced call of "
                 "latent_moe (a trace-time count)")
monitor.describe("moe_expert_rows_expected_total",
                 "rows the routed load needs on average: tokens x top_k x "
                 "held / n_routed, added once per traced call of "
                 "latent_moe (a trace-time count)")


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def route_top_k(u, router_w, router_bias, top_k: int, scale: float):
    """``s = sigmoid(float32(u) W_r^T)`` over all experts; ``sel =
    top_k(s + router_bias)`` (the bias only chooses; no gradient reaches
    it); ``g = scale * s[sel] / (sum s[sel] + 1e-20)``.  Returns ``(sel,
    g)``, both (..., top_k); ``g`` is normalised over all ``top_k``
    whether the experts are held here or not."""
    logits = jnp.einsum("...d,ed->...e", u, router_w,
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=_ROUTER_DTYPE)
    s = jax.nn.sigmoid(logits)
    _, sel = jax.lax.top_k(
        s + jax.lax.stop_gradient(router_bias).astype(s.dtype), top_k)
    picked = jnp.take_along_axis(s, sel, axis=-1)
    g = scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return sel, g


def held_gates(sel, g, held: int, expert_offset: int):
    """(..., held): the gate of each held expert for each token, zero
    where the token was not routed to it."""
    local = sel[..., None] - expert_offset == jnp.arange(held)
    return jnp.sum(jnp.where(local, g[..., None], 0), axis=-2)


def latent_moe(u, router_w, router_bias, down_w, w1, w2, up_w, shared_w1,
               shared_w2, *, top_k: int, scale: float, expert_offset: int):
    """The expert layer on ``u`` (batch, seq, hidden):

        sel, g = route_top_k(u)                    over all n_routed
        z = u W_down                               (the latent)
        y = (sum_{e in sel, held} g_e W2_e relu(W1_e z)^2) W_up
            + W2_s relu(W1_s u)^2                  (the shared expert, on u)

    ``w1`` (held, latent, inner) and ``w2`` (held, inner, latent) are the
    experts ``expert_offset .. expert_offset + held``."""
    held, n_routed = w1.shape[0], router_w.shape[0]
    tokens = u.shape[0] * u.shape[1]
    monitor.stat_add("moe_calls_traced_total", 1)
    monitor.stat_add("moe_expert_rows_computed_total", held * tokens)
    monitor.stat_add("moe_expert_rows_expected_total",
                     tokens * top_k * held / n_routed)
    with jax.named_scope("router"):
        sel, g = route_top_k(u, router_w, router_bias, top_k, scale)
    with jax.named_scope("dispatch"):
        gates = held_gates(sel, g, held, expert_offset).astype(u.dtype)
    with jax.named_scope("latent_down"):
        z = u @ down_w
    with jax.named_scope("experts"):
        inner = _relu2(jnp.einsum("bsd,edf->bsef", z, w1))
    with jax.named_scope("combine"):
        y = jnp.einsum("bsef,efd->bsd", inner * gates[..., None], w2)
    with jax.named_scope("latent_up"):
        y = y @ up_w
    with jax.named_scope("shared"):
        return y + _relu2(u @ shared_w1) @ shared_w2

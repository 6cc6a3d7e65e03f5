"""Mixture-of-experts layers that are told which experts they hold.

The reference framework has no expert layer.  This is expert
parallelism's layer as one rank sees it (the ``model-configs`` guide,
section 4): the router scores **all** ``n_routed`` experts and picks
``top_k`` of them for every token, as published; the layer holds the
experts ``expert_offset .. expert_offset + held`` and computes their part
of the result.  What the absent experts would have added is left out; on
one chip there is no exchange, and nothing stands in for it.  The partial
results of all the shares, with the shared expert counted once, add up to
the uncut layer (``tests/test_nemotron_h.py``,
``tests/test_bailing_hybrid.py``).

Two layers: ``latent_moe``, squared-ReLU experts in a latent narrower
than the hidden state (``models/nemotron_h.py``), and ``swiglu_moe``,
SwiGLU experts at the hidden width, ``(silu(u W1_e) * u W3_e) W2_e``, with
``W1_e`` and ``W3_e`` side by side in one ``w13`` (``models/
bailing_hybrid.py``).  The router may keep a token to some groups of
experts (``route_top_k``'s ``n_group``).

Raw arrays, differentiable by jax.  The device scopes ``router``,
``latent_down``, ``dispatch``, ``experts``, ``combine``, ``latent_up`` and
``shared`` (``swiglu_moe``: no latent) are set here, in the backward too,
the region around them (``mlp``) by the caller.

**No token is dropped, at any load.**  Top-k picks distinct experts, so a
token reaches a held expert at most once and an expert can be reached by
every token.  The routed experts' two matmuls take one of two paths,
chosen from the call's static shapes alone:

- **the dense mask** (``_dense_experts``), the plain path: every held
  expert on every token, ``held`` rows a token, the rows a token was not
  routed to weighted by a gate of zero.  Toy widths, a handful of tokens,
  no TPU: whatever ``ops/pallas/grouped_matmul.supported`` declines.
- **the sorted rows** (``_sorted_experts``), where the latent and inner
  widths are lane multiples and the tokens fill a row tile: the routed
  (token, held expert) pairs are laid, expert by expert, into a row buffer
  in which every expert starts on a row-tile boundary (``dispatch``: sums,
  comparisons and one sort along the tokens, inside the step, no program
  of its own; the rows are gathered by a kernel); ``relu(rows W1_e)`` and
  ``relu^2 W2_e`` are grouped matmuls (``experts``, ``combine``; SwiGLU:
  ``rows W13_e`` into ``[a | b]``, and ``silu(a) * b`` taken on its way
  into ``W2_e``); the rows are gated and added back to their tokens in
  float32 by a kernel.
  The backward is the same mechanism transposed, and the weight gradients
  arrive in the weights' own ``(held, in, out)`` layout.  The buffer has
  room for the worst routing (``min(top_k, held)`` rows a token, the
  mask's own size), and every kernel's grid ends at the tile the routing
  filled last, a run-time count: the work follows the load, ``top_k *
  held / n_routed`` rows a token on average with each expert rounded up
  to a tile (``moe_expert_rows_*`` count both), nothing outside the
  kernels touches the buffer, and no routing overflows it.

The kernels' module is imported by the call that needs it, not with the
package.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from paddle_tpu.framework import monitor
from paddle_tpu.ops.pallas.common import traced_once

__all__ = ["route_top_k", "held_gates", "latent_moe", "swiglu_moe",
           "swiglu"]

# what the router's scores are computed in; the builder's check on the
# chip sets bfloat16 here to show that the hidden-state comparison sees
# it (PERF.md section 6, PR 27).  Not an option.
_ROUTER_DTYPE = jnp.float32

# what a caller's ``jax.checkpoint`` keeps of the router
# (``save_only_these_names(*ROUTER_SAVED)``, ``models/nemotron_h.py``), so
# that its backward neither scores nor chooses a second time: the choice,
# and the logits at the chosen places (``route_top_k`` says why those).
ROUTER_SAVED = ("router_sel", "router_logits")

monitor.describe("moe_calls_traced_total",
                 "calls of latent_moe or swiglu_moe, added once per traced "
                 "call (a trace-time count)")
monitor.describe("moe_expert_rows_computed_total",
                 "rows (token x expert) that the held routed experts' "
                 "matmuls execute, added once per traced call of "
                 "latent_moe or swiglu_moe on the path that call takes (a "
                 "trace-time count): tokens x held on the dense mask; on "
                 "the sorted rows, whose kernels skip tiles by a run-time "
                 "count, the "
                 "rows visited at the expected load, each held expert's "
                 "tokens x top_k / n_routed rounded up to row tiles")
monitor.describe("moe_expert_rows_expected_total",
                 "rows the routed load needs on average: tokens x top_k x "
                 "held / n_routed, added once per traced call of "
                 "latent_moe or swiglu_moe (a trace-time count)")


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def swiglu(x, w13):
    """``silu(x W1) * x W3`` for ``w13 = [W1 | W3]`` (in, 2 x width)."""
    a, b = jnp.split(x @ w13, 2, axis=-1)
    return jax.nn.silu(a) * b


def _keep_groups(biased, n_group: int, topk_group: int):
    """DeepSeek-V3's group limit (``noaux_tc``): the experts cut into
    ``n_group`` groups of consecutive ids, each group scored by the sum of
    its two best ``biased`` scores; the ``topk_group`` best groups keep
    their scores, every other expert reads -inf."""
    grouped = biased.reshape(*biased.shape[:-1], n_group, -1)
    best_two = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, kept = jax.lax.top_k(best_two, topk_group)
    keep = jnp.any(kept[..., :, None] == jnp.arange(n_group), axis=-2)
    return jnp.where(keep[..., None], grouped, -jnp.inf).reshape(
        biased.shape)


def route_top_k(u, router_w, router_bias, top_k: int, scale: float,
                n_group: int = 1, topk_group: int = 1):
    """``s = sigmoid(float32(u) W_r^T)`` over all experts; ``sel =
    top_k(s + router_bias)`` (the bias only chooses; no gradient reaches
    it), among the ``topk_group`` best of ``n_group`` groups of experts
    where ``n_group`` is above 1 (``_keep_groups``); ``g = scale * s[sel]
    / (sum s[sel] + 1e-20)``.  Returns ``(sel, g)``, both (..., top_k);
    ``g`` is normalised over all ``top_k`` whether the experts are held
    here or not.

    ``sel`` and the logits at ``sel`` carry the names ``ROUTER_SAVED``
    (identities outside a ``jax.checkpoint`` whose policy names them).
    The logits and not the scores, because the sigmoid's derivative reads
    the sigmoid's own result: with the scores kept the backward would
    still ask for the logits and run the einsum over all experts again;
    from the kept logits it takes ``top_k`` sigmoids a token.  ``s[sel]``
    is computed as ``sigmoid(logits[sel])``, the same function of the
    same numbers."""
    logits = jnp.einsum("...d,ed->...e", u, router_w,
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=_ROUTER_DTYPE)
    s = jax.nn.sigmoid(logits)
    biased = s + jax.lax.stop_gradient(router_bias).astype(s.dtype)
    if n_group > 1:
        biased = _keep_groups(biased, n_group, topk_group)
    _, sel = jax.lax.top_k(biased, top_k)
    sel_name, logits_name = ROUTER_SAVED
    sel = checkpoint_name(sel, sel_name)
    picked = jax.nn.sigmoid(checkpoint_name(
        jnp.take_along_axis(logits, sel, axis=-1), logits_name))
    g = scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return sel, g


def held_gates(sel, g, held: int, expert_offset: int):
    """(..., held): the gate of each held expert for each token, zero
    where the token was not routed to it."""
    local = sel[..., None] - expert_offset == jnp.arange(held)
    return jnp.sum(jnp.where(local, g[..., None], 0), axis=-2)


def _dense_experts(z, w1, w2, gates):
    """The plain path, a dense mask: every held expert on every token, the
    rows a token was not routed to weighted by a gate of zero.  ``z``
    (tokens, latent), ``gates`` (tokens, held)."""
    with jax.named_scope("experts"):
        inner = _relu2(jnp.einsum("td,edf->tef", z, w1))
    with jax.named_scope("combine"):
        return jnp.einsum("tef,efd->td",
                          inner * gates.astype(z.dtype)[..., None], w2)


def _dense_swiglu(x, w13, w2, gates):
    """``_dense_experts`` for SwiGLU experts: ``x`` (tokens, hidden),
    ``w13`` (held, hidden, 2 x inner), ``w2`` (held, inner, hidden)."""
    with jax.named_scope("experts"):
        a, b = jnp.split(jnp.einsum("td,edf->tef", x, w13), 2, axis=-1)
        inner = jax.nn.silu(a) * b
    with jax.named_scope("combine"):
        return jnp.einsum("tef,efd->td",
                          inner * gates.astype(x.dtype)[..., None], w2)


def _buffer_tiles(tokens: int, held: int, top_k: int, tile_rows: int) -> int:
    """Row tiles of the sorted buffer at the worst routing: every token
    reaches ``min(top_k, held)`` held experts, and every expert rounds up
    to a tile (and owns one when it has no token)."""
    return -(-tokens * min(top_k, held) // tile_rows) + held


def _row_plan(hit, gates, tile_rows: int, n_tiles: int):
    """Where each routed (token, held expert) pair lies in a buffer of
    ``n_tiles`` row tiles, built inside the step from ``hit`` (tokens,
    held): an expert's rows are its tokens in order (one key-value sort
    along the tokens puts them first), every expert owns one tile at
    least and starts on a tile boundary.

    Returns ``tile_group`` (n_tiles,), ``tiles_used`` (1,), ``tile_start``
    (held,), ``key`` (held, tokens: the sorted keys, for the way back) and,
    for every row of the buffer, ``row_token`` (rows,) and ``gate`` (rows,
    1): ``tokens`` and zero on a row of padding or of an unused tile."""
    tokens, held = hit.shape
    i32, rows = jnp.int32, n_tiles * tile_rows
    hit_t = hit.T
    # (plain lax arithmetic where jax.numpy would wrap a jit of its own:
    # every one of those is lowered apart, at every start)
    tiles = jnp.maximum(1, jax.lax.div(
        jnp.sum(hit_t, axis=1, dtype=i32) + (tile_rows - 1), i32(tile_rows)))
    tile_end = jax.lax.cumsum(tiles)
    tile_start = tile_end - tiles
    tile = jnp.arange(n_tiles, dtype=i32)
    tile_group = jnp.minimum(
        jnp.sum(tile_end[None, :] <= tile[:, None], axis=1, dtype=i32),
        held - 1)
    token = jnp.arange(tokens, dtype=i32)
    key, gate = jax.lax.sort(
        (token + tokens * (1 - hit_t.astype(i32)),
         gates.T.astype(jnp.float32)), dimension=1, num_keys=1)
    # by rank within the expert, token and gate side by side (the gate's
    # bits as an integer), then by row: expert after expert from its first
    # tile on; what an expert writes past its own tiles, the next one
    # overwrites
    by_rank = jnp.stack([
        jnp.minimum(key, tokens),
        jax.lax.bitcast_convert_type(
            gate * (key < tokens).astype(jnp.float32), i32)])
    by_row = jnp.stack([jnp.full((rows + tokens,), tokens, i32),
                        jnp.zeros((rows + tokens,), i32)])
    first_row = tile_start * tile_rows

    def lay(e, by_row):
        return jax.lax.dynamic_update_slice(
            by_row, jax.lax.dynamic_index_in_dim(by_rank, e, 1, False),
            (i32(0), first_row[e]))

    by_row = jax.lax.fori_loop(0, held, lay, by_row)
    return (tile_group, jnp.sum(tiles, dtype=i32).reshape(1), tile_start,
            key, by_row[0, :rows],
            jax.lax.bitcast_convert_type(by_row[1, :rows],
                                         jnp.float32).reshape(-1, 1))


def _gates_of_rows(dgate, hit, key, tile_start, tile_rows: int):
    """(tokens, held): ``_row_plan``'s way back for one value a row,
    ``dgate`` (rows, 1); zero where ``hit`` is false."""
    tokens = hit.shape[0]
    flat = jnp.concatenate([dgate.reshape(-1),
                            jnp.zeros((tokens,), dgate.dtype)])
    by_rank = jax.vmap(lambda start: jax.lax.dynamic_slice(
        flat, (start,), (tokens,)))(tile_start * tile_rows)
    token = key - tokens * (key >= tokens).astype(jnp.int32)
    _, by_token = jax.lax.sort((token, by_rank), dimension=1, num_keys=1)
    return jax.lax.select(hit, by_token.T, jnp.zeros_like(by_token.T))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _sorted_experts(z, w1, w2, gates, hit, top_k: int):
    """``sum_e gates[:, e] * relu(z W1_e)^2 W2_e`` over the routed pairs
    ``hit`` alone: the pairs sorted by expert into a row buffer with room
    for the worst routing, two grouped matmuls over the tiles the routing
    filled, the rows gated and added back to their tokens in float32."""
    return _sorted_fwd(z, w1, w2, gates, hit, top_k)[0]


def _sorted_fwd(z, w1, w2, gates, hit, top_k):
    from paddle_tpu.ops.pallas import grouped_matmul as gmm
    return _sorted_fwd_traced(z, w1, w2, gates, hit, top_k, gmm._INTERPRET)


def _sorted_bwd(top_k, saved, dy):
    from paddle_tpu.ops.pallas import grouped_matmul as gmm
    return (*_sorted_bwd_traced(saved, dy, gmm._INTERPRET), None)


# Both halves are traced once a process and replayed, as the kernels
# inside them are, and stay one equation each of the layer around them:
# every expert layer of a stack has the same shapes, the forward is wanted
# by the pass that runs it and by the one that runs it a second time, and
# ``jax.checkpoint`` walks the layer's equations several times over.
# ``interpret`` is what the kernels' module reads while the body is
# traced, so it belongs to the key.
@traced_once(static_argnums=(5, 6), inline=False)
def _sorted_fwd_traced(z, w1, w2, gates, hit, top_k, interpret):
    from paddle_tpu.ops.pallas import grouped_matmul as gmm

    tokens, held = hit.shape
    with jax.named_scope("dispatch"):
        tile_group, used, tile_start, key, row_token, gate = _row_plan(
            hit, gates, gmm.TILE_ROWS,
            _buffer_tiles(tokens, held, top_k, gmm.TILE_ROWS))
        x = gmm.gather_rows(z.astype(jnp.float32), row_token, used,
                            out_dtype=z.dtype)
    with jax.named_scope("experts"):
        r = gmm.group_rows(x, w1, tile_group, used, epilogue="relu")
    with jax.named_scope("combine"):
        y2 = gmm.group_rows(r, w2, tile_group, used, prologue="square")
        y = gmm.scatter_rows(y2, gate, row_token, used, tokens)
    return y.astype(z.dtype), (w1, w2, hit, tile_group, used, tile_start,
                               key, row_token, gate, x, r, y2)


@traced_once(static_argnums=(2,), inline=False)
def _sorted_bwd_traced(saved, dy, interpret):
    from paddle_tpu.ops.pallas import grouped_matmul as gmm

    w1, w2, hit, tile_group, used, tile_start, key, row_token, gate, x, r, \
        y2 = saved
    tokens, held = hit.shape
    with jax.named_scope("combine"):
        dy2, dgate = gmm.gather_rows(dy.astype(jnp.float32), row_token, used,
                                     gate=gate, other=y2, out_dtype=dy.dtype)
        dpre = gmm.group_rows(dy2, w2, tile_group, used, transpose_w=True,
                              epilogue="times_2m", m=r)
        dw2 = gmm.group_weights(r, dy2, tile_group, used, held,
                                prologue="square", out_dtype=w2.dtype)
    with jax.named_scope("experts"):
        dx = gmm.group_rows(dpre, w1, tile_group, used, transpose_w=True)
        dw1 = gmm.group_weights(x, dpre, tile_group, used, held,
                                out_dtype=w1.dtype)
    with jax.named_scope("dispatch"):
        dz = gmm.scatter_rows(dx, jnp.ones_like(gate), row_token, used,
                              tokens).astype(dy.dtype)
        dgates = _gates_of_rows(dgate, hit, key, tile_start, gmm.TILE_ROWS)
    return dz, dw1, dw2, dgates


_sorted_experts.defvjp(_sorted_fwd, _sorted_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _sorted_swiglu(x, w13, w2, gates, hit, top_k: int):
    """``_sorted_experts`` for SwiGLU experts: ``sum_e gates[:, e] *
    (silu(x W1_e) * x W3_e) W2_e`` over the routed pairs ``hit`` alone,
    on the same row buffer: ``rows W13_e`` into ``[a | b]`` (``experts``),
    ``silu(a) * b`` taken on its way into ``W2_e`` (``combine``)."""
    return _swiglu_fwd(x, w13, w2, gates, hit, top_k)[0]


def _swiglu_fwd(x, w13, w2, gates, hit, top_k):
    from paddle_tpu.ops.pallas import grouped_matmul as gmm
    return _swiglu_fwd_traced(x, w13, w2, gates, hit, top_k, gmm._INTERPRET)


def _swiglu_bwd(top_k, saved, dy):
    from paddle_tpu.ops.pallas import grouped_matmul as gmm
    return (*_swiglu_bwd_traced(saved, dy, gmm._INTERPRET), None)


@traced_once(static_argnums=(5, 6), inline=False)
def _swiglu_fwd_traced(x, w13, w2, gates, hit, top_k, interpret):
    from paddle_tpu.ops.pallas import grouped_matmul as gmm

    tokens, held = hit.shape
    with jax.named_scope("dispatch"):
        tile_group, used, tile_start, key, row_token, gate = _row_plan(
            hit, gates, gmm.TILE_ROWS,
            _buffer_tiles(tokens, held, top_k, gmm.TILE_ROWS))
        rows = gmm.gather_rows(x.astype(jnp.float32), row_token, used,
                               out_dtype=x.dtype)
    with jax.named_scope("experts"):
        ab = gmm.group_rows(rows, w13, tile_group, used)
    with jax.named_scope("combine"):
        y2 = gmm.group_rows(ab, w2, tile_group, used, prologue="swiglu")
        y = gmm.scatter_rows(y2, gate, row_token, used, tokens)
    return y.astype(x.dtype), (w13, w2, hit, tile_group, used, tile_start,
                               key, row_token, gate, rows, ab, y2)


@traced_once(static_argnums=(2,), inline=False)
def _swiglu_bwd_traced(saved, dy, interpret):
    from paddle_tpu.ops.pallas import grouped_matmul as gmm

    w13, w2, hit, tile_group, used, tile_start, key, row_token, gate, \
        rows, ab, y2 = saved
    tokens, held = hit.shape
    with jax.named_scope("combine"):
        dy2, dgate = gmm.gather_rows(dy.astype(jnp.float32), row_token, used,
                                     gate=gate, other=y2, out_dtype=dy.dtype)
        dab = gmm.group_rows(dy2, w2, tile_group, used, transpose_w=True,
                             epilogue="dswiglu", m=ab)
        dw2 = gmm.group_weights(ab, dy2, tile_group, used, held,
                                prologue="swiglu", out_dtype=w2.dtype)
    with jax.named_scope("experts"):
        drows = gmm.group_rows(dab, w13, tile_group, used, transpose_w=True)
        dw13 = gmm.group_weights(rows, dab, tile_group, used, held,
                                 out_dtype=w13.dtype)
    with jax.named_scope("dispatch"):
        dx = gmm.scatter_rows(drows, jnp.ones_like(gate), row_token, used,
                              tokens).astype(dy.dtype)
        dgates = _gates_of_rows(dgate, hit, key, tile_start, gmm.TILE_ROWS)
    return dx, dw13, dw2, dgates


_sorted_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def _count_rows(tokens: int, top_k: int, held: int, n_routed: int,
                follow_load: bool) -> None:
    """The layer's ``moe_*_total`` counts, once per traced call."""
    from paddle_tpu.ops.pallas import grouped_matmul as gmm

    if follow_load:
        expected = -(-tokens * top_k // n_routed)
        computed = held * gmm.TILE_ROWS * -(-expected // gmm.TILE_ROWS)
    else:
        computed = held * tokens
    monitor.stat_add("moe_calls_traced_total", 1)
    monitor.stat_add("moe_expert_rows_computed_total", computed)
    monitor.stat_add("moe_expert_rows_expected_total",
                     tokens * top_k * held / n_routed)


def latent_moe(u, router_w, router_bias, down_w, w1, w2, up_w, shared_w1,
               shared_w2, *, top_k: int, scale: float, expert_offset: int):
    """The expert layer on ``u`` (batch, seq, hidden):

        sel, g = route_top_k(u)                    over all n_routed
        z = u W_down                               (the latent)
        y = (sum_{e in sel, held} g_e W2_e relu(W1_e z)^2) W_up
            + W2_s relu(W1_s u)^2                  (the shared expert, on u)

    ``w1`` (held, latent, inner) and ``w2`` (held, inner, latent) are the
    experts ``expert_offset .. expert_offset + held``."""
    from paddle_tpu.ops.pallas import grouped_matmul as gmm

    held, n_routed = w1.shape[0], router_w.shape[0]
    tokens = u.shape[0] * u.shape[1]
    follow_load = gmm.supported(tokens, w1.shape[1], w1.shape[2], u.dtype)
    _count_rows(tokens, top_k, held, n_routed, follow_load)
    with jax.named_scope("router"):
        sel, g = route_top_k(u, router_w, router_bias, top_k, scale)
    with jax.named_scope("dispatch"):
        gates = held_gates(sel, g, held, expert_offset).reshape(tokens, held)
    with jax.named_scope("latent_down"):
        z = (u @ down_w).reshape(tokens, -1)
    if follow_load:
        with jax.named_scope("dispatch"):
            hit = held_gates(sel, jnp.ones_like(g), held,
                             expert_offset).reshape(tokens, held) > 0
        y = _sorted_experts(z, w1, w2, gates, hit, top_k)
    else:
        y = _dense_experts(z, w1, w2, gates)
    with jax.named_scope("latent_up"):
        y = y.reshape(*u.shape[:2], -1) @ up_w
    with jax.named_scope("shared"):
        return y + _relu2(u @ shared_w1) @ shared_w2


def swiglu_moe(u, router_w, router_bias, w13, w2, shared_w13, shared_w2, *,
               top_k: int, scale: float, expert_offset: int,
               n_group: int = 1, topk_group: int = 1):
    """The SwiGLU expert layer on ``u`` (batch, seq, hidden):

        sel, g = route_top_k(u)                    over all n_routed, by
                                                   groups where n_group > 1
        y = sum_{e in sel, held} g_e (silu(u W1_e) * u W3_e) W2_e
            + swiglu(u, W13_s) W2_s                (the shared expert)

    ``w13`` (held, hidden, 2 x inner) and ``w2`` (held, inner, hidden) are
    the experts ``expert_offset .. expert_offset + held``; the routed
    half takes the sorted rows where ``grouped_matmul.supported`` accepts
    the hidden and inner widths, else the dense mask."""
    from paddle_tpu.ops.pallas import grouped_matmul as gmm

    held, n_routed = w13.shape[0], router_w.shape[0]
    tokens = u.shape[0] * u.shape[1]
    follow_load = gmm.supported(tokens, w13.shape[1], w2.shape[1], u.dtype)
    _count_rows(tokens, top_k, held, n_routed, follow_load)
    with jax.named_scope("router"):
        sel, g = route_top_k(u, router_w, router_bias, top_k, scale,
                             n_group, topk_group)
    with jax.named_scope("dispatch"):
        gates = held_gates(sel, g, held, expert_offset).reshape(tokens, held)
    x = u.reshape(tokens, -1)
    if follow_load:
        with jax.named_scope("dispatch"):
            hit = held_gates(sel, jnp.ones_like(g), held,
                             expert_offset).reshape(tokens, held) > 0
        y = _sorted_swiglu(x, w13, w2, gates, hit, top_k)
    else:
        y = _dense_swiglu(x, w13, w2, gates)
    with jax.named_scope("shared"):
        return y.reshape(u.shape) + swiglu(u, shared_w13) @ shared_w2

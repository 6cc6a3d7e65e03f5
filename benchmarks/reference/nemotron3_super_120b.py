"""Plain reference for NVIDIA-Nemotron-3-Super-120B-A12B (``nemotron_h``;
``config.json`` as in the catalog of the ``model-configs`` guide): the
forward loss, and for the tests its gradients, in straightforward
``jax.numpy``, float32, highest matmul precision, no kernels, no chunks,
nothing imported from ``paddle_tpu``.

The stack is ``hybrid_override_pattern``, one letter a layer, each layer
one mixer or one feed-forward part: ``u = RMSNorm(x)`` (eps 1e-5, weight
only), ``x <- x + f(u)``; a final RMSNorm; ``logits = h W_head`` (untied,
no bias anywhere except the convolution's).

- ``M``, Mamba-2 (H heads of P = 64, G groups, N = 128, conv k = 4):
  ``[z | xBC | dt] = u W_in`` (widths H P, H P + 2 G N, H); ``xBC =
  silu(conv1d_causal_depthwise(xBC) + b_conv)``; split into ``x (S,H,P)``,
  ``B, C (S,G,N)`` (head h uses group h // (H/G)); ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t
  (x) x_t``, ``y_t = C_t . h_t + D x_t``; ``y = GroupRMSNorm_G(y *
  silu(z)) * w``; ``out = y W_out``.  Computed here as the *sequential
  recurrence*, a ``lax.scan`` over time (the program computes it in
  chunks).
- ``*``, attention: ``q = u W_q`` (heads of 128), ``k, v = u W_k, u W_v``
  (KV heads, each serving heads / KV heads query heads), causal
  ``softmax(q k^T / sqrt(128)) v`` with a materialised score matrix,
  ``W_o``.  No positional embedding (``assumed``, see the configuration
  file).
- ``E``, latent experts: ``s = sigmoid(u W_r^T)`` over all the router's
  experts; ``sel = top_k(s + b_corr)`` (``b_corr`` a buffer, zero unless
  ``params`` brings ``e_router_bias``); ``g = scale * s[sel] / (sum s[sel]
  + 1e-20)``; ``z = u W_down``; ``y = (sum over sel of g_e W2_e relu(W1_e
  z)^2) W_up + W2_s relu(W1_s u)^2``.  **Held share**: the parameters
  bring ``experts_held`` experts, the ids ``expert_offset`` onwards; the
  sum is a plain loop over those ids with a 0/1 membership mask of
  ``sel``; ``g`` stays normalised over all ``top_k``; what the absent
  experts would add is left out, here as in the program.
- Loss: mean next-token cross entropy over the first S-1 positions over
  the vocabulary rows the parameters hold.

Parameters are the program's own, by the names
``NemotronH.named_parameters()`` gives (per block type stacked on a
leading axis: the i-th ``M`` of the pattern reads row i of every ``m_*``).

Departures from the published model: the multi-token-prediction module is
left out; the held shares (heads, experts, vocabulary rows) are those of
the configuration file's deployment; see its ``reduced`` and ``assumed``.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# The step computes in bf16 (AMP O2: every weight and activation bf16;
# float32 in the norms' statistics, the router's scores, the scan's
# decays and state, and the softmax-CE) and the reference in float32.
# The first loss (about ln 16384 + 0.82 = 10.5 at the cell's sizes: the
# logits of an untied random head have a variance of 1.64) differs by
# what the bf16 rounding of 4095 positions' logits leaves after
# averaging: 1.7e-6 to 4.3e-5 relative over 12 seeds on the chip (PR 27).
# 1.75e-4 is four times the worst of them.  It is tight from above: the
# same step with its softmax-CE in bf16 reads 2.6e-3.  What else a
# lower precision moves (the scan's state in bf16: the hidden state,
# not the loss; the router's scores in bf16: neither, at initialisation)
# is in PERF.md section 6, PR 27, from tools/nemotron_check.py.
TOLERANCE_REL = 1.75e-4

_KINDS = {
    "M": ("m_norm", "m_in_w", "m_conv_w", "m_conv_b", "m_dt_bias",
          "m_a_log", "m_d", "m_gnorm_w", "m_out_w"),
    "*": ("a_norm", "a_q_w", "a_k_w", "a_v_w", "a_o_w"),
    "E": ("e_norm", "e_router_w", "e_router_bias", "e_down_w", "e_w1",
          "e_w2", "e_up_w", "e_shared_w1", "e_shared_w2")}


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _relu2(x):
    return jnp.maximum(x, 0.0) ** 2


def mamba2(u, p, sizes):
    """``f(u)`` of an ``M`` layer for ``u`` (S, hidden), one sequence."""
    heads, dim = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    groups, state = sizes["n_groups"], sizes["ssm_state_size"]
    k, eps = sizes["conv_kernel"], sizes["norm_eps"]
    s, inner = u.shape[0], heads * dim
    z, xbc, dt = jnp.split(u @ p["m_in_w"],
                           [inner, 2 * inner + 2 * groups * state], -1)
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    xbc = _silu(sum(padded[j:j + s] * p["m_conv_w"][j] for j in range(k))
                + p["m_conv_b"])
    x, b, c = jnp.split(xbc, [inner, inner + groups * state], -1)
    x = x.reshape(s, heads, dim)
    # head h reads group h // (heads / groups)
    b, c = (jnp.repeat(t.reshape(s, groups, state), heads // groups, 1)
            for t in (b, c))
    dt = jnp.logaddexp(dt + p["m_dt_bias"], 0.0)            # softplus
    a = -jnp.exp(p["m_a_log"])

    def step(h, now):
        x_t, b_t, c_t, dt_t = now
        h = jnp.exp(dt_t * a)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((heads, dim, state)),
                        (x, b, c, dt))
    y = (y + p["m_d"][:, None] * x).reshape(s, inner) * _silu(z)
    grouped = y.reshape(s, groups, inner // groups)
    grouped = grouped / jnp.sqrt(
        jnp.mean(grouped * grouped, -1, keepdims=True) + eps)
    return (grouped.reshape(s, inner) * p["m_gnorm_w"]) @ p["m_out_w"]


def attention(u, p, sizes):
    """``f(u)`` of a ``*`` layer for ``u`` (S, hidden), one sequence."""
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d, s = sizes["head_dim"], u.shape[0]
    q = (u @ p["a_q_w"]).reshape(s, heads, d).transpose(1, 0, 2)
    k, v = (jnp.repeat((u @ p[w]).reshape(s, kv, d).transpose(1, 0, 2),
                       heads // kv, 0) for w in ("a_k_w", "a_v_w"))
    scores = q @ k.transpose(0, 2, 1) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return (probs @ v).transpose(1, 0, 2).reshape(s, heads * d) @ p["a_o_w"]


def _scores(u, p):
    return 1.0 / (1.0 + jnp.exp(-(u @ p["e_router_w"].T)))


def router_scores(x, p, sizes):
    """``s`` (S, router width) of an ``E`` layer for the residual stream
    ``x`` (S, hidden) that enters it: the scores before any bias."""
    return _scores(_rms(x, p["e_norm"], sizes["norm_eps"]), p)


def experts(u, p, sizes):
    """``f(u)`` of an ``E`` layer for ``u`` (S, hidden): the part the
    held experts give, plus the shared expert."""
    top_k, scale = sizes["num_experts_per_tok"], sizes["routed_scaling_factor"]
    s = _scores(u, p)
    _, sel = jax.lax.top_k(s + p["e_router_bias"], top_k)
    picked = jnp.take_along_axis(s, sel, axis=-1)
    g = scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    z = u @ p["e_down_w"]
    y = jnp.zeros_like(z)
    for i in range(p["e_w1"].shape[0]):
        member = (sel == sizes["expert_offset"] + i).astype(u.dtype)  # 0/1
        g_i = (g * member).sum(-1, keepdims=True)
        y = y + g_i * (_relu2(z @ p["e_w1"][i]) @ p["e_w2"][i])
    return y @ p["e_up_w"] + _relu2(u @ p["e_shared_w1"]) @ p["e_shared_w2"]


BLOCKS = {"M": mamba2, "*": attention, "E": experts}
_NORM = {"M": "m_norm", "*": "a_norm", "E": "e_norm"}


def _layer(kind, x, p, sizes):
    return x + BLOCKS[kind](_rms(x, p[_NORM[kind]], sizes["norm_eps"]),
                            p, sizes)


def _float32(params: dict, sizes: dict) -> dict:
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    if "e_router_bias" not in p:
        p["e_router_bias"] = jnp.zeros(p["e_router_w"].shape[:2])
    held = p["e_w1"].shape[1]
    if held != sizes["n_routed_experts"]:
        raise ValueError(f"the parameters hold {held} experts, the "
                         f"configuration {sizes['n_routed_experts']}")
    return p


def _own(p, kind, i):
    return {k: p[k][i] for k in _KINDS[kind]}


def _walk(p: dict, ids_all, sizes: dict, layer, router_bias=None) -> list:
    """The final hidden states (after the last norm), one a sequence of
    ``ids_all``, layer by layer: the one walk of the pattern.

    ``router_bias``, where given, is asked at every ``E`` layer, in the
    pattern's order, for that layer's ``b_corr``: it gets the scores ``s``
    (the batch's tokens, router width) the layer's router gives under the
    biases of the layers before it, and what it returns takes the place
    of the row ``p`` brings."""
    xs, seen = [p["embed"][ids] for ids in ids_all], dict.fromkeys(_KINDS, 0)
    for kind in sizes["hybrid_override_pattern"]:
        own = _own(p, kind, seen[kind])
        seen[kind] += 1
        if kind == "E" and router_bias is not None:
            own["e_router_bias"] = jnp.asarray(router_bias(jnp.concatenate(
                [router_scores(x, own, sizes) for x in xs])), jnp.float32)
        xs = [layer(kind, x, own, sizes) for x in xs]
    return [_rms(x, p["norm_f"], sizes["norm_eps"]) for x in xs]


def hidden(p: dict, ids, sizes: dict, layer=_layer):
    """The final hidden state (after the last norm) of one sequence."""
    return _walk(p, [ids], sizes, layer)[0]


def _loss_sum(h, head_w, ids):
    logp = jax.nn.log_softmax(h[:-1] @ head_w, axis=-1)
    return -jnp.take_along_axis(logp, ids[1:, None], axis=-1).sum()


def loss(params: dict, batch: tuple, sizes: dict, block: int,
         router_bias=None) -> float:
    """Mean next-token cross entropy of ``batch`` = (ids, labels) under
    ``params``, layer by layer, within a layer a sequence at a time
    (``block`` is the harness's number of sequences a block; every layer
    here takes one).  ``router_bias``: see ``_walk``."""
    ids_all, labels_all = (np.asarray(a) for a in batch)
    if not np.array_equal(ids_all, labels_all):
        raise ValueError("the causal-LM batch uses its ids as labels")
    del block
    jitted = {kind: jax.jit(functools.partial(_layer, kind, sizes=sizes))
              for kind in BLOCKS}
    head = jax.jit(_loss_sum)
    with jax.default_matmul_precision("highest"):
        p = _float32(params, sizes)
        hs = _walk(p, ids_all, sizes,
                   lambda kind, x, own, _: jitted[kind](x, own), router_bias)
        total = sum(float(head(h, p["head_w"], jnp.asarray(ids)))
                    for h, ids in zip(hs, ids_all))
    return total / (ids_all.shape[0] * (ids_all.shape[1] - 1))


def loss_and_grads(params: dict, batch: tuple, sizes: dict):
    """(loss, {name: gradient}) for the tests: the same mathematics in
    one differentiable function, every sequence at once."""
    ids_all = jnp.asarray(np.asarray(batch[0]))

    def mean_loss(p):
        p = _float32(p, sizes)
        total = sum(_loss_sum(hidden(p, ids, sizes), p["head_w"], ids)
                    for ids in ids_all)
        return total / (ids_all.shape[0] * (ids_all.shape[1] - 1))

    with jax.default_matmul_precision("highest"):
        value, grads = jax.jit(jax.value_and_grad(mean_loss))(
            {k: jnp.asarray(v, jnp.float32) for k, v in params.items()})
    return float(value), grads


def flops_per_token(sizes: dict, seq: int) -> float:
    """Model FLOPs one token costs in training, forward + backward, no
    recomputation: 6 per multiply-accumulate (2 forward, 4 backward) of
    every held matmul weight, the routed experts at their expected use
    (``num_experts_per_tok`` x held / router width of a token each), the
    head's slice; plus causal attention's two S x S products at half (the
    lower triangle, as ``gpt2_345m`` counts them) and SSD's products: the
    within-chunk ``C B^T`` and ``(L o C B^T) X`` at half of chunk x chunk
    for the same reason, the chunk's state ``B^T X`` and the carried
    state's ``C h`` in full.  Embedding lookups, norms, the convolution,
    activations, the router's top-k and softmax are not counted."""
    d, pattern = sizes["hidden_size"], sizes["hybrid_override_pattern"]
    heads, dim = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    groups, state = sizes["n_groups"], sizes["ssm_state_size"]
    inner = heads * dim
    mamba = d * (2 * inner + 2 * groups * state + heads) + inner * d
    chunk = sizes["chunk_size"]
    ssd = (groups * state + inner) * chunk / 2 + 2 * inner * state
    q = sizes["num_attention_heads"] * sizes["head_dim"]
    kv = sizes["num_key_value_heads"] * sizes["head_dim"]
    attn = 2 * d * q + 2 * d * kv
    lat, mid = sizes["moe_latent_size"], sizes["moe_intermediate_size"]
    use = (sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
           / sizes["router_width"])
    moe = (sizes["router_width"] * d + 2 * d * lat + use * 2 * lat * mid
           + 2 * d * sizes["moe_shared_expert_intermediate_size"])
    macs = (pattern.count("M") * (mamba + ssd) + pattern.count("*") * attn
            + pattern.count("E") * moe + d * sizes["vocab_size"])
    return 6.0 * macs + pattern.count("*") * 6.0 * seq * q

"""Plain reference for Ling-3.0-flash (``bailing_hybrid``; ``config.json``
as in the catalog of the ``model-configs`` guide): the forward loss, and
for the tests its gradients, in straightforward ``jax.numpy``, float32,
highest matmul precision, no kernels, no chunks, nothing imported from
``paddle_tpu``.

Every layer is a mixer and a feed-forward part, each ``x <- x +
f(RMSNorm(x))`` (eps ``rms_norm_eps``, weight only); the mixer is MLA where
``(i + 1) % layer_group_size == 0`` and KDA elsewhere, the feed-forward
part a dense SwiGLU MLP on the first ``first_k_dense_replace`` layers and
SwiGLU experts after; a final RMSNorm; ``logits = h W_head`` (untied, no
bias anywhere).

- KDA (H heads, keys and values of ``head_dim``, conv k = 4), computed
  here as the **token-by-token recurrence**, a ``lax.scan`` over time (the
  program computes it in chunks): ``[q | k | v] = silu(conv1d_causal(u
  W_qkv))``, ``q, k`` L2-normalised per head, ``q`` scaled by
  ``1/sqrt(head_dim)``; ``beta = sigmoid(u W_beta)``; ``log alpha =
  kda_lower_bound * sigmoid(exp(A_log) (u W_alpha + dt_bias))``;
  ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t
  v_t^T``, ``o_t = S_t^T q_t``; ``out = (RMSNorm_head(o) * sigmoid(u
  W_gate)) W_out``.
- MLA: ``q = u W_q`` per head ``[q_nope | q_rope]``; ``[c | k_rope] = u
  W_kv_a``, ``c = RMSNorm(c)``, ``[k_nope | v] = c W_kv_b`` per head;
  QK-norm over each head's ``[nope | rope]``; the rotary embedding on
  the rope channels, each pair ``(x[2i], x[2i+1])`` taken as the complex
  number ``x[2i] + i x[2i+1]`` and multiplied by ``exp(i t
  theta^(-2i/rope))``; causal ``softmax(q k^T / sqrt(nope + rope)) v``
  with a materialised score matrix, ``block`` query rows at a time; each
  head's output times ``sigmoid(u W_gate)_h``; ``W_o``.
- Experts: ``s = sigmoid(u W_r^T)`` over all the router's experts; the
  choice ``expert_choice(s + b, top_k, sizes)`` (DeepSeek-V3's group
  limit); ``g = scale * s / sum(s over the choice)`` on the chosen
  experts; ``y = sum over the held experts of g_e (silu(u W1_e) * u W3_e)
  W2_e`` (a plain loop over the held ids, the dense mask) plus the
  shared expert.  What the absent experts would add is left out, here as
  in the program.
- Loss: mean next-token cross entropy over the first S-1 positions over
  the vocabulary rows the parameters hold.

Parameters are the program's own, by the names
``BailingHybrid.named_parameters()`` gives (stacked per kind on a leading
axis); ``W13 = [W1 | W3]`` side by side.

Departures from the published model: the multi-token-prediction module,
the SwiGLU clamp of the published layers 34-41 and the sequence-wise
auxiliary loss are left out; the held shares (heads, experts, vocabulary
rows) are those of the configuration file's deployment; see its
``reduced`` and ``assumed``.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# The step computes in bf16 (AMP O2) and the reference in float32; the
# first loss (about 10.39 at the cell's sizes under the solved bias)
# differs by what the rounding of 8191 positions' logits leaves after
# averaging: 4.9e-7 to 4.3e-5 relative over 10 seeds on the chip, and
# this reference computed in bf16 (``dtype``) by 2.9e-4 to 2.4e-3 over
# 7 (PERF.md section 6).  1.75e-4 is four times the worst of the first.
TOLERANCE_REL = 1.75e-4

F32 = jnp.float32
_MIXER = {"kda": ("k_norm", "k_qkv_w", "k_conv_w", "k_beta_w", "k_alpha_w",
                  "k_dt_bias", "k_a_log", "k_gate_w", "k_onorm_w",
                  "k_out_w"),
          "mla": ("a_norm", "a_q_w", "a_kv_a_w", "a_kv_norm", "a_kv_b_w",
                  "a_q_norm", "a_k_norm", "a_gate_w", "a_o_w")}
_FFN = {"dense": ("d_norm", "d_w13", "d_w2"),
        "moe": ("e_norm", "e_router_w", "e_router_bias", "e_w13", "e_w2",
                "e_shared_w13", "e_shared_w2")}
_NORM = {"kda": "k_norm", "mla": "a_norm", "dense": "d_norm",
         "moe": "e_norm"}


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _sigmoid(x):
    # jax's, whose gradient stays finite where exp(-x) overflows (the KDA
    # gate's argument reaches -100 at initialisation)
    return jax.nn.sigmoid(x)


def _swiglu(x, w13):
    a, b = jnp.split(x @ w13, 2, axis=-1)
    return a * _sigmoid(a) * b


def kinds(i: int, sizes: dict) -> tuple:
    """(mixer, feed-forward part) of layer ``i``."""
    return ("mla" if (i + 1) % sizes["layer_group_size"] == 0 else "kda",
            "dense" if i < sizes["first_k_dense_replace"] else "moe")


def kda_inputs(u, p, sizes):
    """``(q, k, v, log_alpha, beta)`` of a KDA mixer for ``u`` (S,
    hidden): q, k, v and log_alpha (S, heads, head_dim), q scaled; beta
    (S, heads)."""
    heads, dim = sizes["num_attention_heads"], sizes["head_dim"]
    k_conv, s = sizes["short_conv_kernel_size"], u.shape[0]
    qkv = u @ p["k_qkv_w"]
    padded = jnp.concatenate([jnp.zeros((k_conv - 1, qkv.shape[1]),
                                        qkv.dtype), qkv])
    conv = sum(padded[j:j + s] * p["k_conv_w"][j] for j in range(k_conv))
    q, k, v = (t.reshape(s, heads, dim)
               for t in jnp.split(conv * _sigmoid(conv), 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / math.sqrt(dim)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    log_alpha = sizes["kda_lower_bound"] * _sigmoid(
        jnp.exp(p["k_a_log"])[:, None]
        * ((u @ p["k_alpha_w"]).reshape(s, heads, dim)
           + p["k_dt_bias"].reshape(heads, dim)))
    return q, k, v, log_alpha, _sigmoid(u @ p["k_beta_w"])


def kda_recurrence(q, k, v, log_alpha, beta):
    """``o_t = S_t^T q_t`` (S, heads, head_dim) of the delta rule, one
    token after another, the state in the inputs' dtype."""
    heads, dim = q.shape[1:]

    def step(state, now):
        q_t, k_t, v_t, la_t, b_t = now
        state = jnp.exp(la_t)[:, :, None] * state
        error = v_t - jnp.einsum("hk,hkv->hv", k_t, state)
        state = state + b_t[:, None, None] * k_t[:, :, None] \
            * error[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    return jax.lax.scan(step, jnp.zeros((heads, dim, dim), q.dtype),
                        (q, k, v, log_alpha, beta))[1]


def kda(u, p, sizes, block=None):
    """``f(u)`` of a KDA mixer for ``u`` (S, hidden), one sequence."""
    o = kda_recurrence(*kda_inputs(u, p, sizes))
    o = _rms(o, p["k_onorm_w"], sizes["rms_norm_eps"]).reshape(u.shape[0], -1)
    return (o * _sigmoid(u @ p["k_gate_w"])) @ p["k_out_w"]


def _rotate(x, theta):
    """The rotary embedding of ``x`` (S, H, d) as complex products."""
    s, d = x.shape[0], x.shape[-1]
    pairs = jax.lax.complex(x[..., 0::2].astype(F32),
                            x[..., 1::2].astype(F32))
    freq = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    turn = jnp.exp(1j * (jnp.arange(s, dtype=F32)[:, None] * freq)
                   ).astype(jnp.complex64)
    out = pairs * turn[:, None, :]
    return jnp.stack([out.real, out.imag], -1).reshape(x.shape).astype(
        x.dtype)


def mla(u, p, sizes, block=1):
    """``f(u)`` of an MLA mixer for ``u`` (S, hidden), one sequence, the
    scores of ``block`` query rows at a time."""
    heads, eps = sizes["num_attention_heads"], sizes["rms_norm_eps"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, rank, s = sizes["v_head_dim"], sizes["kv_lora_rank"], u.shape[0]
    q = (u @ p["a_q_w"]).reshape(s, heads, nope + rope)
    kv_a = u @ p["a_kv_a_w"]
    c = _rms(kv_a[:, :rank], p["a_kv_norm"], eps)
    kv = (c @ p["a_kv_b_w"]).reshape(s, heads, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.tile(
        kv_a[:, None, rank:], (1, heads, 1))], axis=-1)
    v = kv[..., nope:]
    q, k = _rms(q, p["a_q_norm"], eps), _rms(k, p["a_k_norm"], eps)
    q, k = (jnp.concatenate([t[..., :nope], _rotate(
        t[..., nope:], sizes["rope_theta"])], -1) for t in (q, k))
    if s % block:
        raise ValueError(f"{block} query rows a block do not divide {s}")

    def rows(first):
        q_b = jax.lax.dynamic_slice_in_dim(q, first, block)
        scores = jnp.einsum("ihd,jhd->hij", q_b, k) / math.sqrt(nope + rope)
        seen = (first + jnp.arange(block))[:, None] >= jnp.arange(s)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hij,jhd->ihd", probs, v)

    o = jax.lax.map(rows, jnp.arange(0, s, block)).reshape(s, heads, dv)
    o = o * _sigmoid(u @ p["a_gate_w"])[:, :, None]
    return o.reshape(s, heads * dv) @ p["a_o_w"]


def dense(u, p, sizes, block=None):
    return _swiglu(u, p["d_w13"]) @ p["d_w2"]


def expert_choice(biased, top_k: int, sizes: dict):
    """(tokens, experts) marks, 1 where a token takes an expert: the
    experts cut into ``n_group`` groups of consecutive ids, each scored by
    the sum of its two best ``biased`` scores; the ``topk_group`` best
    groups kept; the ``top_k`` best kept experts taken (DeepSeek-V3,
    ``noaux_tc``)."""
    groups, keep = sizes["n_group"], sizes["topk_group"]
    tokens, experts = biased.shape
    grouped = biased.reshape(tokens, groups, experts // groups)
    best_two = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)
    _, kept = jax.lax.top_k(best_two, keep)
    in_kept = jax.nn.one_hot(kept, groups).sum(1) > 0
    open_ = jnp.repeat(in_kept, experts // groups, axis=-1)
    _, chosen = jax.lax.top_k(jnp.where(open_, biased, -jnp.inf), top_k)
    return jax.nn.one_hot(chosen, experts, dtype=biased.dtype).sum(1)


def _scores(u, p):
    return _sigmoid(u @ p["e_router_w"].T)


def router_scores(x, p, sizes):
    """``s`` (S, router width) of an expert layer for the stream ``x``
    (S, hidden) that enters its feed-forward part: before any bias."""
    return _scores(_rms(x, p["e_norm"], sizes["rms_norm_eps"]), p)


def experts(u, p, sizes, block=None):
    """``f(u)`` of an expert layer for ``u`` (S, hidden): the part the
    held experts give, plus the shared expert."""
    s = _scores(u, p)
    marks = expert_choice(s + p["e_router_bias"],
                          sizes["num_experts_per_tok"], sizes)
    g = sizes["routed_scaling_factor"] * s * marks \
        / jnp.sum(s * marks, -1, keepdims=True)
    y = _swiglu(u, p["e_shared_w13"]) @ p["e_shared_w2"]
    for i in range(p["e_w13"].shape[0]):
        g_i = g[:, sizes["expert_offset"] + i][:, None]
        y = y + g_i * (_swiglu(u, p["e_w13"][i]) @ p["e_w2"][i])
    return y


BLOCKS = {"kda": kda, "mla": mla, "dense": dense, "moe": experts}


def _part(kind, x, p, sizes, block):
    return x + BLOCKS[kind](_rms(x, p[_NORM[kind]], sizes["rms_norm_eps"]),
                            p, sizes, block)


def _cast(params: dict, sizes: dict, dtype=F32) -> dict:
    p = {k: jnp.asarray(v, dtype) for k, v in params.items()}
    if "e_router_bias" not in p:
        p["e_router_bias"] = jnp.zeros(p["e_router_w"].shape[:2], dtype)
    held = p["e_w13"].shape[1]
    if held != sizes["n_routed_experts"]:
        raise ValueError(f"the parameters hold {held} experts, the "
                         f"configuration {sizes['n_routed_experts']}")
    return p


def _walk(p: dict, ids_all, sizes: dict, part, router_bias=None) -> list:
    """The final hidden states (after the last norm), one a sequence of
    ``ids_all``, part by part: the one walk of the stack.

    ``router_bias``, where given, is asked at every expert layer, in
    order, for that layer's ``b``: it gets the scores ``s`` (the batch's
    tokens, router width) of the layer's router under the biases before
    it, and what it returns takes the place of the row ``p`` brings."""
    xs = [p["embed"][ids] for ids in ids_all]
    seen = dict.fromkeys((*_MIXER, *_FFN), 0)
    for i in range(sizes["num_hidden_layers"]):
        for kind in kinds(i, sizes):
            names = (_MIXER.get(kind) or _FFN[kind])
            own = {n: p[n][seen[kind]] for n in names}
            seen[kind] += 1
            if kind == "moe" and router_bias is not None:
                own["e_router_bias"] = jnp.asarray(router_bias(
                    jnp.concatenate([router_scores(x, own, sizes)
                                     for x in xs])), F32)
            xs = [part(kind, x, own, sizes) for x in xs]
    return [_rms(x, p["norm_f"], sizes["rms_norm_eps"]) for x in xs]


def hidden(p: dict, ids, sizes: dict, block: int = 1):
    """The final hidden state (after the last norm) of one sequence."""
    return _walk(p, [ids], sizes, functools.partial(_part, block=block))[0]


def _loss_sum(h, head_w, ids):
    logp = jax.nn.log_softmax(h[:-1] @ head_w, axis=-1)
    return -jnp.take_along_axis(logp, ids[1:, None], axis=-1).sum()


def loss(params: dict, batch: tuple, sizes: dict, block: int,
         router_bias=None, dtype=F32) -> float:
    """Mean next-token cross entropy of ``batch`` = (ids, labels) under
    ``params``, part by part, a sequence at a time; MLA's scores ``block``
    query rows at a time.  ``router_bias``: see ``_walk``.  ``dtype``
    bfloat16 computes every array in it, the parameters, the state and the
    loss's sum too: the control that a limit on the first loss has to
    refuse."""
    ids_all, labels_all = (np.asarray(a) for a in batch)
    if not np.array_equal(ids_all, labels_all):
        raise ValueError("the causal-LM batch uses its ids as labels")
    jitted = {kind: jax.jit(functools.partial(_part, kind, sizes=sizes,
                                              block=block))
              for kind in BLOCKS}
    head = jax.jit(_loss_sum)
    with jax.default_matmul_precision("highest"):
        p = _cast(params, sizes, dtype)
        hs = _walk(p, ids_all, sizes,
                   lambda kind, x, own, _: jitted[kind](x, own), router_bias)
        total = sum(float(head(h, p["head_w"], jnp.asarray(ids)))
                    for h, ids in zip(hs, ids_all))
    return total / (ids_all.shape[0] * (ids_all.shape[1] - 1))


def loss_and_grads(params: dict, batch: tuple, sizes: dict, block: int = 1):
    """(loss, {name: gradient}) for the tests: the same mathematics in
    one differentiable function, every sequence at once."""
    ids_all = jnp.asarray(np.asarray(batch[0]))

    def mean_loss(p):
        p = _cast(p, sizes)
        total = sum(_loss_sum(hidden(p, ids, sizes, block), p["head_w"], ids)
                    for ids in ids_all)
        return total / (ids_all.shape[0] * (ids_all.shape[1] - 1))

    with jax.default_matmul_precision("highest"):
        value, grads = jax.jit(jax.value_and_grad(mean_loss))(
            {k: jnp.asarray(v, F32) for k, v in params.items()})
    return float(value), grads


def attention_shape(sizes: dict, batch: int, seq: int) -> tuple:
    """(B, H, S, d, causal, layers) of the flash calls of one step: MLA's
    causal core at the q.k width, which the program's call also gives v
    (zero-padded from ``v_head_dim``)."""
    count = sum(kinds(i, sizes)[0] == "mla"
                for i in range(sizes["num_hidden_layers"]))
    return (batch, sizes["num_attention_heads"], seq,
            sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"], True, count)


def flops_per_token(sizes: dict, seq: int) -> float:
    """Model FLOPs one token costs in training, forward + backward, no
    recomputation: 6 per multiply-accumulate of every held matmul weight,
    the routed experts at their expected use (``num_experts_per_tok`` x
    held / router width of a token each), the head's slice; KDA's chunk
    work at chunk C per head, as the chunked delta rule does it: the
    within-chunk ``(K o G)(K / G)^T`` and ``(Q o G)(K / G)^T`` and the
    products ``T (K o G)``, ``T V`` and ``A Delta`` at half of C (lower
    triangles), the state's ``W S``, ``(K o G_C/G)^T Delta`` and ``(Q o
    G) S`` in full (the inverse of T and the carry are not counted); MLA's
    causal core at half of S x S, ``q.k`` over ``nope + rope`` and ``p.v``
    over ``v_head_dim``.  Norms, the convolution, activations, gates, the
    router's choice and softmax are not counted."""
    d, layers = sizes["hidden_size"], sizes["num_hidden_layers"]
    heads, dim = sizes["num_attention_heads"], sizes["head_dim"]
    chunk, width = sizes["kda_chunk_size"], heads * dim
    kda_macs = (d * 3 * width + d * width + d * heads + d * width
                + width * d
                + heads * (chunk / 2 * (3 * dim + 2 * dim) + 3 * dim * dim))
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    mla_macs = (d * heads * (nope + rope) + d * (rank + rope)
                + rank * heads * (nope + dv) + d * heads + heads * dv * d)
    mla_core = seq / 2 * heads * (nope + rope + dv)
    use = (sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
           / sizes["router_width"])
    moe_macs = (sizes["router_width"] * d
                + use * 3 * d * sizes["moe_intermediate_size"]
                + 3 * d * sizes["moe_shared_expert_intermediate_size"])
    dense_macs = 3 * d * sizes["intermediate_size"]
    count = {}
    for i in range(layers):
        for kind in kinds(i, sizes):
            count[kind] = count.get(kind, 0) + 1
    macs = (count.get("kda", 0) * kda_macs
            + count.get("mla", 0) * (mla_macs + mla_core)
            + count.get("dense", 0) * dense_macs
            + count.get("moe", 0) * moe_macs + d * sizes["vocab_size"])
    return 6.0 * macs

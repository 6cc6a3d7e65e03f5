"""Plain reference for GPT-2 (Radford et al. 2019): the forward loss in
straightforward ``jax.numpy``, float32, highest matmul precision, no
kernels, no scan, nothing imported from ``paddle_tpu``.

Pre-LN decoder: x = wte[ids] + wpe; per layer x += proj(attn(ln1(x))),
x += out(gelu_new(fc(ln2(x)))); final LN; logits against the tied
embedding; mean next-token cross entropy over the first S-1 positions.
Parameters are the program's own, by the names ``GPT.named_parameters()``
gives (per-layer weights stacked on a leading layer axis).

Departures from the published model: ``gelu_new`` (tanh) is the
published activation, so none in the mathematics; the vocabulary is
padded (see ``assumed`` in the configuration file).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

# The step computes in bf16 (AMP O2: every weight and activation bf16,
# only the softmax-CE in float32) and the reference in float32, so the
# first loss (about ln V + 0.2 = 11.0 at the published widths) differs
# by the bf16 rounding of 8k positions' logits, which mostly averages
# out: 3e-7 to 1.3e-5 relative over 14 seeds on the chip, one chip and
# four (PR 24).  5e-5 is four times the worst of them.  What a fault
# moves the loss by, and what this check cannot see, is in PERF.md
# (sections 3 and 7) and benchmarks/tests/test_harness.py.
TOLERANCE_REL = 5e-5

_LAYER = ("ln1_w", "ln1_b", "qkv_w", "qkv_b", "prj_w", "prj_b", "ln2_w",
          "ln2_b", "fc_w", "fc_b", "out_w", "out_b")


def _ln(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _layer(x, p, n_head, eps):
    b, s, h = x.shape
    d = h // n_head
    qkv = _ln(x, p["ln1_w"], p["ln1_b"], eps) @ p["qkv_w"] + p["qkv_b"]
    q, k, v = (t.reshape(b, s, n_head, d).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    a = (probs @ v).transpose(0, 2, 1, 3).reshape(b, s, h)
    x = x + a @ p["prj_w"] + p["prj_b"]
    ff = _gelu_new(_ln(x, p["ln2_w"], p["ln2_b"], eps) @ p["fc_w"]
                   + p["fc_b"])
    return x + ff @ p["out_w"] + p["out_b"]


def _embed(p, ids):
    return p["wte"][ids] + p["wpe"][: ids.shape[1]][None]


def _head_loss_sum(x, p, ids, eps):
    logits = _ln(x, p["lnf_w"], p["lnf_b"], eps) @ p["wte"].T
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    gold = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return -gold.sum()


def loss(params: dict, batch: tuple, sizes: dict, block: int) -> float:
    """Mean next-token cross entropy of ``batch`` = (ids, labels) under
    ``params``, computed ``block`` sequences at a time."""
    ids_all, labels_all = (np.asarray(a) for a in batch)
    if not np.array_equal(ids_all, labels_all):
        raise ValueError("the causal-LM batch uses its ids as labels")
    n_head, eps = sizes["n_head"], sizes["layer_norm_epsilon"]
    layer = jax.jit(_layer, static_argnums=(2, 3))
    head = jax.jit(_head_loss_sum, static_argnums=(3,))
    embed = jax.jit(_embed)
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    n_layer = p["qkv_w"].shape[0]
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for lo in range(0, ids_all.shape[0], block):
            ids = jnp.asarray(ids_all[lo:lo + block])
            x = embed(p, ids)
            for i in range(n_layer):
                x = layer(x, {k: p[k][i] for k in _LAYER}, n_head, eps)
            total += float(head(x, p, ids, eps))
    return total / (ids_all.shape[0] * (ids_all.shape[1] - 1))


def flops_per_token(sizes: dict, seq: int) -> float:
    """Model FLOPs one token costs in training, forward + backward, no
    recomputation: 6 per multiply-accumulate weight (2 forward, 4
    backward) over the matmul weights of the layers and the tied head,
    plus attention's two S x S matmuls.  Convention for causal
    attention: only the lower triangle is needed, so the S x S products
    count at half (6 * S * n_embd a layer instead of 12).  Embedding
    lookups, LayerNorm, GELU and softmax are not counted."""
    h, f = sizes["n_embd"], sizes["n_inner"]
    per_layer = 3 * h * h + h * h + 2 * h * f          # qkv, proj, fc, out
    weights = sizes["n_layer"] * per_layer + sizes["vocab_size"] * h
    attention = sizes["n_layer"] * 6 * seq * h          # causal: half
    return 6.0 * weights + attention


def attention_shape(sizes: dict, batch: int, seq: int) -> tuple:
    """(B, H, S, d, causal, layers) of the attention calls of one step."""
    return (batch, sizes["n_head"], seq, sizes["n_embd"] // sizes["n_head"],
            True, sizes["n_layer"])

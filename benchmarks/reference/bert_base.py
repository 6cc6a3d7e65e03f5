"""Plain reference for BERT (Devlin et al. 2018) pretraining: the forward
MLM + NSP loss in straightforward ``jax.numpy``, float32, highest matmul
precision, no kernels, no scan, nothing imported from ``paddle_tpu``.

Post-LN encoder: x = LN(wte[ids] + wpe + wtt[0]); per layer
x = LN(x + proj(attn(x))), x = LN(x + out(gelu(fc(x)))); MLM head
gelu(x W + b) -> LN -> tied embedding + bias, cross entropy over the
positions whose label is >= 0; NSP head tanh-pooled first token -> 2
logits, mean cross entropy; the loss is their sum.  Parameters are the
program's own, by the names ``Bert.named_parameters()`` gives.

Departures from the published model, all the program's and followed
here so that the two compute the same function: GELU is the tanh
approximation (published: erf), there is no dropout, token types are
all 0 and there is no padding mask (the traffic has none).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

# As for GPT-2: the step is bf16 O2, the reference float32.  The first
# loss is about ln 30522 + ln 2 = 11.0 and a little more.  The two
# differed by 1e-6 to 5.6e-5 relative over 14 seeds on the chip, seven
# at each of two batch shapes (PR 24); 2e-4 is 3.6 times the worst.
# What this check cannot see: PERF.md sections 3 and 7.
TOLERANCE_REL = 2e-4

_LAYER = ("ln1_w", "ln1_b", "qkv_w", "qkv_b", "prj_w", "prj_b", "ln2_w",
          "ln2_b", "fc_w", "fc_b", "out_w", "out_b")


def _ln(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _layer(x, p, n_head, eps):
    b, s, h = x.shape
    d = h // n_head
    qkv = x @ p["qkv_w"] + p["qkv_b"]
    q, k, v = (t.reshape(b, s, n_head, d).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    probs = jax.nn.softmax(q @ k.transpose(0, 1, 3, 2) / math.sqrt(d),
                           axis=-1)
    a = (probs @ v).transpose(0, 2, 1, 3).reshape(b, s, h)
    x = _ln(x + a @ p["prj_w"] + p["prj_b"], p["ln1_w"], p["ln1_b"], eps)
    ff = _gelu_tanh(x @ p["fc_w"] + p["fc_b"])
    return _ln(x + ff @ p["out_w"] + p["out_b"], p["ln2_w"], p["ln2_b"],
               eps)


def _embed(p, ids, eps):
    x = p["wte"][ids] + p["wpe"][: ids.shape[1]][None] + p["wtt"][0]
    return _ln(x, p["emb_ln_w"], p["emb_ln_b"], eps)


def _heads(x, p, mlm_labels, nsp_labels, eps):
    """(sum of MLM losses, sum of NSP losses) over the block."""
    h = _ln(_gelu_tanh(x @ p["mlm_w"] + p["mlm_b"]), p["mlm_ln_w"],
            p["mlm_ln_b"], eps)
    logp = jax.nn.log_softmax(h @ p["wte"].T + p["mlm_bias"], axis=-1)
    valid = mlm_labels >= 0
    gold = jnp.take_along_axis(
        logp, jnp.where(valid, mlm_labels, 0)[..., None], axis=-1)[..., 0]
    pooled = jnp.tanh(x[:, 0] @ p["pool_w"] + p["pool_b"])
    nlogp = jax.nn.log_softmax(pooled @ p["nsp_w"] + p["nsp_b"], axis=-1)
    ngold = jnp.take_along_axis(nlogp, nsp_labels[:, None], axis=-1)[:, 0]
    return -(gold * valid).sum(), -ngold.sum()


def loss(params: dict, batch: tuple, sizes: dict, block: int) -> float:
    """MLM mean over the labelled positions + NSP mean over sequences,
    of ``batch`` = (ids, mlm_labels, nsp_labels) under ``params``,
    computed ``block`` sequences at a time."""
    ids_all, mlm_all, nsp_all = (np.asarray(a) for a in batch)
    n_head, eps = sizes["num_attention_heads"], sizes["layer_norm_eps"]
    layer = jax.jit(_layer, static_argnums=(2, 3))
    heads = jax.jit(_heads, static_argnums=(4,))
    embed = jax.jit(_embed, static_argnums=(2,))
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    n_layer = p["qkv_w"].shape[0]
    mlm_sum = nsp_sum = 0.0
    with jax.default_matmul_precision("highest"):
        for lo in range(0, ids_all.shape[0], block):
            x = embed(p, jnp.asarray(ids_all[lo:lo + block]), eps)
            for i in range(n_layer):
                x = layer(x, {k: p[k][i] for k in _LAYER}, n_head, eps)
            m, n = heads(x, p, jnp.asarray(mlm_all[lo:lo + block]),
                         jnp.asarray(nsp_all[lo:lo + block]), eps)
            mlm_sum += float(m)
            nsp_sum += float(n)
    return (mlm_sum / max(int((mlm_all >= 0).sum()), 1)
            + nsp_sum / ids_all.shape[0])


def flops_per_token(sizes: dict, seq: int) -> float:
    """Model FLOPs one token costs in training, forward + backward, no
    recomputation (the program's per-layer remat is not counted): 6 per
    multiply-accumulate weight over the layers' matmuls, the MLM
    transform and the tied MLM head (computed at every position, as the
    program does), plus attention's two full S x S matmuls (12 * S *
    hidden a layer: bidirectional, nothing halved).  The pooler and NSP
    head (one token a sequence), embeddings, LayerNorm, GELU and softmax
    are not counted."""
    h, f = sizes["hidden_size"], sizes["intermediate_size"]
    per_layer = 3 * h * h + h * h + 2 * h * f
    weights = (sizes["num_hidden_layers"] * per_layer + h * h
               + sizes["vocab_size"] * h)
    attention = sizes["num_hidden_layers"] * 12 * seq * h
    return 6.0 * weights + attention


def attention_shape(sizes: dict, batch: int, seq: int) -> tuple:
    """(B, H, S, d, causal, layers) of the attention calls of one step:
    full attention, no mask (the inputs carry no padding)."""
    heads = sizes["num_attention_heads"]
    return (batch, heads, seq, sizes["hidden_size"] // heads, False,
            sizes["num_hidden_layers"])

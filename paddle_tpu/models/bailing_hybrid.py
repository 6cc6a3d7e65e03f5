"""BailingHybrid: KDA linear attention beside latent attention, SwiGLU experts.

The reference framework stacks one kind of transformer layer; this model
(``bailing_hybrid``, Ling-3.0-flash's ``config.json``) makes two typed
choices a layer.  Every layer is a mixer and a feed-forward part, each
pre-norm, ``x <- x + f(RMSNorm(x))`` (eps ``rms_norm_eps``, weight only),
no bias anywhere:

- the mixer: **MLA** (latent attention) on the last layer of every
  ``layer_group_size``, i.e. where ``(i + 1) % layer_group_size == 0``;
  **KDA** (``nn/functional/kda.py``) on every other;
- the feed-forward part: a dense SwiGLU MLP on the first
  ``first_k_dense_replace`` layers, SwiGLU experts (``nn/functional/
  moe.py`` ``swiglu_moe``) on the rest;

then a final RMSNorm and ``logits = h W_head`` (untied head).

- KDA (``heads`` heads of ``head_dim``): ``kda.kda_mixer``, the
  lower-bounded gate ``kda_lower_bound``, chunks of ``kda_chunk_size``.
- MLA (``q_lora_rank`` none): ``q = u W_q``, per head ``[q_nope | q_rope]``;
  ``[c | k_rope] = u W_kv_a``, ``c = RMSNorm(c)``, ``[k_nope | v] = c
  W_kv_b`` per head, ``k_rope`` one vector shared by every head;
  ``q_h = RMSNorm([q_nope | q_rope])`` and ``k_h = RMSNorm([k_nope |
  k_rope])`` over the q.k width (QK-norm), then the interleaved rotary
  embedding (``rope_theta``) on the rope channels; ``o_h = softmax(q_h
  k_h^T / sqrt(q.k width), causal) v_h * sigmoid(u W_gate)_h``; ``out =
  concat(o_h) W_o``.  The core runs on the flash kernels, which take one
  width for q, k and v: v (128) is zero-padded to the q.k width (192)
  inside the call and the output cut back to v's width.  Exact: v's zero
  columns give output columns that are cut off.  The padding costs half
  as much again of the p.v products.
- Experts: ``s = sigmoid(float32(u) W_r^T)`` over all ``num_experts``;
  ``sel`` = the top ``num_experts_per_tok`` of ``s + b_corr`` (a buffer,
  zero, no gradient) among the ``topk_group`` best of ``n_group`` groups;
  ``g = routed_scaling_factor * s[sel] / sum s[sel]``; ``y = sum_{sel
  held} g_e (silu(u W1_e) * u W3_e) W2_e + SwiGLU_shared(u)``.  The layer
  holds ``experts_held`` experts from ``expert_offset`` on.
- Loss: mean next-token cross entropy over the first S-1 positions,
  float32, over the vocabulary rows held.

Heads, experts and vocabulary rows may be one chip's share of a stated
deployment (the ``model-configs`` guide, section 4): the counts given to
``BailingHybridConfig`` are what is held here.

Parameters are stacked per kind on a leading axis: ``k_*`` (KDA), ``a_*``
(MLA), ``d_*`` (dense MLP), ``e_*`` (experts), the i-th layer of a kind
reading row i.  Where ``remat``, each layer is its own ``jax.checkpoint``
and keeps nothing but its input, except an expert layer's router, which
keeps ``moe.ROUTER_SAVED`` as in ``models/nemotron_h.py``.

Initialiser: normal(0, ``initializer_range``) for matrices, the output
projections (``W_out``, ``W_o``, every ``W2``) divided by sqrt(2 x layers);
norm weights 1; ``A_log`` = log U(1, 16); ``dt_bias`` the inverse softplus
of a log-uniform draw in [0.001, 0.1]; the convolution's weight
U(-1/sqrt(k), 1/sqrt(k)).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import Parameter, Tensor, apply1
from paddle_tpu.framework import monitor
from paddle_tpu.nn.functional import kda as _kda
from paddle_tpu.nn.functional import moe as _moe
from paddle_tpu.nn.functional import rotary as _rotary
from paddle_tpu.nn.functional import ssm as _ssm
from paddle_tpu.nn.layer.layers import Layer
from paddle_tpu.profiler import CountedEvent

__all__ = ["BailingHybridConfig", "BailingHybrid", "bailing_hybrid_loss",
           "bailing_hybrid_tiny"]

_MIXER = {"kda": ("k_norm", "k_qkv_w", "k_conv_w", "k_beta_w", "k_alpha_w",
                  "k_dt_bias", "k_a_log", "k_gate_w", "k_onorm_w",
                  "k_out_w"),
          "mla": ("a_norm", "a_q_w", "a_kv_a_w", "a_kv_norm", "a_kv_b_w",
                  "a_q_norm", "a_k_norm", "a_gate_w", "a_o_w")}
_FFN = {"dense": ("d_norm", "d_w13", "d_w2"),
        "moe": ("e_norm", "e_router_w", "e_w13", "e_w2", "e_shared_w13",
                "e_shared_w2")}


class BailingHybridConfig:
    """The published sizes by default; every count is what is held here."""

    def __init__(self, vocab_size=157184, hidden_size=2560,
                 num_hidden_layers=42, layer_group_size=6,
                 first_k_dense_replace=2, num_attention_heads=32,
                 head_dim=128, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, rope_theta=6e6,
                 intermediate_size=6144, moe_intermediate_size=768,
                 moe_shared_expert_intermediate_size=768, num_experts=512,
                 experts_held=None, expert_offset=0, num_experts_per_tok=8,
                 n_group=8, topk_group=4, routed_scaling_factor=2.5,
                 short_conv_kernel_size=4, kda_lower_bound=-5.0,
                 kda_chunk_size=64, rms_norm_eps=1e-6,
                 initializer_range=0.02, remat: bool = True,
                 use_flash_attention: bool = True, seed: int = 0):
        if num_experts % n_group or not 0 < topk_group <= n_group:
            raise ValueError("experts divide into n_group groups, of which "
                             "topk_group are kept")
        if kda_lower_bound * _kda.SUB_CHUNK < -80:
            raise ValueError(f"a KDA gate bounded at {kda_lower_bound} "
                             f"leaves float32's range within a sub-chunk "
                             f"of {_kda.SUB_CHUNK}")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.layer_group_size = layer_group_size
        self.first_k_dense_replace = first_k_dense_replace
        self.num_attention_heads = num_attention_heads
        self.head_dim = head_dim
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_theta = rope_theta
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_shared_expert_intermediate_size = \
            moe_shared_expert_intermediate_size
        self.num_experts = num_experts
        self.experts_held = num_experts if experts_held is None \
            else experts_held
        self.expert_offset = expert_offset
        if not 0 <= expert_offset <= num_experts - self.experts_held:
            raise ValueError("the held experts lie among the routed ones")
        self.num_experts_per_tok = num_experts_per_tok
        self.n_group = n_group
        self.topk_group = topk_group
        self.routed_scaling_factor = routed_scaling_factor
        self.short_conv_kernel_size = short_conv_kernel_size
        self.kda_lower_bound = kda_lower_bound
        self.kda_chunk_size = kda_chunk_size
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.remat = remat
        self.use_flash_attention = use_flash_attention
        self.seed = seed

    def kinds(self, i: int):
        """(mixer, feed-forward part) of layer ``i``."""
        return ("mla" if (i + 1) % self.layer_group_size == 0 else "kda",
                "dense" if i < self.first_k_dense_replace else "moe")


def bailing_hybrid_tiny(**kw):
    """Four layers, MLA last, one dense, at toy widths, for the CPU."""
    tiny = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
                layer_group_size=4, first_k_dense_replace=1,
                num_attention_heads=2, head_dim=16, kv_lora_rank=32,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                intermediate_size=96, moe_intermediate_size=48,
                moe_shared_expert_intermediate_size=48, num_experts=16,
                experts_held=4, num_experts_per_tok=4, n_group=4,
                topk_group=2, kda_chunk_size=32)
    tiny.update(kw)
    return BailingHybridConfig(**tiny)


class BailingHybrid(Layer):
    def __init__(self, config: BailingHybridConfig):
        super().__init__()
        self.config = config
        with CountedEvent("model.init"):
            self._init_parameters(config)

    def _init_parameters(self, c: BailingHybridConfig):
        """Every parameter and the router's bias, drawn on the host from
        ``c.seed``."""
        rng = np.random.default_rng(c.seed)
        std = c.initializer_range
        out_std = std / math.sqrt(2 * c.num_hidden_layers)
        kinds = [c.kinds(i) for i in range(c.num_hidden_layers)]
        count = {kind: sum(kind in pair for pair in kinds)
                 for kind in (*_MIXER, *_FFN)}
        d, v = c.hidden_size, c.vocab_size

        def normal(shape, scale=std):
            return rng.standard_normal(shape, np.float32) * np.float32(scale)

        def param(name, value):
            self.add_parameter(name, Parameter(
                np.asarray(value, np.float32), name=f"bailing.{name}"))

        param("embed", normal((v, d)))
        n, heads, hd = count["kda"], c.num_attention_heads, c.head_dim
        width = heads * hd
        param("k_norm", np.ones((n, d)))
        param("k_qkv_w", normal((n, d, 3 * width)))
        bound = 1.0 / math.sqrt(c.short_conv_kernel_size)
        param("k_conv_w", rng.uniform(-bound, bound, (
            n, c.short_conv_kernel_size, 3 * width)))
        param("k_beta_w", normal((n, d, heads)))
        param("k_alpha_w", normal((n, d, width)))
        dt = np.exp(rng.uniform(math.log(1e-3), math.log(0.1), (n, width)))
        param("k_dt_bias", dt + np.log(-np.expm1(-dt)))
        param("k_a_log", np.log(rng.uniform(1.0, 16.0, (n, heads))))
        param("k_gate_w", normal((n, d, width)))
        param("k_onorm_w", np.ones((n, hd)))
        param("k_out_w", normal((n, width, d), out_std))
        n = count["mla"]
        qk = c.qk_nope_head_dim + c.qk_rope_head_dim
        param("a_norm", np.ones((n, d)))
        param("a_q_w", normal((n, d, heads * qk)))
        param("a_kv_a_w", normal((n, d, c.kv_lora_rank
                                  + c.qk_rope_head_dim)))
        param("a_kv_norm", np.ones((n, c.kv_lora_rank)))
        param("a_kv_b_w", normal((n, c.kv_lora_rank, heads * (
            c.qk_nope_head_dim + c.v_head_dim))))
        param("a_q_norm", np.ones((n, qk)))
        param("a_k_norm", np.ones((n, qk)))
        param("a_gate_w", normal((n, d, heads)))
        param("a_o_w", normal((n, heads * c.v_head_dim, d), out_std))
        n, mid = count["dense"], c.intermediate_size
        param("d_norm", np.ones((n, d)))
        param("d_w13", normal((n, d, 2 * mid)))
        param("d_w2", normal((n, mid, d), out_std))
        n, held = count["moe"], c.experts_held
        mid, wide = c.moe_intermediate_size, \
            c.moe_shared_expert_intermediate_size
        param("e_norm", np.ones((n, d)))
        param("e_router_w", normal((n, c.num_experts, d)))
        param("e_w13", normal((n, held, d, 2 * mid)))
        param("e_w2", normal((n, held, mid, d), out_std))
        param("e_shared_w13", normal((n, d, 2 * wide)))
        param("e_shared_w2", normal((n, wide, d), out_std))
        param("norm_f", np.ones((d,)))
        param("head_w", normal((d, v)))
        # the router's correction bias: it only chooses, is no parameter
        # and gets no gradient
        self.register_buffer("e_router_bias", Tensor(
            np.zeros((n, c.num_experts), np.float32)))

    def forward(self, input_ids, features_only: bool = False) -> Tensor:
        """input_ids (B, S) int -> logits (B, S, vocabulary rows held), or
        the final hidden state before the head (after the last norm)."""
        names = tuple(self._parameters)
        fn = partial(_forward, self.config, names, features_only)
        return apply1(fn, *self._parameters.values(),
                      self._buffers["e_router_bias"],
                      input_ids, name="bailing_hybrid_forward")


def _kda_block(c: BailingHybridConfig, x, p):
    with jax.named_scope("kda"):
        with jax.named_scope("ln"):
            u = _ssm.rms_norm_array(x, p["k_norm"], c.rms_norm_eps)
        return x + _kda.kda_mixer(
            u, p["k_qkv_w"], p["k_conv_w"], p["k_beta_w"], p["k_alpha_w"],
            p["k_dt_bias"], p["k_a_log"], p["k_gate_w"], p["k_onorm_w"],
            p["k_out_w"], heads=c.num_attention_heads, head_dim=c.head_dim,
            chunk=c.kda_chunk_size, lower_bound=c.kda_lower_bound,
            eps=c.rms_norm_eps)


def _mla_core(c: BailingHybridConfig, q, k, v):
    """Causal ``softmax(q k^T / sqrt(q.k width)) v``, q and k (B, S, H,
    q.k width), v (B, S, H, v width): on the flash kernels where they take
    the padded call (the module's text), else XLA's attention."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    if c.use_flash_attention:
        from paddle_tpu.ops.pallas import flash_attention as _fa
        from paddle_tpu.parallel.mesh import get_mesh, per_device
        width = max(q.shape[-1], v.shape[-1])
        qp, kp, vp = (jnp.pad(t, ((0, 0),) * 3 + ((0, width - t.shape[-1]),))
                      for t in (q, k, v))
        if _fa.supported(tuple(qp.shape), tuple(kp.shape), True, causal=True):
            kernel = partial(_fa.flash_attention, causal=True, scale=scale)
            return per_device(kernel, get_mesh(), (("dp", "sharding"), None,
                                                   "mp", None))(
                qp, kp, vp)[..., :v.shape[-1]]
    from paddle_tpu.nn.functional.attention import _xla_attention
    return _xla_attention(q, k, v, None, scale, True)


def _mla_block(c: BailingHybridConfig, x, p):
    b, s = x.shape[:2]
    heads, eps = c.num_attention_heads, c.rms_norm_eps
    nope, rope = c.qk_nope_head_dim, c.qk_rope_head_dim
    with jax.named_scope("attn"):
        with jax.named_scope("ln"):
            u = _ssm.rms_norm_array(x, p["a_norm"], eps)
        with jax.named_scope("qkv"):
            q = (u @ p["a_q_w"]).reshape(b, s, heads, nope + rope)
            latent, k_rope = jnp.split(u @ p["a_kv_a_w"], [c.kv_lora_rank],
                                       axis=-1)
            latent = _ssm.rms_norm_array(latent, p["a_kv_norm"], eps)
            k_nope, v = jnp.split((latent @ p["a_kv_b_w"]).reshape(
                b, s, heads, nope + c.v_head_dim), [nope], axis=-1)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_rope[:, :, None], (b, s, heads, rope))], axis=-1)
            q, k = (_ssm.rms_norm_array(t, p[w], eps)
                    for t, w in ((q, "a_q_norm"), (k, "a_k_norm")))
            q, k = (jnp.concatenate([t[..., :nope], _rotary.rotary_interleaved(
                t[..., nope:], c.rope_theta)], axis=-1) for t in (q, k))
        with jax.named_scope("core"):
            o = _mla_core(c, q, k, v)
        with jax.named_scope("out"):
            o = o * jax.nn.sigmoid(u @ p["a_gate_w"])[..., None]
            return x + o.reshape(b, s, -1) @ p["a_o_w"]


def _dense_block(c: BailingHybridConfig, x, p):
    with jax.named_scope("mlp"):
        with jax.named_scope("ln"):
            u = _ssm.rms_norm_array(x, p["d_norm"], c.rms_norm_eps)
        with jax.named_scope("up"):
            h = _moe.swiglu(u, p["d_w13"])
        with jax.named_scope("down"):
            return x + h @ p["d_w2"]


def _moe_block(c: BailingHybridConfig, x, p):
    with jax.named_scope("mlp"):
        with jax.named_scope("ln"):
            u = _ssm.rms_norm_array(x, p["e_norm"], c.rms_norm_eps)
        return x + _moe.swiglu_moe(
            u, p["e_router_w"], p["e_router_bias"], p["e_w13"], p["e_w2"],
            p["e_shared_w13"], p["e_shared_w2"],
            top_k=c.num_experts_per_tok, scale=c.routed_scaling_factor,
            expert_offset=c.expert_offset, n_group=c.n_group,
            topk_group=c.topk_group)


_BLOCK = {"kda": _kda_block, "mla": _mla_block, "dense": _dense_block,
          "moe": _moe_block}
_KEEP_ROUTER = jax.checkpoint_policies.save_only_these_names(
    *_moe.ROUTER_SAVED)


def _layers(c: BailingHybridConfig, p: dict):
    """(mixer, feed-forward part, the layer's own parameters) down the
    stack: the i-th layer of a kind reads row i of that kind's stacks."""
    seen = dict.fromkeys((*_MIXER, *_FFN), 0)
    for i in range(c.num_hidden_layers):
        kinds, own = c.kinds(i), {}
        for kind, names in zip(kinds, (_MIXER[kinds[0]], _FFN[kinds[1]])):
            own.update({n: p[n][seen[kind]] for n in names})
            if kind == "moe":
                own["e_router_bias"] = p["e_router_bias"][seen[kind]]
            seen[kind] += 1
        yield kinds, own


def _layer(c: BailingHybridConfig, kinds, x, own):
    mixer, ffn = kinds
    return _BLOCK[ffn](c, _BLOCK[mixer](c, x, own), own)


def _trunk(c: BailingHybridConfig, p: dict, ids):
    with jax.named_scope("embed"):
        x = p["embed"][ids]
    for kinds, own in _layers(c, p):
        layer = partial(_layer, c, kinds)
        if c.remat:
            # only an expert layer holds the names; the rest keep nothing
            layer = jax.checkpoint(layer, policy=_KEEP_ROUTER)
            if kinds[1] == "moe":
                monitor.stat_add("moe_router_kept_blocks_total", 1)
        x = layer(x, own)
    return x


def _forward(c: BailingHybridConfig, names, features_only, *arrays):
    p = dict(zip(names, arrays[:-2]))
    p["e_router_bias"], ids = arrays[-2:]
    x = _trunk(c, p, ids)
    with jax.named_scope("head_loss"):
        h = _ssm.rms_norm_array(x, p["norm_f"], c.rms_norm_eps)
        return h if features_only else h @ p["head_w"]


def bailing_hybrid_loss(model, input_ids, labels):
    """Mean next-token cross entropy over the first S-1 positions (float32
    softmax) over the vocabulary rows held; labels are the input tokens,
    shifted here."""
    logits = model(input_ids)

    @jax.named_scope("head_loss")
    def ce(logits, ids):
        lg = logits[:, :-1].astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    return apply1(ce, logits, labels, name="bailing_hybrid_loss")

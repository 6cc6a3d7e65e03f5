"""NemotronH: a decoder whose stack is a pattern string of typed blocks.

The reference framework stacks one kind of transformer layer; this model
(``nemotron_h``, NVIDIA-Nemotron-3-Super-120B-A12B's ``config.json``)
stacks three, one letter each in ``hybrid_override_pattern``:

    ``M``  a Mamba-2 mixer          (``nn/functional/ssm.py``)
    ``*``  grouped-query attention  (``models/gpt.py``'s ``_attention``)
    ``E``  latent experts           (``nn/functional/moe.py``)

Each layer is **one** mixer or **one** feed-forward part:

    u = RMSNorm(x)  (eps 1e-5, weight only);   x <- x + f(u)

then a final RMSNorm and ``logits = h W_head``: untied embedding and
head, no positional embedding, no bias anywhere except the convolution's.

- ``M`` (H heads of P, G groups, N states, conv k, chunked scan):
  ``[z | xBC | dt] = u W_in``; ``xBC = silu(conv(xBC) + b)``; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; ``h_t = exp(dt_t A)
  h_{t-1} + dt_t B_t (x) x_t``, ``y_t = C_t . h_t + D x_t``; ``out =
  (GroupRMSNorm_G(y * silu(z)) * w) W_out``.
- ``*``: ``q = u W_q`` (heads of ``head_dim``), ``k, v = u W_k, u W_v``
  (KV heads, each repeated to the query heads it serves), causal
  ``softmax(q k^T / sqrt(head_dim)) v``, ``W_o``.
- ``E``: ``s = sigmoid(float32(u) W_r^T)`` over all ``n_routed_experts``;
  ``sel = top_k(s + b_corr)`` (a buffer, zero, no gradient); ``g = scale
  * s[sel] / (sum s[sel] + 1e-20)``; ``z = u W_down``; ``y = (sum_{sel}
  g_e W2_e relu(W1_e z)^2) W_up + W2_s relu(W1_s u)^2``.  The layer holds
  ``experts_held`` experts from ``expert_offset`` on and sums over ``sel``
  within them only; ``g`` is normalised over all ``top_k`` as published.
- Loss: mean next-token cross entropy over the first S-1 positions,
  float32, over the vocabulary rows held.

Heads, experts and vocabulary rows may be one chip's share of a stated
deployment (the ``model-configs`` guide, section 4): the counts given to
``NemotronHConfig`` are what is held here.

Parameters are stacked per block type on a leading axis (``m_*``,
``a_*``, ``e_*``), the i-th ``M`` of the pattern reading row i of every
``m_*``.  Where ``remat``, each layer is its own ``jax.checkpoint`` and
keeps nothing but its input, except an ``E`` layer's router: its choice
``sel`` and the logits at ``sel`` (``moe.ROUTER_SAVED``, 2 x tokens x
``top_k`` x 4 bytes a layer) stay, so the backward neither scores all
``n_routed_experts`` nor takes the top-k a second time, and its row plan
is built from the choice the forward made, not from one made again.

Initialiser: normal(0, ``initializer_range``) for matrices, ``W_out``,
``W_o``, ``W2`` (routed and shared) and ``W_up`` divided by sqrt(number
of layers) (``rescale_prenorm_residual``); norm weights 1; ``D`` = 1;
``A_log`` = log U(1, 16); ``dt_bias`` the inverse softplus of a
log-uniform draw in [``time_step_min``, ``time_step_max``] floored at
``time_step_floor``; the convolution's weight U(-1/sqrt(k), 1/sqrt(k)),
its bias zero.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import Parameter, Tensor, apply1
from paddle_tpu.framework import monitor
from paddle_tpu.models.gpt import _attention
from paddle_tpu.nn.functional import moe as _moe
from paddle_tpu.nn.functional import ssm as _ssm
from paddle_tpu.nn.layer.layers import Layer
from paddle_tpu.profiler import CountedEvent

__all__ = ["NemotronHConfig", "NemotronH", "nemotron_h_loss",
           "nemotron_h_tiny", "routing_load"]

# what the softmax-CE is computed in; the builder's check on the chip sets
# bfloat16 here to show that the first-loss comparison sees it (PERF.md
# section 6, PR 27).  Not an option.
_CE_DTYPE = jnp.float32

monitor.describe("moe_router_kept_blocks_total",
                 "expert blocks wrapped in a jax.checkpoint that keeps the "
                 "router's choice and picked logits (moe.ROUTER_SAVED) for "
                 "the backward, added once per E block when a stack is "
                 "traced under remat (a trace-time count); a stack traced "
                 "without remat adds none")

PUBLISHED_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")

_M = ("m_norm", "m_in_w", "m_conv_w", "m_conv_b", "m_dt_bias", "m_a_log",
      "m_d", "m_gnorm_w", "m_out_w")
_A = ("a_norm", "a_q_w", "a_k_w", "a_v_w", "a_o_w")
_E = ("e_norm", "e_router_w", "e_down_w", "e_w1", "e_w2", "e_up_w",
      "e_shared_w1", "e_shared_w2")
_OF_KIND = {"M": _M, "*": _A, "E": _E}


class NemotronHConfig:
    """The published sizes by default; every count is what is held here."""

    def __init__(self, vocab_size=131072, hidden_size=4096,
                 hybrid_override_pattern=PUBLISHED_PATTERN,
                 mamba_num_heads=128, mamba_head_dim=64, n_groups=8,
                 ssm_state_size=128, conv_kernel=4, chunk_size=128,
                 num_attention_heads=32, num_key_value_heads=2,
                 head_dim=128, n_routed_experts=512, experts_held=None,
                 expert_offset=0, num_experts_per_tok=22,
                 moe_latent_size=1024, moe_intermediate_size=2688,
                 moe_shared_expert_intermediate_size=5376,
                 routed_scaling_factor=5.0, norm_eps=1e-5,
                 initializer_range=0.02, time_step_min=0.001,
                 time_step_max=0.1, time_step_floor=1e-4,
                 remat: bool = True, use_flash_attention: bool = True,
                 seed: int = 0):
        unknown = set(hybrid_override_pattern) - set(_OF_KIND)
        if unknown or not hybrid_override_pattern:
            raise ValueError(f"hybrid_override_pattern is made of "
                             f"{sorted(_OF_KIND)}, not {sorted(unknown)}")
        if mamba_num_heads % n_groups or \
                num_attention_heads % num_key_value_heads:
            raise ValueError("heads divide into their groups / KV heads")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.hybrid_override_pattern = hybrid_override_pattern
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.n_groups = n_groups
        self.ssm_state_size = ssm_state_size
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.n_routed_experts = n_routed_experts
        self.experts_held = (n_routed_experts if experts_held is None
                             else experts_held)
        self.expert_offset = expert_offset
        if not 0 <= expert_offset <= n_routed_experts - self.experts_held:
            raise ValueError("the held experts lie among the routed ones")
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_latent_size = moe_latent_size
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_shared_expert_intermediate_size = \
            moe_shared_expert_intermediate_size
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_eps = norm_eps
        self.initializer_range = initializer_range
        self.time_step_min = time_step_min
        self.time_step_max = time_step_max
        self.time_step_floor = time_step_floor
        self.remat = remat
        self.use_flash_attention = use_flash_attention
        self.seed = seed

    @property
    def num_layers(self):
        return len(self.hybrid_override_pattern)


def nemotron_h_tiny(**kw):
    """Two layers of each kind at toy widths, for the CPU tests."""
    tiny = dict(vocab_size=256, hidden_size=64,
                hybrid_override_pattern="ME*ME*", mamba_num_heads=4,
                mamba_head_dim=8, n_groups=2, ssm_state_size=16,
                chunk_size=16, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, n_routed_experts=8,
                experts_held=4, num_experts_per_tok=2, moe_latent_size=32,
                moe_intermediate_size=48,
                moe_shared_expert_intermediate_size=96)
    tiny.update(kw)
    return NemotronHConfig(**tiny)


class NemotronH(Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        with CountedEvent("model.init"):
            self._init_parameters(config)

    def _init_parameters(self, c: NemotronHConfig):
        """Every parameter and the router's bias, drawn on the host from
        ``c.seed``."""
        rng = np.random.default_rng(c.seed)
        std = c.initializer_range
        out_std = std / math.sqrt(c.num_layers)
        count = {kind: c.hybrid_override_pattern.count(kind)
                 for kind in _OF_KIND}
        d, v = c.hidden_size, c.vocab_size

        def normal(shape, scale=std):
            return rng.standard_normal(shape, np.float32) * np.float32(scale)

        def param(name, value):
            self.add_parameter(name, Parameter(
                np.asarray(value, np.float32), name=f"nemotron_h.{name}"))

        param("embed", normal((v, d)))
        n, heads = count["M"], c.mamba_num_heads
        inner = heads * c.mamba_head_dim
        conv = inner + 2 * c.n_groups * c.ssm_state_size
        param("m_norm", np.ones((n, d)))
        param("m_in_w", normal((n, d, inner + conv + heads)))
        bound = 1.0 / math.sqrt(c.conv_kernel)
        param("m_conv_w", rng.uniform(-bound, bound,
                                      (n, c.conv_kernel, conv)))
        param("m_conv_b", np.zeros((n, conv)))
        dt = np.exp(rng.uniform(math.log(c.time_step_min),
                                math.log(c.time_step_max), (n, heads)))
        dt = np.maximum(dt, c.time_step_floor)
        param("m_dt_bias", dt + np.log(-np.expm1(-dt)))
        param("m_a_log", np.log(rng.uniform(1.0, 16.0, (n, heads))))
        param("m_d", np.ones((n, heads)))
        param("m_gnorm_w", np.ones((n, inner)))
        param("m_out_w", normal((n, inner, d), out_std))
        n = count["*"]
        q, kv = (c.num_attention_heads * c.head_dim,
                 c.num_key_value_heads * c.head_dim)
        param("a_norm", np.ones((n, d)))
        param("a_q_w", normal((n, d, q)))
        param("a_k_w", normal((n, d, kv)))
        param("a_v_w", normal((n, d, kv)))
        param("a_o_w", normal((n, q, d), out_std))
        n, held = count["E"], c.experts_held
        lat, mid = c.moe_latent_size, c.moe_intermediate_size
        wide = c.moe_shared_expert_intermediate_size
        param("e_norm", np.ones((n, d)))
        param("e_router_w", normal((n, c.n_routed_experts, d)))
        param("e_down_w", normal((n, d, lat)))
        param("e_w1", normal((n, held, lat, mid)))
        param("e_w2", normal((n, held, mid, lat), out_std))
        param("e_up_w", normal((n, lat, d), out_std))
        param("e_shared_w1", normal((n, d, wide)))
        param("e_shared_w2", normal((n, wide, d), out_std))
        param("norm_f", np.ones((d,)))
        param("head_w", normal((d, v)))
        # the router's correction bias: it only chooses, is no parameter
        # and gets no gradient
        self.register_buffer("e_router_bias", Tensor(
            np.zeros((n, c.n_routed_experts), np.float32)))

    def forward(self, input_ids, features_only: bool = False) -> Tensor:
        """input_ids (B, S) int -> logits (B, S, vocabulary rows held), or
        the final hidden state before the head (after the last norm)."""
        names = tuple(self._parameters)
        fn = partial(_forward, self.config, names, features_only)
        return apply1(fn, *self._parameters.values(),
                      self._buffers["e_router_bias"],
                      input_ids, name="nemotron_h_forward")


def _m_block(c: NemotronHConfig, x, p):
    with jax.named_scope("ssm"):
        with jax.named_scope("ln"):
            u = _ssm.rms_norm_array(x, p["m_norm"], c.norm_eps)
        return x + _ssm.mamba2_mixer(
            u, p["m_in_w"], p["m_conv_w"], p["m_conv_b"], p["m_dt_bias"],
            p["m_a_log"], p["m_d"], p["m_gnorm_w"], p["m_out_w"],
            heads=c.mamba_num_heads, head_dim=c.mamba_head_dim,
            groups=c.n_groups, state=c.ssm_state_size, chunk=c.chunk_size,
            eps=c.norm_eps)


def _a_block(c: NemotronHConfig, x, p):
    b, s = x.shape[:2]
    heads, kv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    with jax.named_scope("attn"):
        with jax.named_scope("ln"):
            u = _ssm.rms_norm_array(x, p["a_norm"], c.norm_eps)
        with jax.named_scope("qkv"):
            q = (u @ p["a_q_w"]).reshape(b, s, heads, hd)
            # the flash kernel takes equal head counts: each KV head is
            # repeated to the query heads it serves
            k, v = (jnp.repeat((u @ p[w]).reshape(b, s, kv, hd),
                               heads // kv, axis=2)
                    for w in ("a_k_w", "a_v_w"))
        with jax.named_scope("core"):
            a = _attention(c, q, k, v).reshape(b, s, heads * hd)
        with jax.named_scope("out"):
            return x + a @ p["a_o_w"]


def _e_block(c: NemotronHConfig, x, p):
    with jax.named_scope("mlp"):
        with jax.named_scope("ln"):
            u = _ssm.rms_norm_array(x, p["e_norm"], c.norm_eps)
        return x + _moe.latent_moe(
            u, p["e_router_w"], p["e_router_bias"], p["e_down_w"],
            p["e_w1"], p["e_w2"], p["e_up_w"], p["e_shared_w1"],
            p["e_shared_w2"], top_k=c.num_experts_per_tok,
            scale=c.routed_scaling_factor, expert_offset=c.expert_offset)


_BLOCK = {"M": _m_block, "*": _a_block, "E": _e_block}
_KEEP_ROUTER = jax.checkpoint_policies.save_only_these_names(
    *_moe.ROUTER_SAVED)


def _layers(c: NemotronHConfig, p: dict):
    """(kind, the layer's own parameters) down the pattern: the i-th
    block of a kind reads row i of that kind's stacked parameters."""
    seen = dict.fromkeys(_OF_KIND, 0)
    for kind in c.hybrid_override_pattern:
        i = seen[kind]
        seen[kind] += 1
        own = {n: p[n][i] for n in _OF_KIND[kind]}
        if kind == "E":
            own["e_router_bias"] = p["e_router_bias"][i]
        yield kind, own


def _trunk(c: NemotronHConfig, p: dict, ids):
    with jax.named_scope("embed"):
        x = p["embed"][ids]
    for kind, own in _layers(c, p):
        block = partial(_BLOCK[kind], c)
        if c.remat:
            # only an E block holds the names; M and * keep nothing
            block = jax.checkpoint(block, policy=_KEEP_ROUTER)
            if kind == "E":
                monitor.stat_add("moe_router_kept_blocks_total", 1)
        x = block(x, own)
    return x


def _forward(c: NemotronHConfig, names, features_only, *arrays):
    p = dict(zip(names, arrays[:-2]))
    p["e_router_bias"], ids = arrays[-2:]
    x = _trunk(c, p, ids)
    with jax.named_scope("head_loss"):
        h = _ssm.rms_norm_array(x, p["norm_f"], c.norm_eps)
        return h if features_only else h @ p["head_w"]


def nemotron_h_loss(model, input_ids, labels):
    """Mean next-token cross entropy over the first S-1 positions (float32
    softmax) over the vocabulary rows held; labels are the input tokens,
    shifted here."""
    logits = model(input_ids)

    @jax.named_scope("head_loss")
    def ce(logits, ids):
        lg = logits[:, :-1].astype(_CE_DTYPE)
        logz = jax.scipy.special.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    return apply1(ce, logits, labels, name="nemotron_h_loss")


def routing_load(model, input_ids) -> np.ndarray:
    """(expert layers, experts held): how many of the batch's tokens each
    held expert of each ``E`` layer is routed, under the model's own
    parameters and dtype."""
    c = model.config
    p = {n: t._data for n, t in model.named_parameters()}
    p["e_router_bias"] = model._buffers["e_router_bias"]._data
    ids = input_ids._data if isinstance(input_ids, Tensor) else input_ids

    @jax.jit
    def load(p, ids):
        x, out = p["embed"][ids], []
        for kind, own in _layers(c, p):
            if kind == "E":
                u = _ssm.rms_norm_array(x, own["e_norm"], c.norm_eps)
                sel, g = _moe.route_top_k(
                    u, own["e_router_w"], own["e_router_bias"],
                    c.num_experts_per_tok, c.routed_scaling_factor)
                gates = _moe.held_gates(sel, jnp.ones_like(g),
                                        c.experts_held, c.expert_offset)
                out.append(gates.sum((0, 1)))
            x = _BLOCK[kind](c, x, own)
        return jnp.stack(out)

    return np.asarray(load(p, jnp.asarray(ids))).round().astype(np.int64)

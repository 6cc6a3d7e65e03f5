"""BERT / ERNIE — encoder flagship (BASELINE.md config #3: BERT-base /
ERNIE-1.0 pretrain).

Capability parity: the reference expresses BERT through
python/paddle/nn/layer/transformer.py (TransformerEncoder) with ERNIE as
the PaddleNLP recipe on top; dist_transformer.py is its distributed test
model.  Built here with the same stacked-parameter scan trunk as GPT
(models/gpt.py) — one XLA layer body, per-layer remat, hybrid DistAttrs —
plus BERT's bidirectional attention, token-type embeddings, and the
MLM + NSP pretrain heads.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import Parameter, Tensor, apply1
from paddle_tpu.nn.layer.layers import Layer
from paddle_tpu.parallel.mesh import DistAttr, get_mesh
from paddle_tpu.profiler import CountedEvent

__all__ = ["BertConfig", "Bert", "bert_base", "bert_tiny",
           "bert_pretrain_loss", "Ernie", "ErnieConfig"]


class BertConfig:
    def __init__(self, vocab_size=30522, hidden_size=768, num_layers=12,
                 num_heads=12, ffn_size: Optional[int] = None,
                 max_seq_len=512, type_vocab_size=2,
                 initializer_range=0.02, remat: bool = True, seed: int = 0,
                 use_flash_attention: bool = True):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.ffn_size = ffn_size or 4 * hidden_size
        self.max_seq_len = max_seq_len
        self.type_vocab_size = type_vocab_size
        self.initializer_range = initializer_range
        self.remat = remat
        self.seed = seed
        self.use_flash_attention = use_flash_attention

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


ErnieConfig = BertConfig  # ERNIE-1.0 = BERT architecture + corpus recipe


def bert_base(**kw):
    return BertConfig(**kw)


def bert_tiny(**kw):
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("max_seq_len", 128)
    return BertConfig(**kw)


_PARAM_ORDER = ("wte", "wpe", "wtt", "emb_ln_w", "emb_ln_b",
                "ln1_w", "ln1_b", "qkv_w", "qkv_b", "prj_w", "prj_b",
                "ln2_w", "ln2_b", "fc_w", "fc_b", "out_w", "out_b",
                "pool_w", "pool_b", "mlm_w", "mlm_b", "mlm_ln_w",
                "mlm_ln_b", "mlm_bias", "nsp_w", "nsp_b")


class Bert(Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        self.config = config
        with CountedEvent("model.init"):
            self._init_parameters(config)

    def _init_parameters(self, c: BertConfig):
        """Every parameter, drawn on the host from ``c.seed``."""
        rng = np.random.default_rng(c.seed)
        std = c.initializer_range
        L, H, F, V = c.num_layers, c.hidden_size, c.ffn_size, c.vocab_size

        def norm(shape, scale=std):
            return rng.standard_normal(shape).astype(np.float32) * scale

        def param(name, value, spec=None):
            p = Parameter(value, name=f"bert.{name}")
            if spec is not None:
                p.dist_attr = DistAttr(spec)
            self.add_parameter(name, p)
            return p

        param("wte", norm((V, H)), ("mp", None))
        param("wpe", norm((c.max_seq_len, H)))
        param("wtt", norm((c.type_vocab_size, H)))
        param("emb_ln_w", np.ones((H,), np.float32))
        param("emb_ln_b", np.zeros((H,), np.float32))
        param("ln1_w", np.ones((L, H), np.float32), ("pp",))
        param("ln1_b", np.zeros((L, H), np.float32), ("pp",))
        param("qkv_w", norm((L, H, 3 * H)), ("pp", None, "mp"))
        param("qkv_b", np.zeros((L, 3 * H), np.float32), ("pp", "mp"))
        param("prj_w", norm((L, H, H), std / math.sqrt(2 * L)),
              ("pp", "mp", None))
        param("prj_b", np.zeros((L, H), np.float32), ("pp",))
        param("ln2_w", np.ones((L, H), np.float32), ("pp",))
        param("ln2_b", np.zeros((L, H), np.float32), ("pp",))
        param("fc_w", norm((L, H, F)), ("pp", None, "mp"))
        param("fc_b", np.zeros((L, F), np.float32), ("pp", "mp"))
        param("out_w", norm((L, F, H), std / math.sqrt(2 * L)),
              ("pp", "mp", None))
        param("out_b", np.zeros((L, H), np.float32), ("pp",))
        # pooler + pretrain heads
        param("pool_w", norm((H, H)))
        param("pool_b", np.zeros((H,), np.float32))
        param("mlm_w", norm((H, H)))
        param("mlm_b", np.zeros((H,), np.float32))
        param("mlm_ln_w", np.ones((H,), np.float32))
        param("mlm_ln_b", np.zeros((H,), np.float32))
        param("mlm_bias", np.zeros((V,), np.float32), ("mp",))
        param("nsp_w", norm((H, 2)))
        param("nsp_b", np.zeros((2,), np.float32))

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        """-> (mlm_logits (B,S,V), nsp_logits (B,2))."""
        from paddle_tpu.core import apply
        params = [self._parameters[n] for n in _PARAM_ORDER]
        fn = partial(_bert_forward, self.config,
                     token_type_ids is not None, attention_mask is not None)
        extra = [t for t in (token_type_ids, attention_mask)
                 if t is not None]
        mlm, nsp = apply(fn, *params, input_ids, *extra,
                         name="bert_forward")
        return mlm, nsp


def _ln(x, w, b, eps=1e-12):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _mark(x, *spec):
    from paddle_tpu.parallel.mesh import constrain
    return constrain(x, *spec, strip=("sp",))


def _bert_forward(cfg, has_tt, has_mask, wte, wpe, wtt, emb_ln_w, emb_ln_b,
                  ln1_w, ln1_b, qkv_w, qkv_b, prj_w, prj_b, ln2_w, ln2_b,
                  fc_w, fc_b, out_w, out_b, pool_w, pool_b, mlm_w, mlm_b,
                  mlm_ln_w, mlm_ln_b, mlm_bias, nsp_w, nsp_b, ids, *extra):
    H, nh, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    it = iter(extra)
    tt = next(it) if has_tt else jnp.zeros_like(ids)
    mask = next(it) if has_mask else None

    # the named scopes are the regions a device trace is read by
    # (profiler/__init__.py lists them); metadata only
    B, S = ids.shape
    with jax.named_scope("embed"):
        x = wte[ids] + wpe[:S][None] + wtt[tt]
        x = _ln(x, emb_ln_w, emb_ln_b)
        x = _mark(x, "dp", None, None)

    if mask is not None:
        bias = jnp.where(mask[:, None, :].astype(bool), 0.0,
                         -1e30)[:, None, :, :]  # (B,1,1,S) additive
    else:
        bias = None

    stacked = {"ln1_w": ln1_w, "ln1_b": ln1_b, "qkv_w": qkv_w,
               "qkv_b": qkv_b, "prj_w": prj_w, "prj_b": prj_b,
               "ln2_w": ln2_w, "ln2_b": ln2_b, "fc_w": fc_w, "fc_b": fc_b,
               "out_w": out_w, "out_b": out_b}

    scale = 1.0 / math.sqrt(hd)

    def _flash_ok(b, s):
        if not cfg.use_flash_attention:
            return False
        from paddle_tpu.ops.pallas import flash_attention as _fa
        return _fa.supported(
            (b, s, nh, hd), (b, s, nh, hd), bias is None,
            bias_shape=None if bias is None else tuple(bias.shape))

    def layer(x, lp):
        b, s = x.shape[:2]
        with jax.named_scope("attn"):
            with jax.named_scope("qkv"):
                qkv = x @ lp["qkv_w"] + lp["qkv_b"]
                qkv = _mark(qkv, "dp", None, "mp")
                q, k, v = jnp.split(qkv, 3, axis=-1)
            with jax.named_scope("core"):
                if _flash_ok(b, s):
                    # Pallas flash kernel, (B,S,H,D) layout; the padding
                    # mask rides as (B,1,1,S) bias tiles so padded
                    # batches stay O(S·D)
                    from paddle_tpu.ops.pallas import flash_attention as _fa
                    from paddle_tpu.parallel.mesh import per_device
                    kernel = partial(_fa.flash_attention, scale=scale,
                                     bias=bias, bias_grad=False)
                    if bias is None:
                        # GSPMD cannot partition a Mosaic call: under a
                        # mesh each device runs its own batch x heads
                        kernel = per_device(kernel, get_mesh(),
                                            ("dp", None, "mp", None))
                    a = kernel(q.reshape(b, s, nh, hd),
                               k.reshape(b, s, nh, hd),
                               v.reshape(b, s, nh, hd))
                    a = a.reshape(b, s, H)
                else:
                    q = q.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
                    k = k.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
                    v = v.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
                    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
                    if bias is not None:
                        scores = scores + bias
                    p = jax.nn.softmax(scores.astype(jnp.float32),
                                       -1).astype(x.dtype)
                    a = jnp.einsum("bhqk,bhkd->bhqd", p,
                                   v).transpose(0, 2, 1, 3)
                    a = a.reshape(b, s, H)
            # post-LN (original BERT): LN(x + sublayer(x))
            with jax.named_scope("out"):
                y = x + a @ lp["prj_w"] + lp["prj_b"]
            with jax.named_scope("ln"):
                x = _ln(y, lp["ln1_w"], lp["ln1_b"])
        with jax.named_scope("mlp"):
            with jax.named_scope("up"):
                ff = jax.nn.gelu(x @ lp["fc_w"] + lp["fc_b"],
                                 approximate=True)
                ff = _mark(ff, "dp", None, "mp")
            with jax.named_scope("down"):
                y = x + ff @ lp["out_w"] + lp["out_b"]
            with jax.named_scope("ln"):
                x = _ln(y, lp["ln2_w"], lp["ln2_b"])
        return _mark(x, "dp", None, None), None

    body = jax.checkpoint(layer) if cfg.remat else layer
    x, _ = jax.lax.scan(lambda c, lp: body(c, lp), x, stacked)

    with jax.named_scope("head_loss"):
        pooled = jnp.tanh(x[:, 0] @ pool_w + pool_b)
        nsp_logits = pooled @ nsp_w + nsp_b

        h = jax.nn.gelu(x @ mlm_w + mlm_b, approximate=True)
        h = _ln(h, mlm_ln_w, mlm_ln_b)
        mlm_logits = h @ wte.T + mlm_bias
        return _mark(mlm_logits, "dp", None, "mp"), nsp_logits


def bert_pretrain_loss(model, input_ids, mlm_labels, nsp_labels,
                       attention_mask=None):
    """MLM (ignore_index=-100) + NSP cross entropy.  ``attention_mask``
    (B, S), 1 = real token: the padded-batch pretrain layout."""
    mlm_logits, nsp_logits = model(input_ids,
                                   attention_mask=attention_mask)

    @jax.named_scope("head_loss")
    def loss(mlm_logits, nsp_logits, mlm_labels, nsp_labels):
        lg = mlm_logits.astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(lg, axis=-1)
        tgt = jnp.clip(mlm_labels, 0, None)
        gold = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
        valid = (mlm_labels >= 0).astype(jnp.float32)
        mlm = jnp.sum((logz - gold) * valid) / jnp.maximum(
            jnp.sum(valid), 1.0)
        ng = nsp_logits.astype(jnp.float32)
        nlogz = jax.scipy.special.logsumexp(ng, axis=-1)
        ngold = jnp.take_along_axis(ng, nsp_labels[:, None], axis=-1)[:, 0]
        nsp = jnp.mean(nlogz - ngold)
        return mlm + nsp

    return apply1(loss, mlm_logits, nsp_logits, mlm_labels, nsp_labels,
                  name="bert_pretrain_loss")


Ernie = Bert

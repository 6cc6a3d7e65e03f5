"""Attention functionals.

The reference ships only full-materialised attention
(python/paddle/nn/layer/transformer.py:115 MultiHeadAttention) plus fused
inference kernels (operators/fused/multihead_matmul_op.cu).  The TPU-native
replacement is a Pallas flash-attention kernel (paddle_tpu/ops/pallas/
flash_attention.py) — blockwise online-softmax so the S×S score matrix never
hits HBM — with a pure-XLA path for CPU tests and shapes its gate rejects.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from paddle_tpu.core import Tensor, apply1
from paddle_tpu.framework import flags as _flags

__all__ = ["scaled_dot_product_attention", "flash_attention"]


def _xla_attention(q, k, v, mask, scale, causal):
    # q,k,v: (B, S, H, D) paddle layout
    qh = jnp.einsum("bshd->bhsd", q)
    kh = jnp.einsum("bshd->bhsd", k)
    vh = jnp.einsum("bshd->bhsd", v)
    scores = jnp.einsum("bhsd,bhtd->bhst", qh, kh) * scale
    if causal:
        s_q, s_k = scores.shape[-2], scores.shape[-1]
        causal_mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool),
                               k=s_k - s_q)
        scores = jnp.where(causal_mask, scores, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, -1e30)
        else:
            scores = scores + mask
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vh)
    return jnp.einsum("bhsd->bshd", out)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """(B, S, H, D) attention.  Uses the Pallas flash kernel on TPU when
    shapes allow — including masked calls: bool or additive ``attn_mask``
    broadcastable to (B, H, Sq, Sk) rides the kernel as a tile-streamed
    bias (the reference's fused attention takes the same bias_qk input,
    multihead_matmul_op.cu), so padded-batch workloads stay O(S·D).
    Shapes and backends the kernel's ``supported()`` gate rejects take the
    XLA path (still fused reasonably well by XLA, but materialises
    scores); what the gate accepts runs in the kernel or raises."""
    d = query.shape[-1]
    scale = 1.0 / math.sqrt(d)

    from paddle_tpu.ops.pallas import flash_attention as _fa
    use_flash = _fa.supported(
        tuple(query.shape), tuple(key.shape), attn_mask is None,
        causal=is_causal,
        bias_shape=None if attn_mask is None else tuple(attn_mask.shape))

    if use_flash:
        if attn_mask is not None:
            # padding masks are feed data: bias_grad=False skips the dbias
            # kernel and nondiff keeps them off the eager tape.  A LEARNED
            # additive bias (stop_gradient=False Tensor) keeps its grad —
            # the dbias reduction kernel serves it.
            trains = not getattr(attn_mask, "stop_gradient", True)

            def _run(q, k, v, m):
                return _fa.flash_attention(q, k, v, causal=is_causal,
                                           scale=scale, bias=m,
                                           bias_grad=trains)
            out = apply1(_run, query, key, value, attn_mask,
                         name="flash_attention",
                         nondiff=() if trains else (3,))
        else:
            def _run(q, k, v):
                return _fa.flash_attention(q, k, v, causal=is_causal,
                                           scale=scale)
            out = apply1(_run, query, key, value, name="flash_attention")
    else:
        def _run(q, k, v, *m):
            return _xla_attention(q, k, v, m[0] if m else None, scale,
                                  is_causal)
        if attn_mask is not None:
            out = apply1(_run, query, key, value, attn_mask,
                         name="sdp_attention")
        else:
            out = apply1(_run, query, key, value, name="sdp_attention")
    if dropout_p > 0.0 and training:
        from paddle_tpu.nn.functional.common import dropout
        out = dropout(out, p=dropout_p, training=training)
    return out


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, attn_mask=None,
                    q_segment_ids=None, kv_segment_ids=None, name=None):
    """Flash attention with TPU-native extensions.

    ``q_segment_ids``/``kv_segment_ids`` ((B, S) int) enable
    packed-sequence attention — tokens only attend within their segment —
    at O(B·S) mask memory where an explicit packed mask is O(B·S²).  On
    the kernel path they are evaluated inside the Pallas tiles; the XLA
    fallback materialises the equivalent mask.
    """
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("flash_attention: pass both q_segment_ids and "
                         "kv_segment_ids, or neither")
    if q_segment_ids is not None:
        d = query.shape[-1]
        scale = 1.0 / math.sqrt(d)
        from paddle_tpu.ops.pallas import flash_attention as _fa
        ok = _fa.supported(
            tuple(query.shape), tuple(key.shape), attn_mask is None,
            causal=causal, segments=True,
            bias_shape=None if attn_mask is None
            else tuple(attn_mask.shape))
        if ok:
            def _run(q, k, v, qs, ks, *m):
                return _fa.flash_attention(
                    q, k, v, causal=causal, scale=scale,
                    bias=m[0] if m else None, bias_grad=False,
                    q_segment_ids=qs, kv_segment_ids=ks)
        else:
            def _run(q, k, v, qs, ks, *m):
                seg = (qs[:, None, :, None] == ks[:, None, None, :])
                mask = m[0] if m else None
                bias = jnp.where(seg, 0.0, -1e30)
                if mask is not None:
                    bias = bias + (jnp.where(mask, 0.0, -1e30)
                                   if mask.dtype == jnp.bool_ else mask)
                return _xla_attention(q, k, v, bias, scale, causal)
        args = [query, key, value, q_segment_ids, kv_segment_ids]
        nondiff = (3, 4)
        if attn_mask is not None:
            args.append(attn_mask)
            nondiff = (3, 4, 5)
        out = apply1(_run, *args, name="flash_attention", nondiff=nondiff)
        if dropout > 0.0:
            from paddle_tpu.nn.functional.common import dropout as _dropout
            out = _dropout(out, p=dropout)
        if return_softmax:
            return out, None
        return out

    out = scaled_dot_product_attention(query, key, value, dropout_p=dropout,
                                       is_causal=causal, attn_mask=attn_mask)
    if return_softmax:
        return out, None
    return out

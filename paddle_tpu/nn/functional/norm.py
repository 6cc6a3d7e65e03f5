"""Normalization functionals (reference kernels: operators/batch_norm_op.*,
layer_norm_op.*, instance_norm_op.*, group_norm_op.*, norm_op.*)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core import Tensor, apply, apply1
from paddle_tpu.nn.functional.ssm import rms_norm_array

__all__ = ["batch_norm", "layer_norm", "rms_norm", "instance_norm",
           "group_norm", "local_response_norm", "normalize"]


def _mean_var_1pass(a, axes, keepdims=False):
    """mean and variance as SIBLING reductions over one input read.

    ``jnp.var`` reduces twice sequentially (mean, then mean((x-m)^2)) —
    the second pass depends on the first, so XLA cannot fuse them and the
    activation is read twice (3x with the normalize).  E[x^2]-E[x]^2 puts
    both accumulators in one multi-output reduction fusion: profiled on
    one chip, ResNet-50's step time is dominated by exactly these
    BN-stat passes, not the convs.  Accumulation in f32 keeps bf16
    activations numerically safe.

    Numerics (advisor r3: E[x^2]-E[x]^2 cancels when |mean| >> std):
    - low-precision inputs (bf16/f16, the AMP hot path) keep the one-pass
      form — any cancellation error in the f32 accumulators is below the
      input's own quantization (bf16 ULP at |x| dominates), so the clamp
      is a true no-op there.  Shift-K variants were measured and
      rejected: a slice-K costs ResNet-50 ~16% and a running-mean-K
      ~40% (both break XLA's multi-output stat-fusion shape).
    - float inputs that CAN carry sub-cancellation variance (f32/f64)
      take the exact two-pass form instead — the reference's semantics,
      at the cost of the second activation read.
    """
    af = a.astype(jnp.float32)
    if any(a.shape[ax] == 0 for ax in axes):
        # empty reduction: the stats are NaN either way; keep it finite
        m = jnp.mean(af, axis=axes, keepdims=keepdims)
        v = jnp.zeros_like(m)
        return m.astype(a.dtype), v.astype(a.dtype)
    if a.dtype in (jnp.float32, jnp.float64):
        m = jnp.mean(af, axis=axes, keepdims=True)
        v = jnp.mean(jnp.square(af - m), axis=axes, keepdims=True)
    else:
        m = jnp.mean(af, axis=axes, keepdims=True)
        msq = jnp.mean(af * af, axis=axes, keepdims=True)
        v = jnp.maximum(msq - m * m, 0.0)
    if not keepdims:
        m = jnp.squeeze(m, axis=axes)
        v = jnp.squeeze(v, axis=axes)
    return m.astype(a.dtype), v.astype(a.dtype)


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", use_global_stats=None, name=None):
    """Functional batch norm.

    Running-stat update happens host-side on the Tensor buffers (matching the
    reference's in-place mean/var outputs, operators/batch_norm_op.cc); under
    jit capture use Layer form which threads stats functionally.
    """
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    use_batch_stats = training and not use_global_stats

    def _stats_axes(a):
        if channel_last:
            return tuple(range(a.ndim - 1))
        return (0,) + tuple(range(2, a.ndim))

    def _bn(a, mean, var, *wb):
        axes = _stats_axes(a)
        shape = [1] * a.ndim
        c_axis = a.ndim - 1 if channel_last else (1 if a.ndim > 1 else 0)
        shape[c_axis] = a.shape[c_axis]
        if use_batch_stats:
            m, v = _mean_var_1pass(a, axes)
        else:
            m, v = mean, var
        out = (a - m.reshape(shape)) * jax.lax.rsqrt(
            v.reshape(shape) + epsilon)
        if wb:
            w = wb[0]
            out = out * w.reshape(shape)
            if len(wb) > 1:
                out = out + wb[1].reshape(shape)
        return out

    args = [x, running_mean, running_var]
    nondiff = (1, 2)
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    out = apply1(_bn, *args, nondiff=nondiff, name="batch_norm")

    # Running-stat update: works eagerly AND under jit capture — the buffer's
    # ._data becomes a tracer which paddle_tpu.jit harvests as a functional
    # output (see StaticFunction/TrainStep buffer threading).
    if use_batch_stats and isinstance(running_mean, Tensor):
        axes = _stats_axes(x._data)
        m, v = _mean_var_1pass(x._data, axes)
        n = 1
        for ax in axes:
            n *= x._data.shape[ax]
        unbiased = v * (n / max(n - 1, 1))
        running_mean._data = momentum * running_mean._data + (1 - momentum) * m
        running_var._data = momentum * running_var._data + (1 - momentum) * unbiased
    return out


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               name=None):
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    n_axes = len(list(normalized_shape))

    def _ln(a, *wb):
        axes = tuple(range(a.ndim - n_axes, a.ndim))
        m, v = _mean_var_1pass(a, axes, keepdims=True)
        out = (a - m) * jax.lax.rsqrt(v + epsilon)
        if wb:
            out = out * wb[0]
            if len(wb) > 1:
                out = out + wb[1]
        return out

    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply1(_ln, *args, name="layer_norm")


def rms_norm(x, weight, epsilon=1e-5, name=None):
    """``x / sqrt(mean(x^2) + epsilon) * weight`` over the last axis (no
    mean, no bias), statistics in float32.  The reference framework has
    no such functional; ``models/nemotron_h.py`` norms with it."""
    return apply1(lambda a, w: rms_norm_array(a, w, epsilon), x, weight,
                  name="rms_norm")


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None):
    def _in(a, *wb):
        axes = tuple(range(2, a.ndim))
        m, v = _mean_var_1pass(a, axes, keepdims=True)
        out = (a - m) * jax.lax.rsqrt(v + eps)
        if wb:
            shape = [1, a.shape[1]] + [1] * (a.ndim - 2)
            out = out * wb[0].reshape(shape)
            if len(wb) > 1:
                out = out + wb[1].reshape(shape)
        return out
    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply1(_in, *args, name="instance_norm")


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    channel_last = data_format in ("NHWC", "NDHWC", "NLC")

    def _gn(a, *wb):
        if channel_last:
            a_t = jnp.moveaxis(a, -1, 1)
        else:
            a_t = a
        n, c = a_t.shape[0], a_t.shape[1]
        g = num_groups
        grouped = a_t.reshape((n, g, c // g) + a_t.shape[2:])
        axes = tuple(range(2, grouped.ndim))
        m, v = _mean_var_1pass(grouped, axes, keepdims=True)
        out = ((grouped - m) * jax.lax.rsqrt(v + epsilon)).reshape(a_t.shape)
        if wb:
            shape = [1, c] + [1] * (a_t.ndim - 2)
            out = out * wb[0].reshape(shape)
            if len(wb) > 1:
                out = out + wb[1].reshape(shape)
        if channel_last:
            out = jnp.moveaxis(out, 1, -1)
        return out
    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply1(_gn, *args, name="group_norm")


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    def _lrn(a):
        sq = a * a
        c_axis = 1 if data_format.startswith("NC") else a.ndim - 1
        half = size // 2
        pads = [(0, 0)] * a.ndim
        pads[c_axis] = (half, size - half - 1)
        padded = jnp.pad(sq, pads)
        dims = [1] * a.ndim
        dims[c_axis] = size
        summed = jax.lax.reduce_window(padded, 0.0, jax.lax.add, tuple(dims),
                                       (1,) * a.ndim, "VALID")
        return a / jnp.power(k + alpha * summed, beta)
    return apply1(_lrn, x, name="local_response_norm")


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    def _normalize(a):
        if p == 2:
            n = jnp.sqrt(jnp.sum(a * a, axis=axis, keepdims=True))
        else:
            n = jnp.sum(jnp.abs(a) ** p, axis=axis, keepdims=True) ** (1.0 / p)
        return a / jnp.maximum(n, epsilon)
    return apply1(_normalize, x, name="normalize")

"""Automatic mixed precision.

Parity targets: python/paddle/amp/auto_cast.py (:20) + grad_scaler.py (:20);
reference engine: imperative/amp_auto_cast.{h,cc} (AmpOperators white/black
lists :31, AutoCastGuard :58) and the AMP ops
operators/amp/check_finite_and_unscale_op, update_loss_scaling_op.

TPU-first: the compute dtype is bfloat16 (MXU native), which has fp32's
exponent range — so loss scaling is a no-op by default (GradScaler keeps the
reference's API and its dynamic-scaling state machine for fp16 mode, but
``enable=True`` with bf16 performs identity scaling).  auto_cast hooks the
tape's apply() to cast op inputs per white/black list, exactly the role of
AmpOperators in the reference tracer.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
import jax.numpy as jnp

import paddle_tpu.core as core
from paddle_tpu.core import Tensor

__all__ = ["auto_cast", "decorate", "GradScaler", "white_list", "black_list"]

# op-name lists mirroring imperative/amp_auto_cast.cc AmpOperators
white_list = {
    "matmul", "mm", "bmm", "mv", "conv1d", "conv2d", "conv3d",
    "conv1d_transpose", "conv2d_transpose", "conv3d_transpose", "linear",
    "einsum", "flash_attention", "sdp_attention", "addmm",
}
black_list = {
    "exp", "log", "log2", "log10", "log1p", "logsumexp", "mean", "sum",
    "softmax", "log_softmax", "cross_entropy", "nll_loss", "bce_with_logits",
    "binary_cross_entropy", "mse_loss", "l1_loss", "smooth_l1_loss", "kl_div",
    "layer_norm", "batch_norm", "instance_norm", "group_norm", "norm",
    "cumsum", "softmax_with_cross_entropy", "pow", "square", "sqrt", "rsqrt",
}

_amp_state = threading.local()


def _amp_level() -> Optional[str]:
    return getattr(_amp_state, "level", None)


def _amp_dtype():
    return getattr(_amp_state, "dtype", jnp.bfloat16)


def _amp_custom_white():
    return getattr(_amp_state, "custom_white", set())


def _amp_custom_black():
    return getattr(_amp_state, "custom_black", set())


@contextlib.contextmanager
def auto_cast(enable=True, custom_white_list=None, custom_black_list=None,
              level="O1", dtype="bfloat16"):
    """paddle.amp.auto_cast parity; `float16` maps to bfloat16 on TPU unless
    explicitly forced (bf16 is the hardware-native mixed dtype)."""
    prev = (_amp_level(), _amp_dtype(), _amp_custom_white(),
            _amp_custom_black())
    prev_hook = core._amp_hook[0]
    if enable:
        _amp_state.level = level
        _amp_state.dtype = jnp.bfloat16 if str(dtype) in (
            "bfloat16", "bf16", "float16", "fp16") else jnp.dtype(dtype)
        _amp_state.custom_white = set(custom_white_list or ())
        _amp_state.custom_black = set(custom_black_list or ())
        core._amp_hook[0] = amp_cast_for_op
    else:
        _amp_state.level = None
    try:
        yield
    finally:
        (_amp_state.level, _amp_state.dtype, _amp_state.custom_white,
         _amp_state.custom_black) = prev
        core._amp_hook[0] = prev_hook


amp_guard = auto_cast


def amp_cast_for_op(name: str, args):
    """Called by core.apply when an amp level is active: cast float tensor
    args to the amp dtype for white-listed ops, to fp32 for black-listed ops
    (O1); O2 casts everything except black list."""
    level = _amp_level()
    if level is None:
        return args
    dtype = _amp_dtype()
    cw, cb = _amp_custom_white(), _amp_custom_black()
    in_white = (name in white_list or name in cw) and name not in cb
    in_black = name in black_list or name in cb

    # Casting must stay differentiable → do it through the tape
    from paddle_tpu.core import apply1
    def cast_tensor(a, to):
        if not isinstance(a, Tensor):
            return a
        if not jnp.issubdtype(a.dtype, jnp.floating) or a.dtype == jnp.dtype(to):
            return a
        return apply1(lambda x: x.astype(to), a, name="amp_cast")

    if level == "O2":
        if in_black:
            return [cast_tensor(a, jnp.float32) for a in args]
        return [cast_tensor(a, dtype) for a in args]
    if in_white:
        return [cast_tensor(a, dtype) for a in args]
    if in_black:
        return [cast_tensor(a, jnp.float32) for a in args]
    return args


def decorate(models, optimizers=None, level="O2", dtype="bfloat16",
             master_weight=None, save_dtype=None):
    """paddle.amp.decorate parity: O2 casts parameters to the amp dtype
    (master fp32 copies kept by the optimizer when master_weight)."""
    single_model = not isinstance(models, (list, tuple))
    model_list = [models] if single_model else list(models)
    if level == "O2":
        dt = jnp.bfloat16 if str(dtype) in ("bfloat16", "bf16", "float16",
                                            "fp16") else jnp.dtype(dtype)
        for m in model_list:
            for p in m.parameters():
                if jnp.issubdtype(p.dtype, jnp.floating):
                    p.master_data = p._data  # fp32 master copy
                    p._data = p._data.astype(dt)
    if optimizers is None:
        return models if single_model else model_list
    return (models if single_model else model_list), optimizers


class GradScaler:
    """Dynamic loss scaling (reference: python/paddle/amp/grad_scaler.py +
    update_loss_scaling_op).  With bf16 (TPU default) scaling is identity;
    the fp16 state machine is kept for parity and CPU tests."""

    def __init__(self, enable=True, init_loss_scaling=2.0 ** 15,
                 incr_ratio=2.0, decr_ratio=0.5, incr_every_n_steps=1000,
                 decr_every_n_nan_or_inf=2, use_dynamic_loss_scaling=True):
        self._enable = enable
        self._scale = float(init_loss_scaling)
        self._incr_ratio = incr_ratio
        self._decr_ratio = decr_ratio
        self._incr_every = incr_every_n_steps
        self._decr_every = decr_every_n_nan_or_inf
        self._dynamic = use_dynamic_loss_scaling
        self._good_steps = 0
        self._bad_steps = 0
        self._found_inf = False
        # numerics telemetry: consecutive scale DECREASES with no good
        # step in between — K of them is a loss-scale collapse
        # (numerics.scale_collapse flight event), the systematic-
        # overflow signal the GradScaler/ResilientTrainStep coop
        # previously had no observability for
        self._consecutive_downscales = 0

    def is_enable(self):
        return self._enable

    def is_use_dynamic_loss_scaling(self):
        return self._dynamic

    def get_init_loss_scaling(self):
        return self._scale

    def scale(self, var: Tensor) -> Tensor:
        if not self._enable:
            return var
        from paddle_tpu.tensor.math import scale as _scale
        return _scale(var, self._scale)

    def unscale_(self, optimizer):
        if not self._enable:
            return
        inv = 1.0 / self._scale
        # one device computation + ONE host sync for the whole parameter
        # list (check_finite_and_unscale is a single fused op in the
        # reference too — operators/amp/check_finite_and_unscale_op)
        from paddle_tpu.framework.selected_rows import SelectedRows
        grads = [p._grad for p in optimizer._parameter_list or []
                 if p._grad is not None]
        if not grads:
            self._found_inf = False
            return
        # SelectedRows grads unscale their row values in place (the
        # reference's check_finite_and_unscale handles SelectedRows too)
        scaled = [(g.values if isinstance(g, SelectedRows) else g._data)
                  * inv for g in grads]
        flags = jnp.stack([jnp.any(~jnp.isfinite(s)) for s in scaled])
        for g, s in zip(grads, scaled):
            if isinstance(g, SelectedRows):
                g.values = s
            else:
                g._data = s
        self._found_inf = bool(jnp.any(flags))

    def minimize(self, optimizer, scaled_loss):
        scaled_loss.backward()
        self.step(optimizer)
        self.update()

    def step(self, optimizer):
        if not self._enable:
            optimizer.step()
            return
        self.unscale_(optimizer)
        if not self._found_inf:
            optimizer.step()

    def update(self):
        if not self._enable or not self._dynamic:
            return
        # local import: amp loads with the core tensor tier, before the
        # framework observability planes need to exist
        from paddle_tpu.framework import monitor
        from paddle_tpu.framework.flags import flag
        from paddle_tpu.framework.observability import flight
        if self._found_inf:
            self._bad_steps += 1
            self._good_steps = 0
            if self._bad_steps >= self._decr_every:
                self._scale = max(self._scale * self._decr_ratio, 1.0)
                self._bad_steps = 0
                self._consecutive_downscales += 1
                k = int(flag("numerics_scale_collapse_k"))
                if k > 0 and self._consecutive_downscales >= k and \
                        self._consecutive_downscales % k == 0:
                    # K downscales with no good step between them: the
                    # overflow is systematic, not a transient batch
                    cd = self._consecutive_downscales
                    flight.record("numerics.scale_collapse",
                                  severity="warn", scale=self._scale,
                                  consecutive_downscales=cd)
                    monitor.stat_add("amp_scale_collapses_total")
        else:
            self._good_steps += 1
            self._bad_steps = 0
            self._consecutive_downscales = 0
            if self._good_steps >= self._incr_every:
                self._scale *= self._incr_ratio
                self._good_steps = 0
        monitor.stat_set("amp_loss_scale", self._scale)
        self._found_inf = False

    def state_dict(self):
        return {"scale": self._scale, "incr_ratio": self._incr_ratio,
                "decr_ratio": self._decr_ratio,
                "incr_every_n_steps": self._incr_every,
                "decr_every_n_nan_or_inf": self._decr_every,
                "good_steps": self._good_steps, "bad_steps": self._bad_steps,
                "consecutive_downscales": self._consecutive_downscales}

    def set_state_dict(self, sd):
        self._scale = sd.get("scale", self._scale)
        self._good_steps = sd.get("good_steps", 0)
        self._bad_steps = sd.get("bad_steps", 0)
        # restore (or, for a pre-telemetry checkpoint, reset) the
        # collapse streak — a stale streak from this object's past life
        # must not fire a spurious numerics.scale_collapse
        self._consecutive_downscales = sd.get("consecutive_downscales", 0)

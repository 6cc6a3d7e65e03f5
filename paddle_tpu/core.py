"""Core runtime: Tensor facade, eager autograd tape, dtype/device plumbing.

This is the TPU-native replacement for the reference's C++ core:

- ``Tensor`` plays the role of ``imperative::VarBase`` (reference:
  paddle/fluid/imperative/layer.h:66) — an eager tensor carrying autograd
  metadata — but wraps a ``jax.Array`` instead of an allocator-backed buffer.
- The tape (``TapeNode`` + ``apply``) replaces ``Tracer::TraceOp`` recording a
  grad-op graph (reference: paddle/fluid/imperative/tracer.cc:132,205): every
  differentiable op is routed through ``jax.vjp`` eagerly, and ``backward()``
  replaces ``BasicEngine::Execute`` (reference:
  paddle/fluid/imperative/basic_engine.cc:305) with a reverse-topological walk.
- There is no Place/DeviceContext/Allocator layer (reference:
  paddle/fluid/platform/device_context.h, paddle/fluid/memory/) — XLA/PJRT owns
  streams and device memory. ``CPUPlace``/``TPUPlace`` survive as thin device
  handles for API parity only.

Design note (TPU-first): eager mode executes op-by-op through jax's cached
dispatch; the performance path is whole-step capture via ``paddle_tpu.jit``
(to_static) where forward+backward+update fuse into one XLA computation.
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading
import weakref
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Tensor",
    "Parameter",
    "apply",
    "no_grad",
    "enable_grad",
    "is_grad_enabled",
    "set_grad_enabled",
    "CPUPlace",
    "TPUPlace",
    "CUDAPlace",
    "CUDAPinnedPlace",
    "XPUPlace",
    "set_device",
    "get_device",
    "device_count",
    "convert_dtype",
    "get_default_dtype",
    "set_default_dtype",
    "VarDesc",
]

# ---------------------------------------------------------------------------
# dtype system
# ---------------------------------------------------------------------------

# Mirrors the reference's proto dtype enum surface (framework.proto:107-125)
# without the proto: everything is a numpy/jax dtype under the hood.
_DTYPE_ALIASES = {
    "float16": jnp.float16,
    "fp16": jnp.float16,
    "bfloat16": jnp.bfloat16,
    "bf16": jnp.bfloat16,
    "float32": jnp.float32,
    "fp32": jnp.float32,
    "float": jnp.float32,
    "float64": jnp.float64,
    "fp64": jnp.float64,
    "double": jnp.float64,
    "int8": jnp.int8,
    "uint8": jnp.uint8,
    "int16": jnp.int16,
    "int32": jnp.int32,
    "int64": jnp.int64,
    "int": jnp.int32,
    "bool": jnp.bool_,
    "complex64": jnp.complex64,
    "complex128": jnp.complex128,
}


def convert_dtype(dtype) -> jnp.dtype:
    """Normalise any dtype spec (str / np.dtype / jnp dtype / Tensor dtype)."""
    if dtype is None:
        return None
    if isinstance(dtype, str):
        key = dtype.lower().replace("paddle.", "")
        if key in _DTYPE_ALIASES:
            return jnp.dtype(_DTYPE_ALIASES[key])
        return jnp.dtype(key)
    return jnp.dtype(dtype)


_default_dtype = jnp.dtype(jnp.float32)


def set_default_dtype(d):
    global _default_dtype
    d = convert_dtype(d)
    if d not in (jnp.dtype(jnp.float16), jnp.dtype(jnp.bfloat16),
                 jnp.dtype(jnp.float32), jnp.dtype(jnp.float64)):
        raise TypeError(
            "set_default_dtype only supports float16/bfloat16/float32/float64, "
            f"got {d}")
    _default_dtype = d


def get_default_dtype() -> str:
    return _default_dtype.name


class VarDesc:
    """Compat shim: ``VarDesc.VarType.FP32``-style dtype enums.

    The reference exposes proto enums (framework.proto:107); user code sometimes
    touches them. Here they are just jnp dtypes.
    """

    class VarType:
        FP16 = jnp.dtype(jnp.float16)
        BF16 = jnp.dtype(jnp.bfloat16)
        FP32 = jnp.dtype(jnp.float32)
        FP64 = jnp.dtype(jnp.float64)
        INT8 = jnp.dtype(jnp.int8)
        UINT8 = jnp.dtype(jnp.uint8)
        INT16 = jnp.dtype(jnp.int16)
        INT32 = jnp.dtype(jnp.int32)
        INT64 = jnp.dtype(jnp.int64)
        BOOL = jnp.dtype(jnp.bool_)
        COMPLEX64 = jnp.dtype(jnp.complex64)
        COMPLEX128 = jnp.dtype(jnp.complex128)


# ---------------------------------------------------------------------------
# Places / device handles (API parity with platform/place.h)
# ---------------------------------------------------------------------------


class _Place:
    _kind = "unknown"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    def __eq__(self, other):
        return (type(self) is type(other)
                and self._device_id == other._device_id)

    def __hash__(self):
        return hash((self._kind, self._device_id))

    def __repr__(self):
        if self._kind == "cpu":
            return "Place(cpu)"
        return f"Place({self._kind}:{self._device_id})"


class CPUPlace(_Place):
    _kind = "cpu"


class TPUPlace(_Place):
    _kind = "tpu"


class CUDAPlace(TPUPlace):
    """Alias of TPUPlace: code written against CUDAPlace runs on the TPU chip."""
    _kind = "tpu"


class CUDAPinnedPlace(CPUPlace):
    _kind = "cpu"


class XPUPlace(TPUPlace):
    _kind = "tpu"


_current_device: Optional[str] = None
_device_lock = threading.Lock()


def _accelerator_platform() -> Optional[str]:
    """'tpu' when JAX has a TPU backend in this process, else None."""
    try:
        return "tpu" if jax.devices("tpu") else None
    except RuntimeError:
        return None


def get_device() -> str:
    """'tpu:0' when an accelerator is attached, else 'cpu'."""
    global _current_device
    if _current_device is None:
        with _device_lock:
            if _current_device is None:
                plat = _accelerator_platform()
                _current_device = "tpu:0" if plat else "cpu"
    return _current_device


def set_device(device: str):
    """Parity with paddle.set_device; accepts 'cpu', 'tpu', 'tpu:N', 'gpu'...

    'gpu' is accepted and mapped onto the TPU chip so reference-style scripts
    run unchanged.
    """
    global _current_device
    device = device.lower()
    if device in ("gpu", "cuda", "xpu"):
        device = "tpu"
    if device.startswith(("gpu:", "cuda:", "xpu:")):
        device = "tpu:" + device.split(":", 1)[1]
    if device == "tpu":
        device = "tpu:0"
    if device != "cpu" and not device.startswith("tpu:"):
        raise ValueError(f"unsupported device {device!r}")
    if device.startswith("tpu:"):
        # no silent move to the host: a run that asked for the chip and
        # did not get it must not look like one that did
        if _accelerator_platform() is None:
            raise RuntimeError(
                f"set_device({device!r}): JAX found no TPU "
                f"(default backend {jax.default_backend()!r}, "
                f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
        n = len(jax.devices("tpu"))
        if int(device.split(":")[1]) >= n:
            raise ValueError(
                f"set_device({device!r}): only {n} TPU device(s) visible")
    _current_device = device
    return _place_of(device)


def _place_of(device: str) -> _Place:
    if device == "cpu":
        return CPUPlace()
    return TPUPlace(int(device.split(":")[1]))


def device_count() -> int:
    return len(jax.devices("tpu" if _accelerator_platform() else None))


def _default_jax_device():
    dev = get_device()
    if dev == "cpu":
        return jax.devices("cpu")[0]
    return jax.devices("tpu")[int(dev.split(":")[1])]


# ---------------------------------------------------------------------------
# grad mode
# ---------------------------------------------------------------------------

_grad_state = threading.local()


def is_grad_enabled() -> bool:
    return getattr(_grad_state, "enabled", True)


def set_grad_enabled(mode: bool):
    _grad_state.enabled = bool(mode)


class _GradModeGuard(contextlib.ContextDecorator):
    def __init__(self, mode: bool):
        self._mode = mode

    def __enter__(self):
        self._prev = is_grad_enabled()
        set_grad_enabled(self._mode)
        return self

    def __exit__(self, *exc):
        set_grad_enabled(self._prev)
        return False


def no_grad(func=None):
    """Context-manager *and* decorator, parity with paddle.no_grad."""
    if func is None:
        return _GradModeGuard(False)
    return _GradModeGuard(False)(func)


def enable_grad(func=None):
    if func is None:
        return _GradModeGuard(True)
    return _GradModeGuard(True)(func)


# ---------------------------------------------------------------------------
# autograd tape
# ---------------------------------------------------------------------------


class TapeNode:
    """One recorded differentiable op (≈ imperative::GradOpNode,
    reference: paddle/fluid/imperative/layer.h + tracer.cc:205)."""

    __slots__ = ("vjp_fn", "inputs", "outputs", "name", "out_is_seq",
                 "pure_fn", "out_avals", "__weakref__")

    def __init__(self, vjp_fn, inputs, outputs, name="", out_is_seq=False,
                 pure_fn=None, out_avals=None):
        self.vjp_fn = vjp_fn
        self.inputs = inputs          # list[Tensor] (differentiable inputs)
        self.outputs = outputs        # list[weakref to output Tensors]
        # (shape, dtype) per output — lets the engines materialise zero
        # cotangents for outputs whose Tensor has been GC'd (common for
        # unused grads out of a multi-output *_grad node)
        self.out_avals = out_avals
        self.name = name
        # the primal fn returned a tuple/list (vjp then expects the
        # cotangent wrapped in the same structure, even for one output)
        self.out_is_seq = out_is_seq
        # forward restricted to the differentiable args — re-linearized by
        # paddle.grad(create_graph=True) so the backward itself is taped
        # (partial_grad_engine.cc double-grad role)
        self.pure_fn = pure_fn


def _is_float_dtype(d) -> bool:
    return jnp.issubdtype(d, jnp.floating) or jnp.issubdtype(d, jnp.complexfloating)


class Tensor:
    """Eager tensor wrapping a jax.Array (or a jax tracer under to_static).

    API parity target: the reference's dygraph VarBase as surfaced through
    python/paddle/fluid/dygraph/varbase_patch_methods.py (``backward`` :166,
    ``gradient``, ``clear_gradient``) plus the ~200 tensor methods patched in
    python/paddle/tensor/.  Methods are attached by
    ``paddle_tpu.tensor._patch_tensor_methods`` to keep this file small.
    """

    __slots__ = ("_data", "stop_gradient", "_grad", "_node", "_out_index",
                 "name", "persistable", "trainable", "is_leaf_", "_hooks",
                 "__weakref__", "__dict__")

    _name_counter = [0]

    def __init__(self, data, dtype=None, stop_gradient=True, name=None,
                 persistable=False):
        if isinstance(data, Tensor):
            data = data._data
        if not isinstance(data, jax.Array) and not _is_tracer(data):
            # python floats/lists default to the framework dtype (float32);
            # explicit numpy arrays keep their dtype (paddle semantics)
            was_ndarray = isinstance(data, np.ndarray)
            data = np.asarray(data)
            if dtype is None and data.dtype == np.float64 and not was_ndarray:
                data = data.astype(_default_dtype)
            data = jnp.asarray(data, dtype=convert_dtype(dtype))
        elif dtype is not None:
            data = data.astype(convert_dtype(dtype))
        self._data = data
        self.stop_gradient = stop_gradient
        self._grad = None
        self._node = None
        self._out_index = 0
        self.persistable = persistable
        self.trainable = True
        self.is_leaf_ = True
        self._hooks = None
        if name is None:
            Tensor._name_counter[0] += 1
            name = f"generated_tensor_{Tensor._name_counter[0]}"
        self.name = name

    # -- basic properties ---------------------------------------------------
    @property
    def data(self):
        return self._data

    @data.setter
    def data(self, value):
        self._data = value._data if isinstance(value, Tensor) else value

    @property
    def dtype(self):
        return jnp.dtype(self._data.dtype)

    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def size(self):
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    @property
    def is_leaf(self):
        return self._node is None

    @property
    def place(self):
        dev = get_device()
        return _place_of(dev)

    @property
    def grad(self):
        return self._grad

    @grad.setter
    def grad(self, value):
        if value is not None and not isinstance(value, Tensor):
            value = Tensor(value)
        self._grad = value

    def numpy(self) -> np.ndarray:
        return np.asarray(self._data)

    def item(self, *args):
        return np.asarray(self._data).item(*args)

    def tolist(self):
        return np.asarray(self._data).tolist()

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    def __repr__(self):
        grad_str = "stop_gradient=True" if self.stop_gradient else "stop_gradient=False"
        try:
            value = np.asarray(self._data)
            return (f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
                    f"place={self.place}, {grad_str},\n       {value})")
        except Exception:
            return (f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
                    f"{grad_str}, <traced>)")

    def __format__(self, spec):
        if self.ndim == 0:
            return format(self.item(), spec)
        return repr(self)

    def __bool__(self):
        if self.size != 1:
            raise ValueError(
                "The truth value of a Tensor with more than one element is "
                "ambiguous")
        return bool(np.asarray(self._data))

    def __int__(self):
        return int(np.asarray(self._data))

    def __float__(self):
        return float(np.asarray(self._data))

    def __index__(self):
        return int(np.asarray(self._data))

    def __array__(self, dtype=None):
        arr = np.asarray(self._data)
        return arr.astype(dtype) if dtype is not None else arr

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __hash__(self):
        return id(self)

    def __dlpack__(self, *a, **k):
        return self._data.__dlpack__(*a, **k)

    # -- autograd -----------------------------------------------------------
    def register_hook(self, hook: Callable):
        """Gradient hook, parity with VarBase hooks (imperative/hooks.h)."""
        if self._hooks is None:
            self._hooks = []
        self._hooks.append(hook)
        handle = _HookHandle(self._hooks, hook)
        return handle

    def backward(self, grad_tensor=None, retain_graph=False):
        """Reverse sweep (≈ BasicEngine::Execute, basic_engine.cc:305)."""
        from paddle_tpu import autograd as _ag
        _ag.backward_from(self, grad_tensor, retain_graph)

    def gradient(self):
        return None if self._grad is None else self._grad.numpy()

    def clear_gradient(self, set_to_zero=False):
        if set_to_zero and self._grad is not None:
            data = (self._grad._data if isinstance(self._grad, Tensor)
                    else self._grad.to_dense())   # SelectedRows grad
            self._grad = Tensor(jnp.zeros_like(data))
        else:
            self._grad = None

    clear_grad = clear_gradient

    def detach(self) -> "Tensor":
        t = Tensor(self._data, stop_gradient=True, name=self.name + ".detach")
        return t

    def clone(self) -> "Tensor":
        return apply(lambda x: x + 0, self, name="clone")[0] if not (
            self.stop_gradient or not is_grad_enabled()) else Tensor(
                self._data, stop_gradient=self.stop_gradient)

    # -- mutation (leaf only) ----------------------------------------------
    def set_value(self, value):
        if isinstance(value, Tensor):
            value = value._data
        value = jnp.asarray(value, dtype=self.dtype)
        if tuple(value.shape) != tuple(self._data.shape):
            raise ValueError(
                f"set_value shape mismatch: {value.shape} vs {self._data.shape}")
        self._data = value

    def copy_(self, other, blocking=True):
        self.set_value(other)
        return self

    def fill_(self, value):
        self._data = jnp.full_like(self._data, value)
        return self

    def zero_(self):
        self._data = jnp.zeros_like(self._data)
        return self

    # -- device/dtype movement ---------------------------------------------
    def cpu(self):
        return Tensor(jax.device_put(self._data, jax.devices("cpu")[0]),
                      stop_gradient=self.stop_gradient)

    def cuda(self, device_id=0):
        return self.tpu(device_id)

    def tpu(self, device_id=0):
        plat = _accelerator_platform()
        if plat is None:
            return self
        return Tensor(jax.device_put(self._data, jax.devices(plat)[device_id]),
                      stop_gradient=self.stop_gradient)

    def pin_memory(self):
        return self

    def block_until_ready(self):
        if hasattr(self._data, "block_until_ready"):
            self._data.block_until_ready()
        return self


class Parameter(Tensor):
    """Trainable tensor (≈ framework::Parameter / ParamBase).

    ``stop_gradient`` defaults to False; ``trainable`` mirrors the reference's
    ParamAttr.trainable.
    """

    def __init__(self, data, dtype=None, name=None, trainable=True):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable,
                         name=name, persistable=True)
        self.trainable = trainable
        self.is_leaf_ = True

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


class _HookHandle:
    def __init__(self, hooks, hook):
        self._hooks = hooks
        self._hook = hook

    def remove(self):
        try:
            self._hooks.remove(self._hook)
        except ValueError:
            pass


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


# ---------------------------------------------------------------------------
# op application — the single entry point every op goes through
# ---------------------------------------------------------------------------

# amp autocast hook, installed by paddle_tpu.amp when a level is active
# (≈ AmpOperators consultation inside Tracer::TraceOp, amp_auto_cast.cc)
_amp_hook = [None]


# --- eager dispatch cache ---------------------------------------------------
# The reference generated per-op C++ fast paths (core.ops,
# pybind/op_function_generator.cc) so eager dispatch didn't pay python
# overhead per op.  Here the per-op cost is the ``jax.vjp`` re-trace; this
# cache plays the core.ops role: the (forward, vjp) pair is jit-compiled once
# per semantic op and reused.  ``jax.vjp``'s pullback is a pytree (a VJP
# Partial), so it can be *returned from* a jitted forward and *passed into* a
# jitted caller — both sides run compiled after the first hit.
#
# Keying: most functional ops hand ``apply`` a fresh closure per call
# (config baked into cells), so identity keying would never hit.  Instead the
# key is (code object, closure cell values, defaults, kwargs, arg layout,
# grad positions) — semantically equal closures share an entry.  Anything
# non-hashable in cells/args (arrays, per-call RNG keys, mutable objects)
# makes the call uncacheable and it falls back to the direct path.
#
# PURITY REQUIREMENT: a cached fn must be pure in its (args, kwargs, cells,
# defaults) — the key does not see module-level globals, so an op that reads
# mutable global state would have that state frozen into the compiled entry
# at first call.  All in-tree ops satisfy this; custom ops dispatched through
# ``apply`` that read mutable globals must pass the state as an argument or
# disable the cache (FLAGS_eager_op_jit_cache=False).

_OP_CACHE: dict = {}
_OP_CACHE_MAX = 1024
_UNCACHEABLE = object()
# strong refs for identity-keyed singletons (jnp.ufunc instances), so a
# cache key's id() can never be reused by a new object
_PINNED_FNS: dict = {}

# telemetry: monitor counters (STAT_ADD role) — handles resolved once so the
# per-dispatch cost is a single locked int add.  Readable via
# paddle.monitor.get_stat("eager_cache_hit"/"eager_cache_miss"/
# "eager_cache_uncacheable").
_CACHE_STATS = [None]


def _cache_stat(kind_idx):
    stats = _CACHE_STATS[0]
    if stats is None:
        from paddle_tpu.framework.monitor import StatRegistry
        reg = StatRegistry.instance()
        stats = (reg.get("eager_cache_hit"), reg.get("eager_cache_miss"),
                 reg.get("eager_cache_uncacheable"))
        _CACHE_STATS[0] = stats
    stats[kind_idx].increase()


_HIT, _MISS, _UNC = 0, 1, 2


class _Unhashable(Exception):
    pass


def _hash_token(v, depth=0):
    if v is None or isinstance(v, (bool, int, float, str, bytes, type)):
        return v
    if isinstance(v, (tuple, list)):
        return ("t", isinstance(v, tuple),
                tuple(_hash_token(x, depth) for x in v))
    if isinstance(v, dict):
        return ("d", tuple(sorted(
            (k, _hash_token(x, depth)) for k, x in v.items())))
    if isinstance(v, functools.partial):
        return ("p", _fn_token(v.func, depth), _hash_token(v.args, depth),
                _hash_token(v.keywords, depth))
    if isinstance(v, np.dtype):
        return ("dt", str(v))
    if callable(v) and depth < 4:
        return _fn_token(v, depth + 1)
    raise _Unhashable


def _fn_token(fn, depth=0):
    if isinstance(fn, functools.partial):
        return ("p", _fn_token(fn.func, depth), _hash_token(fn.args, depth),
                _hash_token(fn.keywords, depth))
    if getattr(fn, "__self__", None) is not None:
        # bound method: deliberately uncacheable.  An identity key on
        # ``self`` would freeze its *state* into the compiled entry (a
        # Layer's weights at first call), silently violating the purity
        # requirement above — and after the RNG-as-argument fix the
        # measured transformer miss tail contains no bound methods.
        raise _Unhashable
    if isinstance(fn, jnp.ufunc):
        # jnp.ufunc singletons (jnp.add — Tensor.__add__'s op) define
        # __eq__ without __hash__; pin the instance and key by identity.
        # Only module-level jnp singletons qualify — a ufunc minted per
        # call (jnp.frompyfunc) would pin unboundedly and mint a fresh
        # key every call, churning the cache (same policy as the
        # '<locals>' guard below).
        name = getattr(fn, "__name__", "")
        if getattr(jnp, name, None) is not fn:
            raise _Unhashable
        _PINNED_FNS[id(fn)] = fn
        return ("u", name, id(fn))
    code = getattr(fn, "__code__", None)
    if code is None:
        # builtin / PjitFunction singletons (jnp.matmul, jax.nn.relu):
        # identity is stable because the key tuple holds a strong ref.
        # Restrict to module-level names — a callable object minted per
        # call would key by identity and jit-compile on every call.
        if "<locals>" in getattr(fn, "__qualname__", "<locals>"):
            raise _Unhashable
        try:
            hash(fn)
        except TypeError:
            raise _Unhashable from None
        return ("f", fn)
    cells = tuple(_hash_token(c.cell_contents, depth)
                  for c in (fn.__closure__ or ()))
    dflts = _hash_token(fn.__defaults__ or (), depth)
    return ("c", code, cells, dflts)


def _op_cache_key(fn, args, tensor_pos, grad_pos, kwargs):
    """Returns (key, runtime_pos) or None if the call can't be cached."""
    try:
        runtime_pos = []
        arg_sig = []
        tp = set(tensor_pos)
        for i, a in enumerate(args):
            if i in tp or isinstance(a, (jax.Array,)) or (
                    hasattr(a, "shape") and hasattr(a, "dtype")
                    and hasattr(a, "__array__")):
                runtime_pos.append(i)
                arg_sig.append((i, "rt"))
            else:
                arg_sig.append((i, _hash_token(a)))
        key = (_fn_token(fn), tuple(arg_sig), tuple(grad_pos),
               _hash_token(kwargs))
        return key, runtime_pos
    except _Unhashable:
        return None


# compiled pullback caller — caches per (vjp jaxpr treedef, cotangent treedef)
_vjp_call = jax.jit(lambda v, c: v(c))


def _build_op_entry(fn, kwargs, args_template, runtime_pos, grad_pos):
    rt = set(runtime_pos)
    static_args = [None if i in rt else a
                   for i, a in enumerate(args_template)]

    if grad_pos:
        def fwd(rt_arrays):
            full = list(static_args)
            for p, a in zip(runtime_pos, rt_arrays):
                full[p] = a

            def pure(*darrs):
                f2 = list(full)
                for p, d in zip(grad_pos, darrs):
                    f2[p] = d
                return fn(*f2, **kwargs)

            return jax.vjp(pure, *[full[p] for p in grad_pos])
    else:
        def fwd(rt_arrays):
            full = list(static_args)
            for p, a in zip(runtime_pos, rt_arrays):
                full[p] = a
            return fn(*full, **kwargs)
    return jax.jit(fwd)


def _cached_dispatch(fn, frozen, tensor_pos, grad_pos, kwargs):
    """Try the compiled fast path.  Returns (out, vjp_fn_or_None) or None to
    signal the caller to take the direct path."""
    from paddle_tpu.framework.flags import flag
    if not flag("eager_op_jit_cache"):
        return None
    for f in frozen:
        if _is_tracer(f):
            return None  # inside an outer trace: no nested jit, not counted
    keyed = _op_cache_key(fn, frozen, tensor_pos, grad_pos, kwargs)
    if keyed is None:
        _cache_stat(_UNC)
        return None
    key, runtime_pos = keyed
    entry = _OP_CACHE.get(key)
    if entry is _UNCACHEABLE:
        _cache_stat(_UNC)
        return None
    hit = entry is not None
    if entry is None:
        if len(_OP_CACHE) >= _OP_CACHE_MAX:
            for _ in range(_OP_CACHE_MAX // 8):
                _OP_CACHE.pop(next(iter(_OP_CACHE)))
        entry = _build_op_entry(fn, kwargs, frozen, runtime_pos, grad_pos)
        _OP_CACHE[key] = entry
    rt_arrays = [frozen[p] for p in runtime_pos]
    try:
        res = entry(rt_arrays)
    except Exception:
        # value-dependent python control flow etc. — never try again
        _OP_CACHE[key] = _UNCACHEABLE
        _cache_stat(_UNC)
        return None
    _cache_stat(_HIT if hit else _MISS)
    if grad_pos:
        out, vjp = res
        return out, (lambda cts, _v=vjp: _vjp_call(_v, cts))
    return res, None


def _nan_inf_guard(name: str, out):
    """FLAGS_check_nan_inf watcher (reference:
    framework/details/nan_inf_utils.h:28 CheckOpHasNanOrInf, called from
    the executors after every op).  Here it rides the eager tracer entry
    point instead; tracer (in-jit) values are skipped — the jitted tier is
    swept per-step by TrainStep."""
    from paddle_tpu.framework.flags import flag
    if not flag("check_nan_inf"):
        return
    arrs = out if isinstance(out, (tuple, list)) else [out]
    for i, a in enumerate(arrs):
        data = a._data if isinstance(a, Tensor) else a
        if isinstance(data, jax.core.Tracer):
            continue
        if hasattr(data, "dtype") and jnp.issubdtype(data.dtype,
                                                     jnp.inexact):
            if not bool(jnp.isfinite(data).all()):
                raise FloatingPointError(
                    f"Operator {name or 'op'} output {i} contains NaN/Inf "
                    f"(FLAGS_check_nan_inf is set)")


def apply(fn: Callable, *args, name: str = "", nondiff: Sequence[int] = (),
          **kwargs):
    """Run a pure-jax ``fn`` over a mix of Tensors/arrays/python values.

    Replaces ``Tracer::TraceOp`` (tracer.cc:132): executes now, and if grad
    mode is on and any Tensor input requires grad, records a TapeNode whose
    pullback is the eager ``jax.vjp`` of ``fn`` (restricted to the
    differentiable tensor positions).

    Returns a tuple of output Tensors (matching fn's output structure
    flattened); callers unpack.  ``nondiff`` marks positional tensor args to
    exclude from differentiation (e.g. integer indices).
    """
    if _amp_hook[0] is not None:
        args = _amp_hook[0](name or getattr(fn, "__name__", "op"), args)
    tensor_pos = []
    for i, a in enumerate(args):
        if isinstance(a, Tensor):
            tensor_pos.append(i)
    grad_pos = [
        i for i in tensor_pos
        if i not in nondiff and not args[i].stop_gradient
        and _is_float_dtype(args[i].dtype)
    ]
    track = is_grad_enabled() and bool(grad_pos)

    frozen = list(args)
    for i in tensor_pos:
        frozen[i] = frozen[i]._data

    if not track:
        cached = _cached_dispatch(fn, frozen, tensor_pos, (), kwargs)
        if cached is not None:
            out = cached[0]
        else:
            out = fn(*frozen, **kwargs)
        _nan_inf_guard(name or getattr(fn, "__name__", "op"), out)
        return _wrap_outputs(out, stop_gradient=True)

    grad_arrays = [args[i]._data for i in grad_pos]

    def pure(*darrs):
        full = list(frozen)
        for i, arr in zip(grad_pos, darrs):
            full[i] = arr
        return fn(*full, **kwargs)

    cached = _cached_dispatch(fn, frozen, tensor_pos, tuple(grad_pos), kwargs)
    if cached is not None:
        out, vjp_fn = cached
    else:
        out, vjp_fn = jax.vjp(pure, *grad_arrays)
    _nan_inf_guard(name or getattr(fn, "__name__", "op"), out)
    outs = _wrap_outputs(out, stop_gradient=False)
    node = TapeNode(vjp_fn, [args[i] for i in grad_pos],
                    [weakref.ref(t) for t in outs], name=name or getattr(
                        fn, "__name__", "op"),
                    out_is_seq=isinstance(out, (tuple, list)),
                    pure_fn=pure,
                    out_avals=[(t._data.shape, t._data.dtype)
                               for t in outs])
    for idx, t in enumerate(outs):
        t._node = node
        t._out_index = idx
        t.is_leaf_ = False
    return outs


def _wrap_outputs(out, stop_gradient: bool):
    if isinstance(out, (tuple, list)):
        return tuple(
            Tensor(o, stop_gradient=stop_gradient) if not isinstance(o, Tensor)
            else o for o in out)
    return (Tensor(out, stop_gradient=stop_gradient),)


def apply1(fn, *args, **kwargs) -> Tensor:
    """apply() for single-output ops."""
    return apply(fn, *args, **kwargs)[0]

"""Random ops + global Generator.

Replaces the reference's ``framework::Generator`` (reference:
paddle/fluid/framework/generator.h:44 — global/per-device seeded Philox state)
with a stateful wrapper over JAX's counter-based PRNG: a global ``Generator``
holds a PRNGKey and splits per call.  Under ``to_static`` capture the key is
folded in as a constant; jitted training steps that need fresh randomness per
step should thread keys explicitly (see paddle_tpu.jit docs).
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import Tensor, apply1, convert_dtype, get_default_dtype

__all__ = [
    "Generator", "seed", "get_rng_state", "set_rng_state", "default_generator",
    "rand", "randn", "randint", "randint_like", "uniform", "normal",
    "standard_normal", "poisson", "bernoulli", "multinomial", "randperm",
    "uniform_", "normal_", "exponential_",
]


class Generator:
    """Seeded PRNG stream (splitting JAX keys behind a stateful facade)."""

    def __init__(self, seed: int = 0):
        self._lock = threading.Lock()
        self.manual_seed(seed)

    def manual_seed(self, seed: int):
        self._seed = int(seed)
        # built on first use: creating a key starts the backend, and
        # importing the package must not (a launcher or a host-only
        # child that imports it would take the chip from the trainer)
        self._key_arr = None
        return self

    @property
    def _key(self):
        if self._key_arr is None:
            self._key_arr = jax.random.PRNGKey(self._seed)
        return self._key_arr

    @_key.setter
    def _key(self, key):
        self._key_arr = key

    def initial_seed(self) -> int:
        return self._seed

    def split(self):
        with self._lock:
            self._key, sub = jax.random.split(self._key)
        return sub

    def get_state(self):
        return np.asarray(self._key)

    def set_state(self, state):
        self._key = jnp.asarray(state, dtype=jnp.uint32)


default_generator = Generator(0)


def seed(value: int):
    """paddle.seed parity — reseeds the global generator."""
    default_generator.manual_seed(value)
    return default_generator


def get_rng_state():
    return default_generator.get_state()


def set_rng_state(state):
    default_generator.set_state(state)


def _key():
    return default_generator.split()


def _shape_list(shape):
    if isinstance(shape, Tensor):
        return [int(s) for s in shape.numpy().tolist()]
    if isinstance(shape, (int, np.integer)):
        return [int(shape)]
    return [int(s._data) if isinstance(s, Tensor) else int(s) for s in shape]


def rand(shape, dtype=None, name=None) -> Tensor:
    dtype = convert_dtype(dtype) if dtype else convert_dtype(get_default_dtype())
    return Tensor(jax.random.uniform(_key(), _shape_list(shape), dtype=dtype))


def randn(shape, dtype=None, name=None) -> Tensor:
    dtype = convert_dtype(dtype) if dtype else convert_dtype(get_default_dtype())
    return Tensor(jax.random.normal(_key(), _shape_list(shape), dtype=dtype))


standard_normal = randn


def randint(low=0, high=None, shape=(1,), dtype="int64", name=None) -> Tensor:
    if high is None:
        low, high = 0, low
    return Tensor(jax.random.randint(_key(), _shape_list(shape), low, high,
                                     dtype=convert_dtype(dtype)))


def randint_like(x, low=0, high=None, dtype=None, name=None) -> Tensor:
    dtype = dtype or x.dtype
    return randint(low, high, shape=x.shape, dtype=dtype)


def uniform(shape, dtype=None, min=-1.0, max=1.0, seed=0, name=None) -> Tensor:
    dtype = convert_dtype(dtype) if dtype else convert_dtype(get_default_dtype())
    k = jax.random.PRNGKey(seed) if seed else _key()
    return Tensor(jax.random.uniform(k, _shape_list(shape), dtype=dtype,
                                     minval=float(min), maxval=float(max)))


def normal(mean=0.0, std=1.0, shape=None, name=None) -> Tensor:
    if isinstance(mean, Tensor) or isinstance(std, Tensor):
        m = mean._data if isinstance(mean, Tensor) else mean
        s = std._data if isinstance(std, Tensor) else std
        out_shape = jnp.broadcast_shapes(
            jnp.shape(m), jnp.shape(s)) if shape is None else _shape_list(shape)
        z = jax.random.normal(_key(), out_shape,
                              dtype=convert_dtype(get_default_dtype()))
        return Tensor(m + s * z)
    out_shape = _shape_list(shape) if shape is not None else []
    z = jax.random.normal(_key(), out_shape,
                          dtype=convert_dtype(get_default_dtype()))
    return Tensor(mean + std * z)


def poisson(x, name=None) -> Tensor:
    return Tensor(jax.random.poisson(_key(), x._data).astype(x.dtype))


def bernoulli(x, name=None) -> Tensor:
    return Tensor(jax.random.bernoulli(_key(), x._data).astype(x.dtype))


def multinomial(x, num_samples=1, replacement=False, name=None) -> Tensor:
    probs = x._data
    logits = jnp.log(jnp.maximum(probs, 1e-30))
    if replacement:
        out = jax.random.categorical(_key(), logits, axis=-1,
                                     shape=(num_samples,) + probs.shape[:-1])
        out = jnp.moveaxis(out, 0, -1)
    else:
        # Gumbel top-k trick for sampling without replacement
        g = jax.random.gumbel(_key(), probs.shape)
        out = jax.lax.top_k(logits + g, num_samples)[1]
    return Tensor(out.astype(jnp.int64))


def randperm(n, dtype="int64", name=None) -> Tensor:
    return Tensor(jax.random.permutation(_key(), n).astype(convert_dtype(dtype)))


# in-place variants (leaf mutation)
def uniform_(x, min=-1.0, max=1.0, seed=0, name=None):
    x._data = jax.random.uniform(_key(), x._data.shape, dtype=x._data.dtype,
                                 minval=float(min), maxval=float(max))
    return x


def normal_(x, mean=0.0, std=1.0, name=None):
    x._data = mean + std * jax.random.normal(_key(), x._data.shape,
                                             dtype=x._data.dtype)
    return x


def exponential_(x, lam=1.0, name=None):
    u = jax.random.uniform(_key(), x._data.shape, dtype=x._data.dtype)
    x._data = -jnp.log(1.0 - u) / lam
    return x

"""Shared Pallas kernel helpers."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def backend_is_tpu() -> bool:
    """The one backend gate behind every kernel module's ``supported()``:
    Mosaic lowers for the TPU only (tests flip the module's ``_INTERPRET``
    instead)."""
    return jax.default_backend() == "tpu"


def no_x64():
    """Context manager forcing 32-bit trace semantics for a kernel call.

    The package enables jax_enable_x64 globally (paddle parity), but
    Mosaic rejects 64-bit types — index maps and iotas traced under x64
    would emit i64."""
    return jax.enable_x64(False)


def dot_nt(a, b):
    """a (m, d) · b (n, d) → (m, n): contraction over the trailing dim with
    f32 accumulation — keeps bf16 inputs on the MXU's fast path instead of
    casting to f32 first (which quarters MXU throughput on v5e)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)

"""paddle.device namespace (parity: python/paddle/device.py — 2.x home
of set_device/get_device and the is_compiled_with_* probes)."""
from __future__ import annotations

import os
import re

import jax

from paddle_tpu.core import (device_count, get_device,  # noqa: F401
                             set_device)

__all__ = ["set_device", "get_device", "device_count",
           "is_compiled_with_cuda", "is_compiled_with_xpu",
           "is_compiled_with_npu", "is_compiled_with_tpu",
           "get_cudnn_version", "XPUPlace", "use_compile_cache"]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  Every entry point that compiles for the chip calls this
    before its first compile (``chip_smoke.py``, ``benchmarks/run.py``,
    the ``tools/`` scripts); the library and the tests do not.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
    directory and this names no other.  Otherwise the cache is
    ``<checkout>/.jax_cache`` — a fixed path with no pid, time or
    temporary name in it, so every process finds the same entries.

    The cache's key includes the operations' metadata.  By default jax
    strips it, and an executable read back carries the ``op_name`` paths
    and source lines of whatever code first compiled that computation:
    a profile of this checkout would show another checkout's
    ``jax.named_scope`` regions, or none (measured, PR 25: BERT-base
    compiled before the scopes existed read back without them).  File
    names enter the key relative to the checkout, so that a second
    checkout of the same code still hits."""
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(checkout + os.sep))
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(checkout, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def is_compiled_with_cuda() -> bool:
    return False                      # TPU build


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    import paddle_tpu
    return paddle_tpu.is_compiled_with_tpu()


def get_cudnn_version():
    return None                       # no cuDNN in the TPU build


def XPUPlace(dev_id: int = 0):
    from paddle_tpu.core import XPUPlace as _P
    return _P(dev_id)

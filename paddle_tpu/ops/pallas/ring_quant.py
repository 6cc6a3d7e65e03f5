"""Per-row wire quantizer as a Pallas kernel — the codec leg of the
fused ring collectives (``parallel/ring.py``).

The fused ring spends its per-hop compute on ``wire.py``'s blocked row
codec: per-row abs-max scale, scaled round-half-to-even, clip to the
wire's quantized range.  XLA fuses that expression tree well enough on
CPU, but on TPU the ring wants the encode of chunk ``t`` to run while
chunk ``t-1`` rides the ``ppermute`` — a single fused kernel keeps the
whole encode (reduce + divide + round + clip + cast) in VMEM with one
read of the chunk, the shape the overlap schedule needs.

Layout follows the pallas guide's quantization pattern and the
``fused_adam.py`` conventions: ``(rows, D)`` blocks tiled over rows
with ``D`` a multiple of the 128-lane width, scalars as an ``(8, 1)``
block, scales emitted as a lane-broadcast ``(rows, 128)`` block (column
0 is the value — a ``(rows, 1)`` output would violate the minimum f32
tile).  int8 emits the quantized bytes directly; int4 emits int8
values in ``[-7, 7]`` and the nibble pack stays a jnp epilogue (bit
packing changes the trailing width, which Pallas blocks cannot).

Semantics are pinned to ``wire.quantize_rows_traced``: the kernel is
bitwise-identical to the traced twin in interpret mode (the
differential oracle in the tests), so swapping it in changes nothing
but the schedule.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.distributed.wire import (COLLECTIVE_WIRE_DTYPES,
                                         _pack_nibbles, normalize_wire,
                                         quantize_rows_traced)
from paddle_tpu.ops.pallas.common import backend_is_tpu, no_x64

BLOCK_ROWS = 256
_LANES = 128
# f32 input tile budget: Mosaic keeps the double-buffered in/out blocks
# plus a few tile-sized temporaries, and the default scoped VMEM is
# 16 MiB — a (256, 16384) tile alone is 16 MiB and does not compile
_TILE_BYTES = 2 * 1024 * 1024
_INT8_SUBLANES = 32
_MAX_WIDTH = _TILE_BYTES // (4 * _INT8_SUBLANES)

# tests flip this to run in interpreter mode on CPU
_INTERPRET = False


def supported() -> bool:
    return backend_is_tpu() or _INTERPRET


def _block_rows(d: int) -> int:
    """Rows per block: BLOCK_ROWS where the f32 tile fits the budget,
    fewer for wide rows, in multiples of the int8 sublane tile."""
    fit = _TILE_BYTES // (4 * d) // _INT8_SUBLANES * _INT8_SUBLANES
    return max(_INT8_SUBLANES, min(BLOCK_ROWS, fit))


def _rowquant_kernel(x_ref, s_ref, q_ref, sc_ref):
    qmax = s_ref[0, 0]
    x = x_ref[...]
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / qmax
    scale = jnp.where(scale > 0.0, scale, 1.0)
    q_ref[...] = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(
        jnp.int8)
    sc_ref[...] = jnp.broadcast_to(scale, (x.shape[0], _LANES))


def _kernel_quant(rows, qmax: float):
    """One fused pass over ``(R, D)`` f32 rows → (q int8, scale f32)
    with ``R`` padded to the row-block multiple (pad rows are zero →
    scale 1, q 0 — sliced back off before returning)."""
    from jax.experimental import pallas as pl

    r, d = rows.shape
    block = _block_rows(d)
    rows_p = -(-r // block) * block
    x = rows.astype(jnp.float32)
    if rows_p != r:
        x = jnp.pad(x, ((0, rows_p - r), (0, 0)))
    scalars = jnp.full((8, 1), jnp.float32(qmax))
    with no_x64():
        q, sc = pl.pallas_call(
            _rowquant_kernel,
            grid=(rows_p // block,),
            in_specs=[pl.BlockSpec((block, d), lambda i: (i, 0)),
                      pl.BlockSpec((8, 1), lambda i: (0, 0))],
            out_specs=[pl.BlockSpec((block, d), lambda i: (i, 0)),
                       pl.BlockSpec((block, _LANES), lambda i: (i, 0))],
            out_shape=[jax.ShapeDtypeStruct((rows_p, d), jnp.int8),
                       jax.ShapeDtypeStruct((rows_p, _LANES),
                                            jnp.float32)],
            name="ring_quant",
            interpret=_INTERPRET,
        )(x, scalars)
    return q[:r], sc[:r, 0]


def ring_quant_rows(rows, wire: str, force: bool = False):
    """Kernel-accelerated twin of ``wire.quantize_rows_traced`` on
    ``(R, D)`` rows.  The traced jnp codec serves off-TPU, the cast
    wires (no per-row scale to fuse), widths off the 128-lane grid and
    rows wider than ``_MAX_WIDTH`` (no 32-row tile of them fits the
    VMEM budget); ``force=True`` waives the backend check only (the
    abstract-trace hook the analysis zoo uses)."""
    wire = normalize_wire(wire, known=COLLECTIVE_WIRE_DTYPES)
    if wire not in ("int8", "int4") or rows.ndim != 2 \
            or rows.shape[-1] % _LANES or rows.shape[-1] > _MAX_WIDTH \
            or not (supported() or force):
        return quantize_rows_traced(rows, wire)
    q, scale = _kernel_quant(rows, 7.0 if wire == "int4" else 127.0)
    if wire == "int4":
        return (_pack_nibbles(q, jnp), scale)
    return (q, scale)


def xla_reference(rows, wire: str):
    """Unfused reference — the traced wire codec itself."""
    return quantize_rows_traced(rows, wire)

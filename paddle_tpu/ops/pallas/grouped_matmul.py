"""Grouped matmuls over an expert-sorted row buffer (Pallas, TPU).

The routed experts of ``nn/functional/moe.py`` each multiply the rows
routed to them by their own weights.  The rows lie in one buffer, sorted
by expert, every expert's group starting on a row-tile boundary, so that a
row tile belongs to one expert and the kernels need no mask:

    rows    ``x (R, K)``, ``R = n_tiles * tile_rows``
    weights ``w (E, K, N)``                (or ``(E, N, K)``, transposed)
    ``row_token (R,)``         the token each row was taken from (int32)
    ``tile_group (n_tiles,)``  the expert of each row tile (int32)
    ``tiles_used (1,)``        how many tiles the routing filled (int32)

The buffer has room for the worst routing; ``tiles_used`` is a run-time
value and the **grid's extent**, so a kernel runs as many steps as the
routing filled tiles and the work follows the load while every shape
stays static.  What a tile past ``tiles_used`` holds is undefined, and
nothing reads it: no operation outside these kernels touches a buffer of
``R`` rows.

``gather_rows``    ``out[row] = src[row_token[row]]``, optionally times a
                   per-row gate and with the row's dot product with a
                   second buffer (the gate's gradient); the source stays
                   whole in VMEM, in float32, or a block of its columns
                   at a time where the whole would not fit
                   (``_resident_cols``).
``group_rows``     ``out[tile] = f(x[tile]) . w[tile_group[tile]]``; the
                   grid walks the row tiles innermost, so an expert's
                   weights are fetched once for all its tiles.
``group_weights``  ``out[e] = sum over e's tiles of f(x[tile])^T .
                   y[tile]``: the weight gradient, in the weights' own
                   ``(E, K, N)`` layout.  Every expert owns at least one
                   tile (the caller's plan), so every ``out[e]`` is
                   written; rows of padding must be zero in ``y``.
``scatter_rows``   ``out[row_token[row]] += rows[row] * gate[row]`` in
                   float32, the result whole in VMEM, or a block of its
                   columns at a time, as the gather's source; rows of
                   padding must add zero (they land on the last token).

``f`` is the ``prologue``: the identity (``""``), the square
(``"square"``: the latent experts' ``relu^2`` keeps ``relu`` and squares
it on the way in) or SwiGLU (``"swiglu"``: ``x = [a | b]``, twice the
contracted width, and ``f(x) = silu(a) * b``, computed in float32 on the
way in); ``group_rows`` can end
in ``relu``, in ``* 2 * m`` (``relu^2``'s derivative) or in ``dswiglu``,
SwiGLU's derivative: from ``d = dy . w^T`` and ``m = [a | b]`` it writes
``[d * b * silu'(a) | d * silu(a)]``, twice the width of ``w``'s rows, each
half's block of ``d`` computed by its own grid step.  bf16 or float32
operands, float32 accumulation.

Each kernel is traced once a process for each distinct (shapes, variant)
and replayed at every site (``common.traced_once``; the flash nest does
the same through ``jax.jit``'s cache): a stack of unrolled, checkpointed
layers calls them at many sites, in three passes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.common import (backend_is_tpu, no_x64,
                                          traced_once)

__all__ = ["supported", "TILE_ROWS", "gather_rows", "group_rows",
           "group_weights", "scatter_rows"]

_INTERPRET = False

TILE_ROWS = 256
# a block of weights, double-buffered beside its rows and its result,
# stays well inside the VMEM a kernel is given
_WEIGHT_BLOCK_BYTES = 2 << 20
# what ``gather_rows`` reads from and ``scatter_rows`` adds into lies whole
# in VMEM as (tokens, width) float32
_RESIDENT_BYTES = 24 << 20
_VMEM_LIMIT_BYTES = 48 << 20


def supported(tokens: int, k: int, n: int, dtype) -> bool:
    """Whether rows of ``tokens`` tokens, ``k`` wide, run through these
    kernels with weights ``(k, n)`` and ``(n, k)``: both widths lane
    multiples, enough tokens to fill a row tile, the tokens' float32
    copy within ``_RESIDENT_BYTES`` whole or by blocks of columns, a float
    dtype the MXU takes."""
    if not (backend_is_tpu() or _INTERPRET):
        return False
    return (k % 128 == 0 and n % 128 == 0 and tokens >= TILE_ROWS
            and _resident_cols(tokens, k) is not None
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def _resident_cols(tokens: int, width: int):
    """The columns of a (tokens, width) float32 operand that
    ``gather_rows`` and ``scatter_rows`` hold in VMEM at once: all of them
    where they fit ``_RESIDENT_BYTES`` (one copy, outside the pipeline);
    else the widest lane-multiple divisor of ``width`` whose block fits
    twice (the pipeline double-buffers it); None where none does."""
    if tokens * width * 4 <= _RESIDENT_BYTES:
        return width
    fits = [cols for cols in range(128, width, 128)
            if width % cols == 0 and 2 * tokens * cols * 4 <= _RESIDENT_BYTES]
    return max(fits, default=None)


def _block_cols(k: int, n: int, itemsize: int) -> int:
    """The widest lane-multiple divisor of ``n`` whose ``(k, cols)`` block
    of weights stays inside ``_WEIGHT_BLOCK_BYTES``."""
    best = 128
    for cols in range(128, n + 1, 128):
        if n % cols == 0 and k * cols * itemsize <= _WEIGHT_BLOCK_BYTES:
            best = cols
    return best


def _params(*semantics):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _each_row(token_ref, tokens: int, body, tile_axis: int = 0):
    """``body(row of the tile, its token)`` for the tile's rows in turn,
    eight to a loop step; a row of padding (token ``tokens``) reads and
    writes the last token.  The tile is the grid's axis ``tile_axis``."""
    from jax.experimental import pallas as pl

    first = pl.program_id(tile_axis) * TILE_ROWS

    def step(block, carry):
        for k in range(8):
            i = block * 8 + k
            body(i, jnp.minimum(token_ref[first + i], tokens - 1))
        return carry

    jax.lax.fori_loop(0, TILE_ROWS // 8, step, 0)


def _gather_kernel(token_ref, src_ref, *rest, gated, tile_axis=0):
    from jax.experimental import pallas as pl

    if gated:
        gate_ref, other_ref, o_ref, dot_ref, buf_ref = rest
    else:
        o_ref, buf_ref = rest

    def take(i, token):
        buf_ref[pl.ds(i, 1), :] = src_ref[pl.ds(token, 1), :]

    _each_row(token_ref, src_ref.shape[0], take, tile_axis)
    rows = buf_ref[...]
    if gated:
        dot = jnp.sum(rows * other_ref[...].astype(jnp.float32), axis=1,
                      keepdims=True)
        # by blocks of columns, each block's part of the dot product
        dot_ref[...] = dot[None] if tile_axis else dot
        rows = rows * gate_ref[...]
    o_ref[...] = rows.astype(o_ref.dtype)


@traced_once(static_argnums=(5, 6))
def _gather_call(row_token, tiles_used, src, gate, other, out_dtype,
                 interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, width = row_token.shape[0], src.shape[1]
    gated = gate is not None
    cols = _resident_cols(src.shape[0], width)
    if cols != width:
        return _gather_blocked(row_token, tiles_used, src, gate, other,
                               out_dtype, interpret, cols)
    tile = pl.BlockSpec((TILE_ROWS, width), lambda i, t: (i, 0))
    column = pl.BlockSpec((TILE_ROWS, 1), lambda i, t: (i, 0))
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    out_shape = jax.ShapeDtypeStruct((rows, width), out_dtype)
    with no_x64():
        return pl.pallas_call(
            functools.partial(_gather_kernel, gated=gated),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(tiles_used[0],),
                in_specs=[whole] + ([column, tile] if gated else []),
                out_specs=[tile, column] if gated else tile,
                scratch_shapes=[pltpu.VMEM((TILE_ROWS, width),
                                           jnp.float32)]),
            out_shape=[out_shape, jax.ShapeDtypeStruct((rows, 1),
                                                       jnp.float32)]
            if gated else out_shape,
            compiler_params=_params("arbitrary"), interpret=interpret,
            name="gather_rows",
        )(row_token, src, *((gate, other) if gated else ()))


def _gather_blocked(row_token, tiles_used, src, gate, other, out_dtype,
                    interpret, cols):
    """``_gather_call`` a block of ``cols`` columns at a time: the grid
    walks the tiles inside each block of columns, so each block of the
    source is fetched once; the gate's dot product comes back by blocks,
    summed here over a (blocks, R, 1) array."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (tokens, width), rows = src.shape, row_token.shape[0]
    gated, blocks = gate is not None, width // cols
    tile = pl.BlockSpec((TILE_ROWS, cols), lambda j, i, t: (i, j))
    column = pl.BlockSpec((TILE_ROWS, 1), lambda j, i, t: (i, 0))
    source = pl.BlockSpec((tokens, cols), lambda j, i, t: (0, j))
    out_shape = jax.ShapeDtypeStruct((rows, width), out_dtype)
    with no_x64():
        out = pl.pallas_call(
            functools.partial(_gather_kernel, gated=gated, tile_axis=1),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(blocks, tiles_used[0]),
                in_specs=[source] + ([column, tile] if gated else []),
                out_specs=[tile, pl.BlockSpec(
                    (1, TILE_ROWS, 1), lambda j, i, t: (j, i, 0))]
                if gated else tile,
                scratch_shapes=[pltpu.VMEM((TILE_ROWS, cols),
                                           jnp.float32)]),
            out_shape=[out_shape, jax.ShapeDtypeStruct((blocks, rows, 1),
                                                       jnp.float32)]
            if gated else out_shape,
            compiler_params=_params("arbitrary", "arbitrary"),
            interpret=interpret, name="gather_rows",
        )(row_token, src, *((gate, other) if gated else ()))
    return [out[0], jnp.sum(out[1], axis=0)] if gated else out


def gather_rows(src, row_token, tiles_used, *, gate=None, other=None,
                out_dtype=None):
    """``out[row] = src[row_token[row]]`` for ``src`` (tokens, width)
    float32.  With ``gate`` (R, 1) float32 and ``other`` (R, width):
    ``(out * gate, sum(out * other, axis=1))``, the second (R, 1)
    float32."""
    return _gather_call(row_token, tiles_used, src, gate, other,
                        jnp.dtype(out_dtype or src.dtype), _INTERPRET)


def _scatter_kernel(token_ref, rows_ref, gate_ref, o_ref, buf_ref,
                    tile_axis=0):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(tile_axis) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    # one row at a time is read from float32 alone: a packed dtype keeps
    # two rows to a sublane
    buf_ref[...] = rows_ref[...].astype(jnp.float32) * gate_ref[...]

    def add(i, token):
        o_ref[pl.ds(token, 1), :] += buf_ref[pl.ds(i, 1), :]

    _each_row(token_ref, o_ref.shape[0], add, tile_axis)


@traced_once(static_argnums=(4, 5))
def _scatter_call(row_token, tiles_used, rows, gate, tokens, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    width = rows.shape[1]
    cols = _resident_cols(tokens, width)
    if cols != width:
        # a block of columns at a time, the tiles inside each block
        with no_x64():
            return pl.pallas_call(
                functools.partial(_scatter_kernel, tile_axis=1),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(width // cols, tiles_used[0]),
                    in_specs=[
                        pl.BlockSpec((TILE_ROWS, cols),
                                     lambda j, i, t: (i, j)),
                        pl.BlockSpec((TILE_ROWS, 1), lambda j, i, t: (i, 0))],
                    out_specs=pl.BlockSpec((tokens, cols),
                                           lambda j, i, t: (0, j)),
                    scratch_shapes=[pltpu.VMEM((TILE_ROWS, cols),
                                               jnp.float32)]),
                out_shape=jax.ShapeDtypeStruct((tokens, width), jnp.float32),
                compiler_params=_params("arbitrary", "arbitrary"),
                interpret=interpret, name="scatter_rows",
            )(row_token, rows, gate)
    with no_x64():
        return pl.pallas_call(
            _scatter_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(tiles_used[0],),
                in_specs=[
                    pl.BlockSpec((TILE_ROWS, width), lambda i, t: (i, 0)),
                    pl.BlockSpec((TILE_ROWS, 1), lambda i, t: (i, 0))],
                out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
                scratch_shapes=[pltpu.VMEM((TILE_ROWS, width),
                                           jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((tokens, width), jnp.float32),
            compiler_params=_params("arbitrary"), interpret=interpret,
            name="scatter_rows",
        )(row_token, rows, gate)


def scatter_rows(rows, gate, row_token, tiles_used, tokens: int):
    """(tokens, width) float32: ``out[row_token[row]] += rows[row] *
    gate[row]``, ``gate`` (R, 1) float32."""
    return _scatter_call(row_token, tiles_used, rows, gate, tokens,
                         _INTERPRET)


def _prologue(x, prologue: str):
    """``f`` of the module's text on a tile of rows, in their dtype."""
    if prologue == "square":
        return x * x
    if prologue == "swiglu":
        half = x.shape[1] // 2
        a = x[:, :half].astype(jnp.float32)
        return (a * jax.nn.sigmoid(a)
                * x[:, half:].astype(jnp.float32)).astype(x.dtype)
    return x


def _rows_kernel(group_ref, x_ref, w_ref, *rest, transpose_w, prologue,
                 epilogue):
    from jax.experimental import pallas as pl

    if epilogue == "times_2m":
        m_ref, o_ref = rest
    elif epilogue == "dswiglu":
        a_ref, b_ref, o_ref = rest
    else:
        o_ref = rest[0]
    x = _prologue(x_ref[...], prologue)
    contract = (((1,), (1 if transpose_w else 0,)), ((), ()))
    acc = jax.lax.dot_general(x, w_ref[0], contract,
                              preferred_element_type=jnp.float32)
    if epilogue == "relu":
        acc = jnp.maximum(acc, 0.0)
    elif epilogue == "times_2m":
        acc = acc * (2.0 * m_ref[...].astype(jnp.float32))
    elif epilogue == "dswiglu":
        a = a_ref[...].astype(jnp.float32)
        sig = jax.nn.sigmoid(a)
        # the first half of the grid's column blocks writes the gradient
        # of ``a``, the second that of ``b``
        first = pl.program_id(0) < pl.num_programs(0) // 2
        b = b_ref[...].astype(jnp.float32)
        acc = acc * jnp.where(first, b * sig * (1.0 + a * (1.0 - sig)),
                              a * sig)
    o_ref[...] = acc.astype(o_ref.dtype)


@traced_once(static_argnums=(5, 6, 7, 8, 9))
def _rows_call(tile_group, tiles_used, x, w, m, transpose_w, prologue,
               epilogue, out_dtype, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, width = x.shape
    k = width // 2 if prologue == "swiglu" else width
    n = w.shape[1] if transpose_w else w.shape[2]
    cols = _block_cols(k, n, w.dtype.itemsize)
    x_spec = pl.BlockSpec((TILE_ROWS, width), lambda j, i, g: (i, 0))
    o_spec = pl.BlockSpec((TILE_ROWS, cols), lambda j, i, g: (i, j))
    # ``dswiglu`` writes two halves of n columns each: its grid walks the
    # ``half`` column blocks of ``w`` twice, and ``m``'s two halves come
    # in apart
    half = n // cols
    col = (lambda j: j % half) if epilogue == "dswiglu" else (lambda j: j)
    if transpose_w:
        w_spec = pl.BlockSpec((1, cols, k),
                              lambda j, i, g: (g[i], col(j), 0))
    else:
        w_spec = pl.BlockSpec((1, k, cols),
                              lambda j, i, g: (g[i], 0, col(j)))
    operands, in_specs = [x, w], [x_spec, w_spec]
    blocks = half
    if epilogue == "times_2m":
        operands.append(m)
        in_specs.append(o_spec)
    elif epilogue == "dswiglu":
        operands += [m, m]
        in_specs += [
            pl.BlockSpec((TILE_ROWS, cols), lambda j, i, g: (i, j % half)),
            pl.BlockSpec((TILE_ROWS, cols),
                         lambda j, i, g: (i, j % half + half))]
        n, blocks = 2 * n, 2 * half
    with no_x64():
        return pl.pallas_call(
            functools.partial(_rows_kernel, transpose_w=transpose_w,
                              prologue=prologue, epilogue=epilogue),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(blocks, tiles_used[0]),
                in_specs=in_specs, out_specs=o_spec),
            out_shape=jax.ShapeDtypeStruct((rows, n), out_dtype),
            compiler_params=_params("parallel", "arbitrary"),
            interpret=interpret, name="group_rows",
        )(tile_group, *operands)


def group_rows(x, w, tile_group, tiles_used, *, transpose_w: bool = False,
               prologue: str = "", epilogue: str = "", m=None,
               out_dtype=None):
    """``out[tile] = f(x[tile]) . w[g]`` (``w[g]^T`` where
    ``transpose_w``), ``g = tile_group[tile]``; ``epilogue`` is ``""``,
    ``"relu"``, ``"times_2m"`` (``m`` shaped like the result) or
    ``"dswiglu"`` (``m`` twice as wide as ``w[g]``'s result, and so is the
    result)."""
    return _rows_call(tile_group, tiles_used, x, w, m, transpose_w,
                      prologue, epilogue, jnp.dtype(out_dtype or x.dtype),
                      _INTERPRET)


def _weights_kernel(group_ref, x_ref, y_ref, o_ref, acc_ref, *, prologue):
    from jax.experimental import pallas as pl

    i, last = pl.program_id(1), pl.num_programs(1) - 1
    group = group_ref[i]

    @pl.when(jnp.logical_or(
        i == 0, group_ref[jnp.maximum(i - 1, 0)] != group))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = _prologue(x_ref[...], prologue)
    acc_ref[...] += jax.lax.dot_general(
        x, y_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_or(
        i == last, group_ref[jnp.minimum(i + 1, last)] != group))
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@traced_once(static_argnums=(4, 5, 6, 7))
def _weights_call(tile_group, tiles_used, x, y, groups, prologue,
                  out_dtype, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    width, n = x.shape[1], y.shape[1]
    k = width // 2 if prologue == "swiglu" else width
    cols = _block_cols(k, n, 4)          # the float32 accumulator
    with no_x64():
        return pl.pallas_call(
            functools.partial(_weights_kernel, prologue=prologue),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(n // cols, tiles_used[0]),
                in_specs=[
                    pl.BlockSpec((TILE_ROWS, width), lambda j, i, g: (i, 0)),
                    pl.BlockSpec((TILE_ROWS, cols), lambda j, i, g: (i, j))],
                out_specs=pl.BlockSpec((1, k, cols),
                                       lambda j, i, g: (g[i], 0, j)),
                scratch_shapes=[pltpu.VMEM((k, cols), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((groups, k, n), out_dtype),
            compiler_params=_params("parallel", "arbitrary"),
            interpret=interpret, name="group_weights",
        )(tile_group, x, y)


def group_weights(x, y, tile_group, tiles_used, groups: int, *,
                  prologue: str = "", out_dtype=None):
    """``out[e] = sum over the tiles of e of f(x[tile])^T . y[tile]``,
    shaped ``(groups, f(x) columns, y columns)``."""
    return _weights_call(tile_group, tiles_used, x, y, groups, prologue,
                         jnp.dtype(out_dtype or x.dtype), _INTERPRET)

"""The two row movements of a held share of a routed layer, as Mosaic
kernels: a row moves by one DMA, and only if it exists.

`take_rows`: out (C, E), row r = x[tokens[r]] for r < n_valid and zero for
the rest (`ops/moe.py:_take`: dispatch forward, combine backward).
`sum_rows`: out (T, E), token t = the float32 sum over its k choices j with
slot[t, j] < C of rows[slot[t, j]] (times scale[t, j] if given), in the
order of the choices, in the rows' type (`ops/moe.py:_sum_into_tokens`:
combine forward, dispatch backward).

**A row that one DMA can move.**  In the tiled layout a (rows, E) array
has in HBM, a row is E / 128 pieces, 4 KB apart, and a bfloat16 row shares
its words with its neighbour: Mosaic copies no such slice.  So the source
is handed over as (rows, 1, E') words of 32 bits, which XLA lays out row
after row (`_pack`: one elementwise pass; a bfloat16 row's two halves side
by side in a word's two halves, so that taking them apart again is a shift
and a mask of whole lane tiles and the values never change).

**The kernels.**  The source stays in HBM (`memory_space=ANY`); the grid
walks tiles of result rows, whose indices come a tile at a time through
SMEM; each row that exists is one `make_async_copy` of its E' words into a
VMEM scratch, all of a tile's copies in flight at once on one semaphore
(started `_ISSUE` to a turn of the loop, which took a sixth off both
kernels: the scalar unit reads the next indices meanwhile), and the rows
that do not exist (past ``n_valid``; a choice whose expert is another
chip's) are never fetched.  Then the tile's rows are read out of the
scratch 16 at a time, taken apart, and written (`take_rows`; zeros past
``n_valid``) or summed over the choices in float32 (`sum_rows`) into the
result's block in the layout and type XLA expects.

**What the shape decides** (`_tile`).  The kernels take rows of a whole
number of 128-lane tiles in bfloat16 or float32 whose count divides into
tiles; every other shape gets None and its caller runs the XLA form, which
is also what any platform but a TPU runs beyond the interpreter's sizes
(`ops.by_platform`).

Counts itself on the job timeline as the step is traced:
`moe.row_kernel_passes`, the calls the kernels took, and
`moe.row_kernel_declined`, those that went to XLA by their shape.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import by_platform, interpreted
from ray_tpu.util import tracing

_LANE = 128
_F32, _U32 = jnp.float32, jnp.uint32
# rows the kernels read out of their scratch at a time: one tile of
# bfloat16 rows, and columns of them: (16, 512) words are 8 registers
_SUB, _COLS = 16, 512
# result rows of a grid step, the most
_TAKE_TILE = 256
_SUM_TILE = 128
_PACK_TILE = 256
# copies started in a turn of the loop that starts them
_ISSUE = 8

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary",), vmem_limit_bytes=48 << 20)


def _tile(rows, E, dtype, most) -> Optional[int]:
    """The result rows of a grid step, or None for a shape the kernels
    decline: a row that is no whole number of lane tiles, a type that is
    neither bfloat16 nor float32, rows that do not divide into tiles of a
    multiple of `_SUB`."""
    tile = min(most, rows)
    if E % _LANE or dtype not in (jnp.bfloat16, jnp.float32) \
            or rows % tile or tile % _SUB:
        return None
    return tile


def _words(E, dtype) -> int:
    """32-bit words of a packed row: E float32, or the wider half of a
    bfloat16 row in whole lane tiles."""
    return E if dtype == jnp.float32 else -(-E // (2 * _LANE)) * _LANE


def _pack_kernel(n_ref, x_ref, out_ref):
    tile, E = x_ref.shape
    H = out_ref.shape[2]
    bits = lambda a: jax.lax.bitcast_convert_type(a.astype(_F32), _U32)

    @pl.when(pl.program_id(0) * tile < jnp.maximum(n_ref[0], 1))
    def _():
        def body(r0):
            at = pl.ds(r0, _SUB)
            for c, width in _columns(H):
                # a bfloat16 is the high half of its float32
                low = bits(x_ref[at, pl.ds(c, width)]) >> 16
                there = min(width, E - H - c)   # the high halves that exist
                if there > 0:
                    out_ref[at, 0, pl.ds(c, there)] = low[:, :there] | bits(
                        x_ref[at, pl.ds(H + c, there)])
                if there < width:
                    there = max(there, 0)
                    out_ref[at, 0, pl.ds(c + there, width - there)] = \
                        low[:, there:]

        _over_rows(tile, body)


def _pack(x, n_valid, interpret):
    """(rows, E) -> (rows, 1, E') uint32, a row's words one after another
    in HBM: float32 as it is; bfloat16 columns [0, E') in the low halves
    and [E', E) in the high halves, zeros behind them, by a kernel that
    reads and writes only the tiles that hold a row before ``n_valid``
    (the words of the others are whatever they were: nobody fetches
    them)."""
    rows, E = x.shape
    if x.dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(x, _U32).reshape(rows, 1, E)
    H = _words(E, x.dtype)
    tile = min(_PACK_TILE, rows)
    held = lambda i, n: jnp.minimum(i, jnp.maximum(n[0] - 1, 0) // tile)
    return pl.pallas_call(
        _pack_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // tile,),
            in_specs=[pl.BlockSpec((tile, E), lambda i, n: (held(i, n), 0))],
            out_specs=pl.BlockSpec((tile, 1, H),
                                   lambda i, n: (held(i, n), 0, 0))),
        out_shape=jax.ShapeDtypeStruct((rows, 1, H), _U32),
        compiler_params=_COMPILER_PARAMS, interpret=interpret,
    )(jnp.asarray(n_valid, jnp.int32).reshape(1), x)


def _unpacked(words, start, E, dtype):
    """What `_pack` made of columns [start, start + width) of its words,
    (rows, width) uint32 -> [(first column, float32 values)], each a whole
    number of lane tiles of the (rows, E) array."""
    if dtype == jnp.float32:
        return [(start, jax.lax.bitcast_convert_type(words, _F32))]
    H = _words(E, dtype)
    width = words.shape[1]
    low = jax.lax.bitcast_convert_type(words << 16, _F32)
    high = jax.lax.bitcast_convert_type(words & _U32(0xFFFF0000), _F32)
    there = min(width, E - H - start)       # the high halves that exist
    return [(start, low)] + ([(H + start, high[:, :there])] if there > 0
                             else [])


def _columns(words):
    return [(c, min(_COLS, words - c)) for c in range(0, words, _COLS)]


def _over_rows(rows, body):
    """``body(first row)`` for each `_SUB` rows of a tile, in a loop that
    is not unrolled: the columns inside it are."""
    def step(i, carry):
        body(pl.multiple_of(i * _SUB, _SUB))
        return carry

    jax.lax.fori_loop(0, rows // _SUB, step, 0)


def _start_each(n, start):
    """``start(i)`` for i < n, `_ISSUE` to a turn of the loop: the scalar
    unit reads the next rows' indices while it hands a copy over."""
    def some(g, carry):
        for u in range(_ISSUE):
            start(g * _ISSUE + u)
        return carry

    def one(i, carry):
        start(i)
        return carry

    whole = n // _ISSUE
    jax.lax.fori_loop(0, whole, some, 0)
    jax.lax.fori_loop(whole * _ISSUE, n, one, 0)


def _wait(n, source, half, sem):
    """Wait for ``n`` started copies of one row of ``source`` into rows of
    ``half`` (rows) -> a view of the scratch: a semaphore counts bytes, so
    `_SUB` of them at a time and the rest one by one."""
    def wait(rows):
        def step(_, carry):
            pltpu.make_async_copy(source.at[pl.ds(0, rows)], half(rows),
                                  sem).wait()
            return carry
        return step

    jax.lax.fori_loop(0, n // _SUB, wait(_SUB), 0)
    jax.lax.fori_loop(0, n % _SUB, wait(1), 0)


def _a_tile_ahead(fetch, now, ahead):
    """The grid's step i starts tile i + 1's copies (``fetch(indices,
    tile)``, into the half of the scratch that tile i - 1 was read out
    of) before it waits for its own, which step i - 1 started: the copies
    run while a tile is read out and written."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        fetch(now, 0)

    @pl.when(i + 1 < pl.num_programs(0))
    def _():
        fetch(ahead, i + 1)


def _take_kernel(n_ref, tokens_ref, ahead_ref, x_ref, out_ref, buf, sem):
    tile, E = out_ref.shape
    i = pl.program_id(0)
    held = lambda step: jnp.clip(n_ref[0] - step * tile, 0, tile)

    def fetch(tokens, step):
        _start_each(held(step), lambda r: pltpu.make_async_copy(
            x_ref.at[pl.ds(tokens[0, r], 1)],
            buf.at[step % 2, pl.ds(r, 1)], sem.at[step % 2]).start())

    _a_tile_ahead(fetch, tokens_ref, ahead_ref)
    n, half = held(i), i % 2
    _wait(n, x_ref, lambda rows: buf.at[half, pl.ds(0, rows)], sem.at[half])

    @pl.when(n == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(n > 0)
    def _():
        def body(r0):
            at = pl.ds(r0, _SUB)
            valid = r0 + jax.lax.broadcasted_iota(
                jnp.int32, (_SUB, 1), 0) < n
            for c, width in _columns(buf.shape[3]):
                words = jnp.where(valid, buf[half, at, 0, pl.ds(c, width)],
                                  0)
                for first, values in _unpacked(words, c, E, out_ref.dtype):
                    out_ref[at, pl.ds(first, values.shape[1])] = \
                        values.astype(out_ref.dtype)

        _over_rows(tile, body)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _take(x, tokens, n_valid, *, tile, interpret=False):
    C, E = tokens.shape[0], x.shape[1]
    H = _words(E, x.dtype)
    return pl.pallas_call(
        _take_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(C // tile,),
            in_specs=[pl.BlockSpec((1, tile), lambda i, n: (0, i),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec((1, tile), lambda i, n: (
                          0, jnp.minimum(i + 1, C // tile - 1)),
                          memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, E), lambda i, n: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, tile, 1, H), _U32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((C, E), x.dtype),
        compiler_params=_COMPILER_PARAMS, interpret=interpret,
    )(n_valid.reshape(1).astype(jnp.int32), *[tokens.reshape(1, C)] * 2,
      _pack(x, x.shape[0], interpret))


def _take_reference(x, tokens, n_valid):
    """`take_rows` in plain XLA: what the kernel is held to and what a
    shape it declines runs."""
    valid = jnp.arange(tokens.shape[0], dtype=jnp.int32) < n_valid
    return jnp.where(valid[:, None], x[tokens], 0)


def _hits(slot, C, tile):
    """slot (T, k) -> (the choices that are among the C rows, a tile of
    tokens at a time and those first: their rows and their places in the
    kernel's scratch, choice by choice a tile of tokens, each (T / tile,
    1, tile * k) int32; how many a tile has (T / tile,)).  A sort of
    tile * k words a tile, the place and the row in fields of one word."""
    T, k = slot.shape
    row_bits = (C - 1).bit_length()
    token = jnp.arange(T, dtype=jnp.int32)[:, None] % tile
    choice = jnp.arange(k, dtype=jnp.int32)[None]
    there = slot < C
    word = jnp.where(there, ((choice * tile + token) << row_bits) | slot,
                     jnp.iinfo(jnp.int32).max)
    word = jnp.sort(word.reshape(T // tile, 1, tile * k), axis=-1)
    return (word & ((1 << row_bits) - 1), word >> row_bits,
            jnp.sum(there.reshape(T // tile, tile * k), axis=1,
                    dtype=jnp.int32))


def _sum_kernel(n_ref, row_ref, place_ref, row_ahead, place_ahead, at_ref,
                *rest, C, E, scaled):
    scale_ref = rest[0] if scaled else None
    rows_ref, out_ref, buf, sem = rest[scaled:]
    tile, k = at_ref.shape
    i = pl.program_id(0)

    def fetch(hits, step):
        row, place = hits
        _start_each(n_ref[step], lambda h: pltpu.make_async_copy(
            rows_ref.at[pl.ds(row[0, 0, h], 1)],
            buf.at[step % 2, pl.ds(place[0, 0, h], 1)],
            sem.at[step % 2]).start())

    _a_tile_ahead(fetch, (row_ref, place_ref), (row_ahead, place_ahead))
    half = i % 2
    _wait(n_ref[i], rows_ref, lambda rows: buf.at[half, pl.ds(0, rows)],
          sem.at[half])

    def body(r0):
        at = pl.ds(r0, _SUB)
        there = at_ref[at, :] < C                   # (_SUB, k)
        scale = scale_ref[at, :] if scaled else None
        for c, width in _columns(buf.shape[3]):
            totals = None
            for j in range(k):
                parts = _unpacked(
                    buf[half, pl.ds(j * tile + r0, _SUB), 0,
                        pl.ds(c, width)], c, E, out_ref.dtype)
                firsts = [first for first, _ in parts]
                values = [values * scale[:, j:j + 1] if scaled else values
                          for _, values in parts]
                # 0 + the first, as the XLA form has it (-0 becomes 0)
                totals = [(0 if totals is None else totals[i])
                          + jnp.where(there[:, j:j + 1], part, 0)
                          for i, part in enumerate(values)]
            for first, total in zip(firsts, totals):
                out_ref[at, pl.ds(first, total.shape[1])] = total.astype(
                    out_ref.dtype)

    _over_rows(tile, body)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _sum(rows, n_valid, slot, scale=None, *, tile, interpret=False):
    (C, E), (T, k) = rows.shape, slot.shape
    H = _words(E, rows.dtype)
    scaled = scale is not None
    row, place, n_hits = _hits(slot, C, tile)
    by_token = pl.BlockSpec((tile, k), lambda i, n: (i, 0))
    hits = lambda ahead: pl.BlockSpec(
        (1, 1, tile * k), lambda i, n: (
            jnp.minimum(i + ahead, T // tile - 1), 0, 0),
        memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_sum_kernel, C=C, E=E, scaled=scaled),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(T // tile,),
            in_specs=[hits(0), hits(0), hits(1), hits(1),
                      by_token, *([by_token] if scaled else []),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, E), lambda i, n: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, k * tile, 1, H), _U32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((T, E), rows.dtype),
        compiler_params=_COMPILER_PARAMS, interpret=interpret,
    )(n_hits, row, place, row, place, slot, *([scale] if scaled else []),
      _pack(rows, n_valid, interpret))


def _sum_reference(rows, n_valid, slot, scale=None):
    """`sum_rows` in plain XLA: a gather of T rows for each of the k
    choices and one sum, which XLA:TPU fuses; nothing with T*k rows
    exists."""
    C = rows.shape[0]
    total = 0
    for j in range(slot.shape[1]):
        row = rows[jnp.minimum(slot[:, j], C - 1)].astype(_F32)
        if scale is not None:
            row = row * scale[:, j][:, None]
        total = total + jnp.where((slot[:, j] < C)[:, None], row, 0)
    return total.astype(rows.dtype)


def _counted(tile, first) -> bool:
    """Whether the kernel takes this call, told to the job timeline."""
    runs = bool(tile) and (interpreted(first)
                           or jax.default_backend() == "tpu")
    tracing.count("moe.row_kernel_passes", int(runs))
    tracing.count("moe.row_kernel_declined", int(tile is None))
    return tile is not None


def take_rows(x, tokens, n_valid):
    """x (T, E); tokens (C,) int32; n_valid a scalar -> (C, E) in x's
    type: row r is x[tokens[r]] for r < n_valid and zero for the rest."""
    E = x.shape[1]
    tile = _tile(tokens.shape[0], E, x.dtype, _TAKE_TILE)
    if _tile(x.shape[0], E, x.dtype, _PACK_TILE) is None:
        tile = None
    if not _counted(tile, x):
        return _take_reference(x, tokens, n_valid)
    return by_platform(functools.partial(_take, tile=tile), _take_reference,
                       x, tokens, jnp.asarray(n_valid, jnp.int32))


def sum_rows(rows, n_valid, slot, scale=None):
    """rows (C, E), of which only those before ``n_valid`` are ever
    chosen; slot (T, k) int32, C and more for a choice that is not among
    the rows; scale (T, k) float32 or None -> (T, E) in the rows' type:
    each token's rows, times their scales if given, summed in float32 in
    the order of its choices."""
    (C, E), (T, k) = rows.shape, slot.shape
    tile = _tile(T, E, rows.dtype, _SUM_TILE)
    # the buffer has to divide into `_pack`'s tiles too, and a hit's two
    # fields (its place in the scratch, its row) to fit one sorted word
    if _tile(C, E, rows.dtype, _PACK_TILE) is None or (tile and (
            tile * k - 1).bit_length() + (C - 1).bit_length() > 31):
        tile = None
    if not _counted(tile, rows):
        return _sum_reference(rows, n_valid, slot, scale)
    operands = (slot,) if scale is None else (slot, scale)
    return by_platform(functools.partial(_sum, tile=tile), _sum_reference,
                       rows, jnp.asarray(n_valid, jnp.int32), *operands)

"""A causal depthwise convolution with a bias and an optional SiLU over the
(B, S, C) layout: a Pallas (Mosaic) kernel a pass, forward and backward,
under one `jax.custom_vjp`.

    out_t = act(sum_j w_j * v_{t-(K-1)+j} + b),   zeros before the sequence

v (B, S, W) with the C channels of the convolution its columns ``start`` to
``start + C`` (Mamba-2's xBC lies in W_in's result [z | xBC | dt]: the
kernels read those columns where they lie and XLA slices nothing out for
them); w (C, K), one filter a channel; b (C,).  The taps' products, the
sums and the activation in float32, the results in v's type.  ``widths``
cuts the C channels into several results (Mamba-2's x, B and C), each
written by a call of its own over its own columns: a grid step of one call
over all of them would flush every result's block whether it wrote it or
not, and a call's one result is its FIRST, the widest (B, S, .) array it
makes, which is what the benchmark's shape readers look at (no head-major
(., S, 128) array, no (rows, E) array of a grouped product).  The kernels
know K and the activation as static arguments and nothing of Mamba.

**Forward.**  A grid over (batch, tiles of rows, blocks of channels).  A
step lands a (rows, lanes) tile of v and, through a second `BlockSpec` on
the same array, the `_SUB` rows before it: the HALO, of which the last
K - 1 are read (zeros for a sequence's first tile).  Inside, a loop takes
`_SUB` rows at a time and carries them to the next turn as the rows behind
it; the shifted rows come from the two in the registers, a select and a
roll along the sublanes a tap, never from a padded copy.

**Backward.**  Residuals: the inputs only (v, which a Mamba-2 layer's plan
keeps as W_in's result, w, b); the pre-activation is made again from the
tile that is read anyway.  With dpre = dy * act'(pre):

    dv_t = sum_j w_j * dpre_{t+(K-1)-j},      d b = sum_t dpre_t,
    d w[:, j] = sum_t dpre_t * v_{t-(K-1)+j}

dv looks AHEAD, so the grid walks a channel block's row tiles from the
sequence's end (`arbitrary`, the innermost axis, batch outside it) and a
tile leaves its first `_SUB` rows of dpre in a scratch for the tile before
it; inside, the loop walks the same way and carries dpre.  The taps' and
the bias's partial sums ride the loop in registers, eight rows each, and
are added once a step into a float32 block that stays in VMEM while a
channel block's tiles pass; XLA adds up the eight rows.  No float32
(B, S, C) array exists in HBM in either pass.  v's gradient is dv with
zeros around it, padded in v's own shape so that XLA folds it into the sum
with the array's other parts' gradients (`ops/gated_norm.py` does the same).

**What the shape decides** (`_taken`, `_blocks`).  The kernels take a call
whose sequence divides into row tiles of a multiple of `_SUB` and whose
``start`` and every width are whole 128-lane blocks.  Any other runs
`_reference`, the K shifted multiply-adds in plain jax that the kernels are
tested and timed beside, which is also what a platform that is no TPU runs
beyond the interpreter's sizes (`ops.by_platform`).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import by_platform, interpreted

_LANE = 128
_F32 = jnp.float32
# rows the kernels' inner loop takes at a time, and the rows of the halo:
# one tile of bfloat16 rows, two of float32
_SUB = 16
# a pass's grid step: (rows of its tile at most, channels of its block at
# most, `_SUB` rows a turn of the inner loop written out).  The largest of
# `_ROW_TILES` and `_CHANNEL_BLOCKS` under them that divide the call's.
# The forward is at the speed of a plain pass at any of the forms tried
# (0.70-0.72 ms a layer at (2, 8192, 6144) against 0.49 of bytes at the
# HBM's peak); the backward holds a dozen float32 values a turn and is
# bound by a turn's chain of dependent operations, which four turns written
# out overlap, on 256 lanes (4 registers a value): `tools/chip_kernels.py
# --cases conv_8k`, PERF.md section 6, PR 62
_FORWARD = (512, 1024, 1)
_BACKWARD = (2048, 256, 4)
_ROW_TILES = (2048, 1024, 512, 256, 128, 64, 32, 16)
_CHANNEL_BLOCKS = (1024, 512, 256, 128)

ACTIVATIONS = (None, "silu")


def _act(pre, activation):
    """-> (act(pre), act'(pre)) in float32."""
    if activation is None:
        return pre, jnp.ones_like(pre)
    s = jax.nn.sigmoid(pre)
    return pre * s, s * (1 + pre * (1 - s))


def _reference(v, w, b, start, activation):
    """The rule in plain jax over v's columns from ``start``: K shifted
    multiply-adds in float32, each shift one `pad` with a negative high
    edge that XLA:TPU fuses into the pass that reads it."""
    C, K = w.shape
    x = v[..., start:start + C]

    def back(k):
        return (x if k == 0 else jax.lax.pad(
            x, jnp.zeros((), x.dtype),
            ((0, 0, 0), (k, -k, 0), (0, 0, 0)))).astype(_F32)

    pre = sum(w[:, j].astype(_F32) * back(K - 1 - j) for j in range(K)) \
        + b.astype(_F32)
    return _act(pre, activation)[0].astype(v.dtype)


def _blocks(S, start, width, most) -> Optional[Tuple[int, int]]:
    """-> (rows of a tile, channels of a block) of a pass whose limits are
    ``most``, or None for a shape the kernels decline: a sequence that is
    no whole number of `_SUB` rows, a ``start`` or a width that is no whole
    number of 128-lane blocks."""
    tile = next((t for t in _ROW_TILES
                 if t <= most[0] and S % t == 0), None)
    block = next((c for c in _CHANNEL_BLOCKS if c <= most[1]
                  and start % c == 0 and width % c == 0), None)
    return None if tile is None or block is None else (tile, block)


def _behind(cur, prev, k, row):
    """Row t holds row t - k of [prev; cur], `_SUB` rows each."""
    if k == 0:
        return cur
    return pltpu.roll(jnp.where(row >= _SUB - k, prev, cur), k, 0)


def _ahead(cur, nxt, k, row):
    """Row t holds row t + k of [cur; nxt]."""
    if k == 0:
        return cur
    return pltpu.roll(jnp.where(row < k, nxt, cur), _SUB - k, 0)


def _pre(shifted, taps, bias):
    """sum_j w_j * v_{t-(K-1)+j} + b from the K shifts of v, shifted[k] row
    t's v_{t-k}; the sum in `_reference`'s order."""
    K = len(taps)
    return sum(taps[j] * shifted[K - 1 - j] for j in range(K)) + bias


def _rows(i):
    """The ``i``-th `_SUB` rows of a tile."""
    return pl.ds(pl.multiple_of(i * _SUB, _SUB), _SUB)


def _forward_kernel(v_ref, halo_ref, w_ref, b_ref, out_ref, *, activation,
                    unroll):
    _, tile, lanes = v_ref.shape
    K = w_ref.shape[0]
    unroll = min(unroll, tile // _SUB)
    row = jax.lax.broadcasted_iota(jnp.int32, (_SUB, lanes), 0)
    taps, bias = [w_ref[j:j + 1] for j in range(K)], b_ref[...]

    def turn(i, prev):
        # ``unroll`` times `_SUB` rows, each the next one's rows behind
        for j in range(unroll):
            at = _rows(i * unroll + j)
            cur = v_ref[0, at].astype(_F32)
            pre = _pre([_behind(cur, prev, k, row) for k in range(K)],
                       taps, bias)
            out_ref[0, at] = _act(pre, activation)[0].astype(out_ref.dtype)
            prev = cur
        return prev

    halo = halo_ref[0].astype(_F32)
    jax.lax.fori_loop(
        0, tile // _SUB // unroll, turn,
        jnp.where(pl.program_id(1) == 0, jnp.zeros_like(halo), halo))


def _backward_kernel(v_ref, halo_ref, dy_ref, w_ref, b_ref, dv_ref, sums_ref,
                     ahead_ref, *, activation, unroll):
    _, tile, lanes = v_ref.shape
    K = w_ref.shape[0]
    unroll = min(unroll, tile // _SUB)
    turns = tile // _SUB // unroll
    row = jax.lax.broadcasted_iota(jnp.int32, (_SUB, lanes), 0)
    taps, bias = [w_ref[j:j + 1] for j in range(K)], b_ref[...]
    # the grid's last axis counts a sequence's tiles from its end
    ends = pl.program_id(2) == 0
    starts = pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when((pl.program_id(1) == 0) & ends)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    zero = jnp.zeros((_SUB, lanes), _F32)
    halo = jnp.where(starts, zero, halo_ref[0].astype(_F32))

    def turn(i, carry):
        # ``unroll`` times `_SUB` rows from the tile's end on, each read
        # with the rows behind it (the halo behind the tile's first) and
        # handing its dpre to the rows before it
        nxt, sums = carry
        for j in range(unroll):
            n = (turns - 1 - i) * unroll + unroll - 1 - j
            cur = v_ref[0, _rows(n)].astype(_F32)
            prev = jnp.where(n == 0, halo, v_ref[
                0, _rows(jnp.maximum(n - 1, 0))].astype(_F32))
            shifted = [_behind(cur, prev, k, row) for k in range(K)]
            dpre = dy_ref[0, _rows(n)].astype(_F32) * _act(
                _pre(shifted, taps, bias), activation)[1]
            dv_ref[0, _rows(n)] = sum(
                taps[k] * _ahead(dpre, nxt, K - 1 - k, row)
                for k in range(K)).astype(dv_ref.dtype)
            # `_SUB` rows folded onto 8 by whole registers, the 8 left to
            # XLA: the K taps' sums, then the bias's
            parts = [dpre * shifted[K - 1 - k] for k in range(K)] + [dpre]
            sums = [total + part[:8] + part[8:]
                    for total, part in zip(sums, parts)]
            nxt = dpre
        return nxt, sums

    dpre, sums = jax.lax.fori_loop(
        0, turns, turn,
        (jnp.where(ends, zero, ahead_ref[...]), [zero[:8]] * (K + 1)))
    ahead_ref[...] = dpre
    for k, total in enumerate(sums):
        sums_ref[k] += total


def _compiler_params(semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=32 << 20)


def _taps_operands(w, b):
    """w (C, K), b (C,) as the kernels' blocks cut them: (K, C) and (1, C)
    float32."""
    return w.astype(_F32).T, b.astype(_F32).reshape(1, -1)


@functools.partial(jax.jit, static_argnames=(
    "start", "activation", "interpret"))
def _forward(v, w, b, *, start, activation, interpret=False):
    """v (B, S, W), w (C, K), b (C,) -> (B, S, C) in v's type: the
    convolution of v's columns from ``start``."""
    B, S, _ = v.shape
    C, K = w.shape
    tile, block = _blocks(S, start, C, _FORWARD)
    first, halos = start // block, tile // _SUB
    return pl.pallas_call(
        functools.partial(_forward_kernel, activation=activation,
                          unroll=_FORWARD[2]),
        grid=(B, S // tile, C // block),
        in_specs=[
            pl.BlockSpec((1, tile, block), lambda n, i, c: (n, i, first + c)),
            pl.BlockSpec((1, _SUB, block), lambda n, i, c: (
                n, jnp.maximum(i * halos - 1, 0), first + c)),
            pl.BlockSpec((K, block), lambda n, i, c: (0, c)),
            pl.BlockSpec((1, block), lambda n, i, c: (0, c))],
        out_specs=pl.BlockSpec((1, tile, block), lambda n, i, c: (n, i, c)),
        out_shape=jax.ShapeDtypeStruct((B, S, C), v.dtype),
        compiler_params=_compiler_params(("parallel",) * 3),
        interpret=interpret,
    )(v, v, *_taps_operands(w, b))


@functools.partial(jax.jit, static_argnames=(
    "start", "activation", "interpret"))
def _backward(v, w, b, dy, *, start, activation, interpret=False):
    """-> (dv, dw, db), each in its primal's shape and type: dv is 0
    outside the convolution's columns."""
    B, S, W = v.shape
    C, K = w.shape
    tile, block = _blocks(S, start, C, _BACKWARD)
    first, halos, tiles = start // block, tile // _SUB, S // tile
    at = lambda i: tiles - 1 - i
    dv, sums = pl.pallas_call(
        functools.partial(_backward_kernel, activation=activation,
                          unroll=_BACKWARD[2]),
        grid=(C // block, B, tiles),
        in_specs=[
            pl.BlockSpec((1, tile, block),
                         lambda c, n, i: (n, at(i), first + c)),
            pl.BlockSpec((1, _SUB, block), lambda c, n, i: (
                n, jnp.maximum(at(i) * halos - 1, 0), first + c)),
            pl.BlockSpec((1, tile, block), lambda c, n, i: (n, at(i), c)),
            pl.BlockSpec((K, block), lambda c, n, i: (0, c)),
            pl.BlockSpec((1, block), lambda c, n, i: (0, c))],
        out_specs=[
            pl.BlockSpec((1, tile, block), lambda c, n, i: (n, at(i), c)),
            pl.BlockSpec((K + 1, 8, block), lambda c, n, i: (0, 0, c))],
        out_shape=[jax.ShapeDtypeStruct((B, S, C), v.dtype),
                   jax.ShapeDtypeStruct((K + 1, 8, C), _F32)],
        scratch_shapes=[pltpu.VMEM((_SUB, block), _F32)],
        compiler_params=_compiler_params(
            ("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(v, v, dy, *_taps_operands(w, b))
    sums = jnp.sum(sums, axis=1)
    # padded in v's own shape, as the cotangent of a slice of it is
    dv = jnp.pad(dv, ((0, 0), (0, 0), (start, W - start - C)))
    return dv, sums[:K].T.astype(w.dtype), sums[K].astype(b.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _kernels(v, w, b, start, activation):
    return _kernels_fwd(v, w, b, start, activation)[0]


def _kernels_fwd(v, w, b, start, activation):
    static = dict(start=start, activation=activation)
    out = by_platform(functools.partial(_forward, **static),
                      functools.partial(_reference, **static), v, w, b)
    return out, (v, w, b)


def _kernels_bwd(start, activation, inputs, dy):
    static = dict(start=start, activation=activation)

    def reference(v, w, b, dy):
        return jax.vjp(functools.partial(_reference, **static),
                       v, w, b)[1](dy)

    return by_platform(functools.partial(_backward, **static), reference,
                       *inputs, dy)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def _widths(w, widths):
    return (w.shape[0],) if widths is None else tuple(widths)


def _taken(v, w, start, widths) -> bool:
    """Whether the kernels take the shape: taps the halo's rows cover, and
    rows and channels that divide into blocks (`_blocks`: the forward's
    decide, the backward's divide whatever those do)."""
    at = [start + sum(widths[:i]) for i in range(len(widths))]
    return w.shape[1] - 1 <= _SUB and all(
        _blocks(v.shape[1], first, width, _FORWARD)
        for first, width in zip(at, widths))


def takes(v, w, start: int = 0,
          widths: Optional[Sequence[int]] = None) -> bool:
    """Whether the kernels make this call where it runs: a shape they take
    (`_taken`), on a TPU or at a size another platform interprets."""
    return _taken(v, w, start, _widths(w, widths)) and (
        interpreted(v) or jax.default_backend() == "tpu")


def causal_conv(v, w, b, activation: Optional[str] = None, start: int = 0,
                widths: Optional[Sequence[int]] = None):
    """-> the convolution of v's C columns from ``start`` (B, S, C) in v's
    type, or with ``widths`` (which sum to C) a tuple of its columns cut to
    them: the rule above, by the kernels where the shape lets them
    (`_taken`) and by `_reference` elsewhere.  v (B, S, ``start`` + C or
    more), w (C, K), b (C,); ``activation`` one of `ACTIVATIONS`."""
    if activation not in ACTIVATIONS:
        raise ValueError(f"activation {activation!r}: one of {ACTIVATIONS}")
    cut = _widths(w, widths)
    if sum(cut) != w.shape[0]:
        raise ValueError(
            f"widths {cut} do not sum to the taps' {w.shape[0]} channels")
    one = _kernels if _taken(v, w, start, cut) else _reference
    outs, at = [], 0
    for width in cut:
        outs.append(one(v, w[at:at + width], b[at:at + width], start + at,
                        activation))
        at += width
    return outs[0] if widths is None else tuple(outs)

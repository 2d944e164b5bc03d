"""The grouped matmul of a routed layer's experts, as Mosaic kernels:
rows (R, K) sorted by group x stacks (n, K, N), a matrix a group, by
group_sizes (n,) -> (R, N), row r times the matrix of the group it lies in.
Only the first sum(group_sizes) rows belong to a group; the result's other
rows are zeros and nothing of them is fetched or multiplied.

Three products under one `custom_vjp` (`grouped_matmul`), which keeps the
rows, the stacks and the walk and nothing else:

  forward         rows[g] @ stacks[g]            (`_gmm`)
  rows' gradient  dy[g] @ stacks[g]^T            (`_gmm`, transposed: the
                  contraction runs over the stack's last axis inside the
                  kernel, and no transposed copy of the stacks exists)
  stacks' gradient  rows[g]^T @ dy[g]            (`_tgmm`: float32 in VMEM
                  over a group's row tiles, written once a group)

**The walk** (`_walk`).  The rows are cut into tiles of `tile` rows and the
kernels' inner grid axis walks VISITS, a (group, row tile) pair each, in
row order: a tile that a group boundary crosses is visited once for each
group with the other groups' rows masked, a group of no rows is visited
once (its gradient is written as zeros), and behind the last group's last
visit each tile that holds no group's row is visited once to be written as
zeros, its inputs not fetched (the index maps stay on the last tile that
holds a row).  R / tile + n visits are enough under any sizes.  The walk is
a few hundred int32 made from group_sizes by small XLA operations, once for
a layer's three stacks and its forward, replay and backward (`over`), and
handed to the kernels through scalar prefetch.

**What the shape decides** (`_plan`).  Row tiles of `_ROW_TILES`, the first
that divides the rows; the contraction whole in VMEM, so a group's matrix is fetched
once a group and not once a row tile (the visits are the inner axis and
consecutive visits of a group keep its block), and the rows are read once;
the other axis of the matrix whole as well unless the blocks would pass
`_VMEM_BUDGET`, and then in the fewest equal parts of whole lane tiles that
fit.  Inside a visit `_gmm` multiplies 128 rows at a turn of a loop and skips
those with no row of the visit's group (`_SUB`).  The kernels take K and N of whole 128-lane tiles, bfloat16 or float32
(operands as they come, float32 accumulation, the result in the rows'
type: what `jax.lax.ragged_dot` does), and rows that divide into tiles;
every other shape runs `jax.lax.ragged_dot`, which is also what any
platform but a TPU runs beyond the interpreter's sizes (`ops.by_platform`).

Counts itself on the job timeline as the step is traced:
`moe.grouped_kernel_passes`, the products the kernels took, and
`moe.grouped_kernel_declined`, those that went to `ragged_dot` by their
shape.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import by_platform, interpreted
from ray_tpu.util import tracing

LANE = 128
_F32 = jnp.float32
# rows of a tile, the first that divides the rows.  A boundary visit of the
# stacks' gradient costs a whole tile of work and a grid step a third of a
# microsecond, and 256 wins or ties from 768-row groups to 2,048-row ones
# (forward of an up product with the tile multiplied whole, ms on a v5e at
# 128 / 256 / 512, PERF.md §6, PR 59: mellum2's 16 groups in 65,536 rows
# 0.937 / 0.915 / 0.967, kanana's 16 in 24,576 0.325 / 0.320 / 0.366;
# OLMoE's 64 in 131,072 3.462 / 3.360, nemotron's 8 in 12,288 0.489 / 0.491)
_ROW_TILES = (256, 128)
# bytes of a kernel's blocks (inputs and results twice, for the pipeline,
# and the float32 scratch) above which the matrix's other axis is cut
_VMEM_BUDGET = 56 << 20
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=96 << 20)
# Mosaic writes a product out tile by tile, and a kernel's text lies in HBM
# once a CALL SITE (sixty a routed layer), so `_gmm` multiplies a tile
# `_SUB` rows at a turn of a loop, which also skips the sub-blocks with no
# row of the visit's group (a layer's six products at mellum2's shape 5.98
# ms for 5.98 whole, 0.21 MB of text a kernel at kanana's shape for 0.36),
# and `_tgmm`'s masked product, one visit in three to nine, walks K
# `_MASKED_STEP` rows at a turn (0.66 MB for 0.89; the unmasked one in such
# a loop read 3.21 ms a layer for 2.72, so it stays whole)
_SUB = 128
_MASKED_STEP = 512


class Plan(NamedTuple):
    """What `_plan` chose for rows (R, K) x stacks (n, K, N): the rows of
    a tile, and the columns of a block of each product's result (the
    forward's N, the rows' gradient's K, the stacks' gradient's N)."""
    tile: int
    forward: int
    transposed: int
    stacks: int


def _row_tile(R) -> Optional[int]:
    """The rows of a tile for R rows, or None where no tile divides
    them."""
    for tile in _ROW_TILES:
        if R % tile == 0:
            return tile
    # fewer rows than the smallest tile (the tests' sizes): one tile of
    # whole sublane tiles
    return R if R < _ROW_TILES[-1] and R % 16 == 0 else None


def _columns(width, fits) -> Optional[int]:
    """The widest block of whole lane tiles that divides ``width`` into
    equal parts and ``fits(block)``."""
    lanes = width // LANE
    for parts in range(1, lanes + 1):
        if lanes % parts == 0 and fits(width // parts):
            return width // parts
    return None


def _plan(R, K, N, dtype, tile=None) -> Optional[Plan]:
    """The tiles of the three products, or None for a shape the kernels
    decline: a K or N that is no whole number of lane tiles, a type that is
    neither bfloat16 nor float32, rows that no tile divides."""
    if K % LANE or N % LANE or dtype not in (jnp.bfloat16, jnp.float32):
        return None
    tile = tile or _row_tile(R)
    if not tile:
        return None
    size = jnp.dtype(dtype).itemsize

    def gmm(contracted):
        # rows, the matrix and the result twice, the float32 product once
        return lambda block: 2 * size * (
            tile * contracted + contracted * block + tile * block) \
            + 4 * tile * block <= _VMEM_BUDGET

    def tgmm(block):
        # both operands and the result twice, the float32 sum once
        return 2 * size * (tile * K + tile * block + K * block) \
            + 4 * K * block <= _VMEM_BUDGET

    blocks = (_columns(N, gmm(K)), _columns(K, gmm(N)), _columns(N, tgmm))
    return Plan(tile, *blocks) if all(blocks) else None


def _walk(group_sizes, R, tile):
    """-> (group (V,), tile (V,), bounds (n + 3,)) int32, V = R / tile + n:
    the group and the row tile of each visit, in row order; bounds[g] and
    bounds[g + 1] the first row of group g and the one past its last,
    bounds[n + 1] the last tile that holds a group's row (0 where none
    does), which the index maps of the inputs stay on, and bounds[n + 2]
    the visits that are a group's: those behind them multiply nothing."""
    n, tiles = group_sizes.shape[0], R // tile
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tile, tiles - 1)
    last = jnp.where(sizes > 0, (ends - 1) // tile, first)
    until = jnp.cumsum(last - first + 1)        # visits up to each group's
    visit = jnp.arange(tiles + n, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(until, visit, side="right"),
                        n - 1).astype(jnp.int32)
    inside = first[group] + visit - (until - (last - first + 1))[group]
    held = -(-ends[-1] // tile)                 # tiles that hold a row
    behind = held + visit - until[-1]           # the tiles of no group
    row_tile = jnp.where(visit < until[-1], inside, behind)
    bounds = jnp.concatenate([
        jnp.zeros((1,), jnp.int32), ends, jnp.maximum(held - 1, 0)[None],
        until[-1:]])
    return group, jnp.minimum(row_tile, tiles - 1).astype(jnp.int32), bounds


def _here(group_ref, tile_ref, bound_ref, rows):
    """Of this visit: (its group, the first of the tile's rows that is the
    group's, the one past the last), the rows counted from the tile's
    first; no row for a visit behind the groups'."""
    v = pl.program_id(1)
    g, t = group_ref[v], tile_ref[v]
    lo = jnp.maximum(bound_ref[g] - t * rows, 0)
    hi = jnp.minimum(bound_ref[g + 1] - t * rows, rows)
    return g, lo, jnp.where(v < bound_ref[bound_ref.shape[0] - 1], hi, lo)


def _first_of(ref, v):
    """Whether visit v is the first of its run of equal values in ``ref``
    (a tile's visits, a group's)."""
    return (v == 0) | (ref[jnp.maximum(v - 1, 0)] != ref[v])


def _in_group(lo, hi, rows):
    at = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    return (at >= lo) & (at < hi)


def _loop(n, step, body):
    """``body(first)`` for each ``step`` of ``n``, in a loop that is not
    unrolled (`_SUB`)."""
    def turn(i, carry):
        body(pl.multiple_of(i * step, step))
        return carry

    jax.lax.fori_loop(0, n // step, turn, 0)


def _gmm_kernel(group_ref, tile_ref, bound_ref, x_ref, w_ref, out_ref, *,
                transposed):
    rows, width = out_ref.shape
    sub = min(_SUB, rows)
    _, lo, hi = _here(group_ref, tile_ref, bound_ref, rows)
    # a tile's first visit writes the rows of no group as zeros; a later
    # one keeps what the groups before it wrote
    first = _first_of(tile_ref, pl.program_id(1))

    def part(r0):
        at = pl.ds(r0, sub)
        some = (lo < r0 + sub) & (hi > r0)

        @pl.when(some)
        def _():
            if transposed:
                y = jax.lax.dot_general(
                    x_ref[at, :], w_ref[0], (((1,), (1,)), ((), ())),
                    preferred_element_type=_F32)
            else:
                y = jnp.dot(x_ref[at, :], w_ref[0],
                            preferred_element_type=_F32)
            kept = jnp.where(first, 0, out_ref[at, :].astype(_F32))
            out_ref[at, :] = jnp.where(_in_group(lo - r0, hi - r0, sub), y,
                                       kept).astype(out_ref.dtype)

        @pl.when(~some & first)
        def _():
            out_ref[at, :] = jnp.zeros((sub, width), out_ref.dtype)

    _loop(rows, sub, part)


def _tgmm_kernel(group_ref, tile_ref, bound_ref, x_ref, dy_ref, out_ref,
                 acc_ref):
    rows, K = x_ref.shape
    v, visits = pl.program_id(1), pl.num_programs(1)
    g, lo, hi = _here(group_ref, tile_ref, bound_ref, rows)

    @pl.when(_first_of(group_ref, v))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def add(keep, step):
        def some_of_k(k0):
            at = pl.ds(k0, step)
            acc_ref[at, :] += jax.lax.dot_general(
                keep(x_ref[:, at]), keep(dy_ref[...]),
                (((0,), (0,)), ((), ())), preferred_element_type=_F32)

        _loop(K, step, some_of_k)

    whole = (lo == 0) & (hi == rows)

    @pl.when(whole)
    def _():
        add(lambda a: a, K)

    @pl.when((hi > lo) & ~whole)
    def _():
        # both operands: whatever the other groups' rows hold times zero is
        # not zero
        mine = _in_group(lo, hi, rows)
        add(lambda a: jnp.where(mine, a, 0), _columns(
            K, lambda step: step <= _MASKED_STEP))

    @pl.when((v == visits - 1)
             | (group_ref[jnp.minimum(v + 1, visits - 1)] != g))
    def _():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _fetched(tile, bounds):
    """The tile a visit's inputs are fetched from: its own, and for the
    visits behind the last group the last one that holds a row."""
    return jnp.minimum(tile, bounds[bounds.shape[0] - 2])


@functools.partial(jax.jit,
                   static_argnames=("block", "transposed", "interpret"))
def _gmm(x, w, group, tile, bounds, *, block, transposed=False,
         interpret=False):
    """x (R, K) x w (n, K, N) -> (R, N); ``transposed``: x (R, N) x w^T ->
    (R, K).  ``block``: the result's columns a grid step."""
    R, contracted = x.shape
    rows = R // (group.shape[0] - w.shape[0])      # R / tile + n visits
    width = w.shape[1] if transposed else w.shape[2]
    if transposed:
        stack = pl.BlockSpec((1, block, contracted),
                             lambda j, v, g, t, b: (g[v], j, 0))
    else:
        stack = pl.BlockSpec((1, contracted, block),
                             lambda j, v, g, t, b: (g[v], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(width // block, group.shape[0]),
            in_specs=[pl.BlockSpec((rows, contracted), lambda j, v, g, t, b: (
                _fetched(t[v], b), 0)), stack],
            out_specs=pl.BlockSpec((rows, block),
                                   lambda j, v, g, t, b: (t[v], j))),
        out_shape=jax.ShapeDtypeStruct((R, width), x.dtype),
        compiler_params=_COMPILER_PARAMS, interpret=interpret,
    )(group, tile, bounds, x, w)


@functools.partial(jax.jit, static_argnames=("block", "n", "interpret"))
def _tgmm(x, dy, group, tile, bounds, *, block, n, interpret=False):
    """x (R, K), dy (R, N) -> (n, K, N) in x's type: each group's rows of
    x, transposed, times its rows of dy."""
    (R, K), N = x.shape, dy.shape[1]
    rows = R // (group.shape[0] - n)
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N // block, group.shape[0]),
            in_specs=[pl.BlockSpec((rows, K), lambda j, v, g, t, b: (
                _fetched(t[v], b), 0)),
                pl.BlockSpec((rows, block), lambda j, v, g, t, b: (
                    _fetched(t[v], b), j))],
            out_specs=pl.BlockSpec((1, K, block),
                                   lambda j, v, g, t, b: (g[v], 0, j)),
            scratch_shapes=[pltpu.VMEM((K, block), _F32)]),
        out_shape=jax.ShapeDtypeStruct((n, K, N), x.dtype),
        compiler_params=_COMPILER_PARAMS, interpret=interpret,
    )(group, tile, bounds, x, dy)


def _reference(rows, stacks, group_sizes, *walk):
    """The product in plain XLA: what the kernels are held to, and what a
    shape they decline runs."""
    return jax.lax.ragged_dot(rows, stacks, group_sizes)


def _reference_rows(dy, stacks, group_sizes, *walk):
    rows = jax.ShapeDtypeStruct((dy.shape[0], stacks.shape[1]), dy.dtype)
    return jax.linear_transpose(
        lambda r: _reference(r, stacks, group_sizes), rows)(dy)[0]


def _reference_stacks(rows, dy, group_sizes, *walk):
    stacks = jax.ShapeDtypeStruct(
        (group_sizes.shape[0], rows.shape[1], dy.shape[1]), rows.dtype)
    return jax.linear_transpose(
        lambda w: _reference(rows, w, group_sizes), stacks)(dy)[0]


def _product(kernel, reference, **tiles):
    """``kernel`` over (first, second, group_sizes, *walk) where the call
    is lowered for a TPU, counted as the step is traced."""
    def run(first, second, group_sizes, *walk):
        tracing.count("moe.grouped_kernel_passes", int(
            interpreted(first) or jax.default_backend() == "tpu"))
        return by_platform(
            lambda a, b, sizes, *walk, interpret: kernel(
                a, b, *walk, interpret=interpret, **tiles),
            reference, first, second, group_sizes, *walk)
    return run


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _grouped(rows, stacks, group_sizes, walk, plan):
    return _product(_gmm, _reference, block=plan.forward)(
        rows, stacks, group_sizes, *walk)


def _grouped_bwd(plan, res, dy):
    rows, stacks, group_sizes, walk = res
    return (
        _product(_gmm, _reference_rows, block=plan.transposed,
                 transposed=True)(dy, stacks, group_sizes, *walk),
        _product(_tgmm, _reference_stacks, block=plan.stacks,
                 n=stacks.shape[0])(rows, dy, group_sizes, *walk),
        None, None)


_grouped.defvjp(
    lambda rows, stacks, group_sizes, walk, plan: (
        _grouped(rows, stacks, group_sizes, walk, plan),
        (rows, stacks, group_sizes, walk)),
    _grouped_bwd)


def over(group_sizes, R, tile=None):
    """-> `matmul(rows (R, K), stacks (n, K, N)) -> (R, N)` in the rows'
    type, each row times the matrix of the group ``group_sizes`` (n,) puts
    it in, zeros for the rows behind the last group; differentiable in the
    rows and the stacks.  Every product of one call of `over` shares one
    walk of the rows (a layer's three stacks; its forward and backward).
    ``tile``: the rows of a tile, for `tools/chip_kernels.py`'s sweep;
    None: `_row_tile`'s."""
    tile = tile or _row_tile(R)
    walk = _walk(group_sizes, R, tile) if tile else None

    def matmul(rows, stacks):
        plan = tile and rows.dtype == stacks.dtype and _plan(
            R, *stacks.shape[1:], rows.dtype, tile)
        tracing.count("moe.grouped_kernel_declined", int(not plan))
        if not plan:
            return _reference(rows, stacks, group_sizes)
        return _grouped(rows, stacks, group_sizes, walk, plan)
    return matmul


def grouped_matmul(rows, stacks, group_sizes):
    """One product of `over`."""
    return over(group_sizes, rows.shape[0])(rows, stacks)

"""Flash attention — Pallas TPU kernels with online softmax.

The hot op of the transformer stack, built TPU-first (MXU-sized tiles,
VMEM-resident accumulators, bf16 in / f32 accumulate).  Replaces what the
reference delegates to torch/CUDA (scaled_dot_product_attention inside user
train loops); here it is a framework op reused by models, ring attention
(`ray_tpu/parallel/ring_attention.py`) and serving.

Two entry points / layouts:

* ``flash_attention`` — (batch, heads, seq, head_dim): one head per grid
  step, online softmax with running (max, sum, acc).
* ``flash_attention_bshd`` — (batch, seq, heads, head_dim), the layout
  models naturally produce from the fused qkv projection.  The arrays are
  viewed as (batch, seq, heads*head_dim) and the kernels take 128-wide
  *lane* blocks (one 128-dim head, or a pair of 64-dim heads, per block;
  Pallas TPU requires minor block dims of 128), slicing each head out of
  the lanes in-kernel.  No (B,S,H,D) <-> (B,H,S,D) transpose ever
  materializes — the bhsd route costs four such transposes per transformer
  layer fwd (plus their mirrors in bwd), each a round trip of the whole
  array through HBM.

Causal calls compute only the tiles on and below the diagonal and mask
only the tiles the diagonal crosses, forward and backward; `_auto_tiles`
picks the tile from S and `causal` (`block_q` / `block_k` name one
explicitly).  Non-causal calls have nothing to skip and take the whole
sequence (1024-capped) as one tile; the backward past `_WHOLE_SEQ_MAX`
takes 512-tiles either way.

``causal`` is a RULE of static integers, of which the diagonal is one
(`BlockRule`): positions in blocks, a block seeing itself whole, and,
block diffusion's training form, two kinds of row a sequence (L clean rows,
then their L noised copies: a noised row attends the clean rows of earlier
blocks and the noised rows of its own).  Which tiles a rule empties, which
it leaves whole and which it crosses is worked out from the tiles' indices
when the step is traced (`_crossed`, walked by rows in `_k_spans` and by
columns in `_q_spans`): an empty tile is never fetched or multiplied, a
whole tile is not masked, and a crossed tile's mask is made in the kernel
from its offsets (`_rule_mask`: block indices compared), never read.
``causal=True`` is the rule at a block of 1 with one kind of row and
compiles to the kernel it always was.  ALIGNED windows
(`BlockRule(aligned=A)`: a row attends the keys of its own window of A
positions up to itself) are the diagonal with the tiles of earlier windows
empty: a row of tiles starts, and a column of them ends, with its own
window's (`_k_spans`, `_q_spans`), and the mask is the diagonal's.  A WINDOW
(`BlockRule(window=W)`: a row attends its W latest keys) is a second bound
inside the same rule, the lower compare `_rule_mask`'s too.  Past `_WHOLE_SEQ_MAX` a tile's keys are
ONE band (`_band`, PR 64): a q tile's W' + block_q keys (the backward: a k
tile's block_k + W' query rows; W' the window in whole lanes) are one slice
of the k and v (q, do, statistics and dq) the kernel holds anyway, one
product, one mask with both bounds and one turn of the softmax, and a grid
step takes `_BAND_STEP` rows, its tiles written out.  On a v5e at
S = 16,384 (ms a layer, forward + backward, the walk of 512-tiles beside
the band of 256-tiles; PERF.md §6, PR 64): W = 512 with 64 query heads on
8 of 128 19.42 -> 11.40, W = 1,024 with 32 on 4 12.70 -> 8.27, W = 512
with 20 on 10 and q, k 64 wide on v 128 6.18 -> 3.67; the band's mask is
0.14 of 3.16 ms of the forward and nothing of the backward, so it is
compared in the kernel at every tile and not made once.  Where no band is
taken (a grid step the whole sequence, a mask that is data, a window too
wide for `_TILE_VMEM` or as long as the sequence) a q tile's k tiles run
from the first its window reaches, a k tile's q tiles end with the last
whose window still holds it, and a tile is crossed by the window's bound,
by the diagonal, by both or by neither (`_two_bounds`).  A model whose
layers mix windowed and full attention pays for the pairs each attends,
and no (S, S) mask.

Up to S = `_WHOLE_SEQ_MAX` a grid step takes a whole (b, h) slice (a
128-lane group in the lane layout) and every extent inside it is static,
so nothing loops: the tiles of a row (forward) or column (backward) that
lie wholly below the diagonal are merged into one unmasked span, the ones
the diagonal crosses into one masked span (`_span`).  The forward walks
its q tiles, an online softmax of two steps at most each.  Past
`_WHOLE_SEQ_MAX` the grid walks the tiles: the forward's q tiles, each
looping over its k blocks, and the backward's k tiles, each looping over
its q blocks (`_tile_loop`).

The backward is ONE kernel for dq/dk/dv at every length, with one body
(`_bwd_fused_core`): per k tile it recomputes s and dp once and shares them
between dq, dk and dv (5 dots instead of the 7 a two-kernel
FlashAttention-2 split pays, and a tile's mask, `exp2` and ds made once),
and dq sums over k tiles in an f32 VMEM scratch.  Past `_WHOLE_SEQ_MAX` a
(b, h) slice's q, do and row statistics stay in VMEM while its k tiles
pass, last to first, under a limit of scoped VMEM that `_compiler_params`
reckons from the shapes.  The S×S matrix never exists in HBM in any pass.

Grouped-query attention: k and v may come with fewer heads than q
(``H % H_kv == 0``), query head h reading key/value head h // (H / H_kv).
The head-major kernels read that head through their `BlockSpec` index maps
(`_kv_rows`), so no copy of k or v with H heads exists; dk and dv leave the
backward kernels as one float32 partial a query head and are summed over
each group in float32 (`_sum_groups`: at (2, 8192, 32 / 8, 64) on a v5e the
split backward of PR 34 took 28.68 ms of kernels and 31.44 with the sums
and the transposes; a dk/dv kernel that summed a group itself, the group's
heads its innermost grid axis, took 31.58 and 33.71, fetching a head's q,
do and statistics again at every k block: PERF.md §6, PR 34; the one
kernel keeps the parts).  The lane layout slices every operand's heads out
of the same lanes and declines such a call, which then takes the
head-major kernels.

The public forward rules name the two residuals the backward kernels read
besides q, k and v, the output and the row statistics (`KEPT_RESIDUALS`):
a checkpointed layer whose policy keeps them
(`models/layers.py:checkpoint_layer`) recomputes its forward pass without
running the forward kernel a second time.

A mask that is DATA: ``mask`` (B, S, S) int8, one value a (query, key)
pair, not 0 where the pair is attended, shared by all heads of a sequence
(attention that selects its keys: `ops/sparse_index.py`; a segment rule
filling the same array).  It is an operand of the head-major
forward kernel and of the one backward kernel, combined with ``causal``.
Up to `_WHOLE_SEQ_MAX` a grid step holds a sequence's whole (S, S) mask and
slices it as it slices k.  Past it the mask comes tile-major (`_tile_major`:
each (block_q, block_k) tile contiguous, the q tile's row of them in the
forward, the k tile's column of them in the backward, so a loop picks a
tile by a leading index: `_mask_specs`).  Every causal tile is visited
whatever the mask holds of it.  The lane layout declines a mask as it
declines grouped queries, and the call takes the head-major kernels.  A
call without a mask is the program it was: no operand, no instruction.

Each kernel adds its tiles, the pairs of those it visits and the heads it
reads to the job timeline as the step is traced (`attention.tiles`,
`attention.tiles_skipped`, `attention.pairs_visited`, `attention.q_heads`,
`attention.kv_heads`, and under a window `attention.window_kernels`,
`attention.window`, `attention.window_pairs_visited` and, where it takes a
band, `attention.window_band_kernels`: see `_count_tiles`).

On non-TPU backends the same kernels run in interpret mode for tiny shapes
(tests), and a pure-XLA reference path is used otherwise.
"""

from __future__ import annotations

import functools
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import by_platform
from ray_tpu.util import tracing

_NEG_INF = -1e30
_LOG2E = 1.4426950408889634  # 1/ln(2)
_LANE = 128  # minor-dim block width Pallas TPU requires

# Longest sequence a grid step takes whole — all q rows in the forward, all
# k rows in the backward: the (b, h) slices of q, k, v, do and the three
# gradients (double-buffered), the lane layout's padded lse/delta and the
# f32 dq scratch stay within a few MB of VMEM up to here, and a merged span
# scores at most this many rows at once.  Past it the grid walks the tiles
# (and the lane layout's backward goes head-major).  `_resolve` makes the
# decision from it; `_auto_tiles` reads it for the tiles, `_rows_kept` for
# the layout of a kept lse.
_WHOLE_SEQ_MAX = 1024

# Both grid dims are embarrassingly parallel (batch*heads, and q/k blocks
# within a head); telling Mosaic so lets it pipeline block prologues across
# steps instead of treating the grid as a dependent loop nest.
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"))


# The two residuals the backward kernels read besides their recomputed
# inputs, as the public forward rules name them (`_named_residuals`): the
# kernel's output and its (B, H, S) row statistics.  A `jax.checkpoint`
# whose policy keeps these names (`models/layers.py:checkpoint_layer`) does
# not run the forward kernel again in the backward pass; anywhere else a
# name is nothing.
KEPT_RESIDUALS = ("flash_attention.o", "flash_attention.lse")

# The `jax.named_scope` each of the four `pallas_call`s is made under: the
# form of kernel, which an operation's `op_name` (and the chip's trace, as
# `tf_op`) then says besides forward / backward.  The head-major forward (a
# grid step a q tile, or the whole sequence) and the lane layout's; the
# one-kernel backward of each layout (head-major: a grid step the whole
# sequence, or a k tile of it); the two head-major ones again under a rule
# of blocks or of two kinds of row, and again under a window (`_form`).
KERNEL_FORMS = ("fwd_rows", "fwd_lanes", "bwd_fused", "bwd_fused_lanes",
                "fwd_rows_blocks", "bwd_fused_blocks",
                "fwd_rows_window", "bwd_fused_window")


# Mosaic's default limit of scoped VMEM on a v5e, what one tile's
# temporaries (s, p, dp, ds of 1024 x 1024 scores) may take of it, and the
# most a kernel asks of the chip's 128 MiB.
_SCOPED_VMEM = 16 << 20
_TILE_VMEM = 10 << 20
_VMEM_MAX = 100 << 20


def _lanes(d):
    """A width padded to whole lanes, as VMEM holds it."""
    return -(-d // _LANE) * _LANE


def _bwd_held_bytes(S, D, Dv, dtype):
    """What the backward past `_WHOLE_SEQ_MAX` holds in VMEM while a (b, h)
    slice's k tiles pass: the slice's q and do (double-buffered), two
    (S, 1) f32 statistics that fill a lane a row, dq's block and the f32
    dq scratch.  5.5 KB a row at 192 / 128 in bfloat16 (q and k take 256
    lanes), 46 MB at S = 8,192; 4 KB a row at D = 128 or 64, 16 MB at
    S = 4,096, and 128 MiB at S = 32,768, half of it the statistics."""
    width = jnp.dtype(dtype).itemsize
    return S * (2 * (_lanes(D) + _lanes(Dv)) * width    # q and do
                + 2 * 2 * _LANE * 4                     # lse and delta
                + 2 * _lanes(D) * width                 # dq's block
                + _lanes(D) * 4)                        # the scratch


def _compiler_params(S, D, Dv, dtype, bwd_steps=1, extra=0):
    """`_COMPILER_PARAMS`, with a higher limit of scoped VMEM where the
    operands a head-major kernel holds for the whole sequence (a width
    padded to whole lanes, every block double-buffered) leave a tile's
    temporaries no room under the default.

    The forward holds k and v: at S = 8,192 with q/k 192 wide and v 128 it
    wants 17.8 MB of the default 16 (compiled for a v5e without the chip:
    PERF.md §6, PR 32) and is given 16 MB + 4 x the 12 of k and v; every
    shape that fits takes the default (S = 4,096 at D = 128 holds 4 MB of k
    and v), and so does a backward of one grid step a (b, h) slice
    (``bwd_steps`` = 1: S <= `_WHOLE_SEQ_MAX`, a megabyte of each).

    ``bwd_steps`` > 1: the backward with a slice's k tiles on the grid.
    They run one after the other (dq sums over them in scratch: that grid
    axis is "arbitrary") while `_bwd_held_bytes` stay in VMEM.  The limit
    is that plus the default's 16 MB for the k, v, dk, dv tiles and a
    tile's temporaries, `_VMEM_MAX` at most: S = 16,384 fits at every width
    a cell has; a slice that leaves a tile no room (S = 32,768) never gets
    here (`_tiling_problem`).

    ``extra``: bytes of further blocks a step holds, double-buffered: a
    mask's (`_mask_block_bytes`)."""
    if bwd_steps > 1:
        return pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(
                _VMEM_MAX,
                _SCOPED_VMEM + _bwd_held_bytes(S, D, Dv, dtype) + extra))
    resident = 2 * S * (_lanes(D) + _lanes(Dv)) * jnp.dtype(dtype).itemsize \
        + extra
    if resident <= _SCOPED_VMEM - _TILE_VMEM:
        return _COMPILER_PARAMS
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=min(_VMEM_MAX, _SCOPED_VMEM + 4 * resident))


# ---------------------------------------------------------------------------
# shared kernel cores (operate on squeezed (rows, d) tiles)
# ---------------------------------------------------------------------------

def _span(first, last, block, body, carry):
    """``body(start, rows, carry)`` over the tiles [first, last) of
    ``block`` rows taken as ONE span (the bounds are Python ints): a tile's
    fixed and per-row costs are paid once and nothing loops."""
    if last > first:
        carry = body(first * block, (last - first) * block, carry)
    return carry


def _tile_loop(first, last, block, body, carry):
    """The same tiles one at a time; the bounds may be traced (from the q
    tile of the forward's grid step, the k tile of the backward's)."""
    return jax.lax.fori_loop(
        first, last, lambda i, c: body(i * block, block, c), carry)


def _run(start, rows, block, body, carry):
    """A band's walker (`_band`): its one run comes in rows already,
    ``rows`` of them (a Python int) from ``start`` (the grid's), and is ONE
    visit whatever the tile."""
    return body(start, rows, carry)


class BlockRule(NamedTuple):
    """Which keys a query attends, as a RULE of static integers: the tiles
    it empties are known when the step is traced and are never fetched, the
    tiles it leaves whole are not masked, and a tile it crosses is masked in
    the kernel from the tile's offsets.  Nothing of it is an operand.

    ``block``: positions come in blocks of this many, and a query attends
    the keys of no later block than its own, its own block whole (1: the
    diagonal, what ``causal=True`` is).

    ``kinds`` = 2, block diffusion's training form: the S rows are L = S / 2
    CLEAN rows and then L NOISED rows, row L + i the noised copy of
    position i.  A clean query attends clean keys as above and no noised
    key; a noised query attends the clean keys of STRICTLY earlier blocks
    and the noised keys of its own block.  L (L + block) pairs of the
    (2 L)^2.

    ``window`` = W: a second bound a row.  A query attends the W latest
    keys, its own among them (key j iff i - W < j <= i); None: no lower
    bound.  For the diagonal with one kind of row (`_rule` refuses it
    beside blocks or two kinds until a model needs that).  It need not
    divide a tile or be divided by one.

    ``aligned`` = A: windows that do not slide.  Positions come in aligned
    windows of A, and a query attends the keys of ITS OWN window up to
    itself (key j iff j <= i and j // A == i // A) and nothing of an earlier
    window, whose tiles are empty: the diagonal inside each of S / A
    squares (EVA's exact half: `ops/eva.py`).  The tiles divide A
    (`_tiling_problem`), so a tile lies in one window and is classed from
    its index as under the diagonal alone.  For the diagonal with one kind
    of row and no sliding window (`_rule` refuses it beside them until a
    model needs that)."""
    block: int = 1
    kinds: int = 1
    window: Optional[int] = None
    aligned: Optional[int] = None


CAUSAL = BlockRule()


def _rule(causal) -> Optional[BlockRule]:
    """A call's ``causal`` (False, True or a `BlockRule`) as a rule, None
    where every pair is attended."""
    if isinstance(causal, BlockRule):
        if causal.window is not None and (
                causal.window < 1 or (causal.block, causal.kinds) != (1, 1)):
            raise NotImplementedError(
                f"{causal}: a window is a second bound of the diagonal with "
                f"one kind of row (at least one key wide); blocks or two "
                f"kinds of row under a window are not written, no model "
                f"asks for them")
        if causal.aligned is not None and (
                causal.aligned < 1 or causal.window is not None
                or (causal.block, causal.kinds) != (1, 1)):
            raise NotImplementedError(
                f"{causal}: aligned windows hold the diagonal with one kind "
                f"of row (at least one key wide); beside a sliding window, "
                f"blocks or two kinds of row they are not written, no "
                f"model asks for them")
        return causal
    return CAUSAL if causal else None


def _windowed(causal) -> bool:
    """Whether a call's rule has a window."""
    return isinstance(causal, BlockRule) and causal.window is not None


def _band(causal, S, block, whole, masked=False):
    """The rows of the ONE band a tile of ``block`` rows takes under a
    window past `_WHOLE_SEQ_MAX`, None where the call keeps the walk of
    tiles (`_two_bounds`).  A q tile's keys are the one run [q_start - W +
    1, q_start + block), a k tile's query rows [k_start, k_start + block +
    W - 1): W' + block rows, W' the window rounded up to whole lanes, which
    keeps the run's start (clamped at 0, or so that it ends at S) where a
    slice of the held rows may start.  The kernel reads it as one slice,
    makes one product, one mask with both bounds and, forward, one turn of
    the softmax, where a walk of 512-tiles visits two or three tiles, each
    with its own mask and turn.  What decides is what the call can see:
    ``whole`` (a grid step the whole sequence: every extent is static and
    `_span` merges already), ``masked`` (a mask that is data comes
    tile-major, a tile a visit), a tile that is no whole lanes, a band
    longer than the sequence (W >= S - block: little or nothing to leave
    out) and a band whose temporaries (s and dp in float32, p and ds in
    bfloat16: 12 bytes a pair) outgrow `_TILE_VMEM`: at 512-tiles a window
    past 1,152, whose walk has whole tiles between the crossed ones."""
    rule = _rule(causal)
    if whole or masked or rule is None or rule.window is None \
            or block % _LANE:
        return None
    rows = _lanes(rule.window) + block
    if rows > S or 12 * block * rows > _TILE_VMEM:
        return None
    return rows


# The rows a grid step takes under a band, as whole tiles of the band's
# (`_band_step`; swept on a v5e at 256-tiles, a tile a step and steps of 512,
# 1,024 and 2,048 rows, forward + backward ms a layer: W = 512, 64 heads on 8
# of 128, 13.08 / 12.03 / 11.40 / 11.05; W = 1,024, 32 on 4, 9.32 / 8.81 /
# 8.27 / 7.93: PERF.md §6, PR 64.  2,048 writes out eight tiles a kernel for
# 3 % more).
_BAND_STEP = 1024


def _band_step(S, block, band):
    """The rows of a grid step whose tile is ``block``: under a ``band`` at
    least `_BAND_STEP` of them where they divide S, its tiles written out
    one after the other (independent chains, which the scheduler may
    interleave), so that a step's fixed cost is paid once for several."""
    step = max(block, _BAND_STEP)
    return step if band and S % step == 0 else block


def _int(flag):
    """A comparison of tile indices as 0 or 1, whether they are Python's
    (every extent static) or the grid's."""
    return int(flag) if isinstance(flag, bool) else flag.astype(jnp.int32)


def _crossed(tile, rows, cols):
    """[first, last) of the tiles of ``cols`` positions that share a
    position with tile ``tile`` of ``rows`` positions: those a rule's
    diagonal crosses, whichever way the square is walked (a q tile's k
    tiles, a k tile's q tiles).  The one classification: tiles before
    ``first`` lie wholly on one side of the diagonal and tiles from ``last``
    wholly on the other (a rule's blocks divide the tiles:
    `_tiling_problem`)."""
    return (tile * rows) // cols, pl.cdiv((tile + 1) * rows, cols)


def _floor0(x):
    """max(x, 0), of a Python int or of the grid's."""
    return max(x, 0) if isinstance(x, int) else jnp.maximum(x, 0)


def _least(n, x):
    """min(n, x), likewise."""
    return min(n, x) if isinstance(x, int) else jnp.minimum(n, x)


def _aligned(x):
    """A band's first row, which is whole lanes from the sequence's start
    (`_band`): said to Mosaic where it is the grid's."""
    return x if isinstance(x, int) else pl.multiple_of(x, _LANE)


def _two_bounds(rule, block_q, block_k, before, after):
    """The runs of tiles a window leaves a tile, in the order they are
    walked: ``before`` and ``after`` are (first, last, how) of the tiles
    each of its two bounds crosses (the window's bound "from", the
    diagonal "upto"), the former starting and ending no later than the
    latter.  Where a tile pair is wider than the window (W < block_q +
    block_k - 1) the two ranges meet or overlap at every tile index, and
    the tiles of both are one run with both compares; else they never
    overlap and the tiles between them are whole.  Which of the two holds
    is static, so a kernel has three runs either way."""
    (a, b, first_how), (c, d, last_how) = before, after
    if rule.window < block_q + block_k - 1:
        return [(a, c, first_how), (c, b, "both"), (b, d, last_how)]
    return [(a, b, first_how), (b, c, None), (c, d, last_how)]


def _k_spans(rule, qi, block_q, block_k, seq_len, band=None):
    """What q tile ``qi`` (a Python int, or the grid's) visits of the k
    tiles -> (the tile's index among its own kind's, whether its rows are
    noised (0 or 1), [(first, last, how)]): runs of k tiles, ``how`` None
    where the rule attends every pair (no mask), "upto" where it crosses
    the tiles among the clean keys and "own" among the noised keys of the
    rows' own blocks; under a window "from" where its bound crosses them
    and "both" where that and the diagonal do (`_two_bounds`).  Every other
    tile is empty and in no run.  ``band`` (`_band`): the one run of that
    many KEYS that ends with the tile's last row, (its first key, ``band``,
    "both"), `_run`'s to walk; the first tiles' starts at key 0 and the
    diagonal's compare takes the keys past their rows."""
    n = seq_len // block_k
    if band:
        return qi, 0, [
            (_aligned(_floor0((qi + 1) * block_q - band)), band, "both")]
    if rule is None:
        return qi, 0, [(0, n, None)]
    if rule.window is not None:
        # the k tiles the window's bound crosses: from the one the tile's
        # first row still reaches to the first its last row reaches whole
        top = qi * block_q - rule.window
        lower = (_floor0(top + 1) // block_k,
                 pl.cdiv(_floor0(top + block_q), block_k), "from")
        return qi, 0, _two_bounds(
            rule, block_q, block_k, lower,
            (*_crossed(qi, block_q, block_k), "upto"))
    if rule.kinds == 1:
        first, last = _crossed(qi, block_q, block_k)
        # aligned windows: from the first k tile of the q tile's own window
        start = 0 if rule.aligned is None else \
            (qi * block_q) // rule.aligned * (rule.aligned // block_k)
        return qi, 0, [(start, first, None), (first, last, "upto")]
    half_q, half_k = seq_len // 2 // block_q, n // 2
    noised = _int(qi >= half_q)
    at = qi - noised * half_q
    first, last = _crossed(at, block_q, block_k)
    return at, noised, [
        (0, first, None), (first, last, "upto"),
        (half_k + first, half_k + first + noised * (last - first), "own")]


def _q_spans(rule, kj, block_q, block_k, seq_len, band=None):
    """`_k_spans` the other way: what k tile ``kj`` is visited by, of the q
    tiles -> (the tile's index among its own kind's, [(first, last, how,
    whether those q rows are noised)]).  ``band``: the one run of that many
    query ROWS from the tile's first key, (its first row, ``band``, "both",
    0); the last tiles' ends at row S and the diagonal's compare takes the
    rows before their keys."""
    n = seq_len // block_q
    if band:
        return kj, [(_aligned(_least(seq_len - band, kj * block_k)), band,
                     "both", 0)]
    if rule is None:
        return kj, [(0, n, None, 0)]
    if rule.window is not None:
        # the q tiles the window's bound crosses: from the first whose
        # last row no longer reaches the tile's first key to the first
        # whose first row no longer reaches its last
        end = kj * block_k + rule.window
        lower = (_least(n, end // block_q),
                 _least(n, pl.cdiv(end + block_k - 1, block_q)), "from")
        return kj, [run + (0,) for run in _two_bounds(
            rule, block_q, block_k,
            (*_crossed(kj, block_k, block_q), "upto"), lower)]
    if rule.kinds == 1:
        first, below = _crossed(kj, block_k, block_q)
        # aligned windows: up to the last q tile of the k tile's own window
        end = n if rule.aligned is None else \
            ((kj * block_k) // rule.aligned + 1) * (rule.aligned // block_q)
        return kj, [(first, below, "upto", 0), (below, end, None, 0)]
    half_q, half_k = n // 2, seq_len // 2 // block_k
    noised = _int(kj >= half_k)
    clean = 1 - noised
    at = kj - noised * half_k
    first, below = _crossed(at, block_k, block_q)
    crossed, rest = below - first, half_q - below
    noised_first = half_q + first
    return at, [
        (first, first + clean * crossed, "upto", 0),
        (below, below + clean * rest, None, 0),
        (noised_first, noised_first + clean * crossed, "upto", 1),
        (noised_first, noised_first + noised * crossed, "own", 1),
        (half_q + below, half_q + below + clean * rest, None, 1)]


def _rule_mask(s, rule, q_start, k_start, how, strict=0):
    """Scores of the rows at positions q_start.. against the keys at
    positions k_start.. (each within its own kind's rows), the pairs the
    rule leaves out at -inf.  ``how`` "upto": keys of no later block than
    the row's, or, ``strict`` (a noised row's clean keys), of an earlier
    one; "own": keys of the row's own block.  At a block of 1 the block of
    a position is the position (the diagonal's compare, on the tile's
    shape); else a column of row blocks is compared with a row of key
    blocks, the division made on those and not on the tile."""
    rows, cols = s.shape
    if how in ("from", "both"):
        # how far behind its row a key lies, against the window's width
        # (and, a tile both bounds cross, against the diagonal's 0): the
        # tiles' offsets go on the scalar side of the compares
        behind = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
            - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        apart = q_start - k_start
        seen = behind < rule.window - apart
        if how == "both":
            seen &= behind >= -apart
        return jnp.where(seen, s, _NEG_INF)
    if rule.block == 1:
        q_at = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_at = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    else:
        block = jnp.int32(rule.block)
        q_at = jax.lax.div(q_start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0), block)
        k_at = jax.lax.div(k_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, cols), 1), block)
    if how == "own":
        return jnp.where(q_at == k_at, s, _NEG_INF)
    if rule.kinds == 2:
        q_at = q_at - strict
    return jnp.where(q_at >= k_at, s, _NEG_INF)


def _fwd_core(q, read_k, read_v, qi, over, *, causal, block_q, block_k,
              seq_len, v_dim, select=None, band=None):
    """Online-softmax forward over one q tile.

    At small head_dim the two dots leave the matrix unit half full and the
    vector work per score element (mask, max, exp2, sum) is what the body
    can save:

      * dots are bf16-in / f32-accumulate — never cast operands to f32
        (that demotes the MXU to its multi-pass f32 path);
      * sm_scale*log2(e) is pre-folded into the q tile by the caller
        (d ops/row, not bk) and the whole softmax runs in base-2 units;
      * the causal mask (iota+compare+select) runs ONLY on blocks
        intersecting the diagonal — interior blocks take the unmasked
        body, blocks above it are not visited;
      * exp2 runs on bf16 lanes (2x VPU width; p is consumed as bf16 by
        the p@v dot anyway, and max-subtraction bounds the error).

    q: (block_q, d) with scale folded, base-2 units; ``qi`` its tile
    index.  ``over`` walks the runs of k blocks the rule leaves the tile
    (`_k_spans`; `_fwd_rows`): `_span` takes the interior blocks as one span
    and the diagonal's as another, a pass of two steps at most (three under
    two kinds of row); `_tile_loop` loops over them; under a window's
    ``band`` (`_band`) `_run` takes the tile's keys as one run, one mask
    and one turn of the softmax.  read_k/read_v:
    (start, rows) -> (rows, d) of k and (rows, v_dim) of v, which may be
    another width (latent attention: 192 and 128).  Returns (acc f32
    (block_q, v_dim), m, l).

    ``select``: (start, rows) -> (block_q, rows), not 0 where the q tile's
    row attends the key (a mask that is data; with ``causal``, both hold).
    A row none of whose keys has come yet carries m = -1e30 and sums
    garbage, which its first attended key's alpha = 0 wipes; every row of a
    mask attends a key."""

    rule = _rule(causal)

    def body(start, rows, carry, how):
        acc, m_prev, l_prev = carry
        k = read_k(start, rows)
        v = read_v(start, rows)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bq, rows) f32
        if how:
            s = _rule_mask(
                s, rule, at * block_q,
                start - seq_len // 2 if how == "own" else start, how, noised)
        if select is not None:
            s = jnp.where(select(start, rows) != 0, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        p = jnp.exp2((s - m_new).astype(v.dtype))  # bf16: 2x VPU lanes
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True,
                                         dtype=jnp.float32)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc, m_new, l_new

    carry = (
        jnp.zeros((block_q, v_dim), jnp.float32),
        jnp.full((block_q, 1), _NEG_INF, jnp.float32),
        jnp.zeros((block_q, 1), jnp.float32),
    )
    # the runs of k blocks the rule leaves this q tile: those it attends
    # whole take the unmasked body, those it crosses the masked one
    at, noised, spans = _k_spans(rule, qi, block_q, block_k, seq_len, band)
    for first, last, how in spans:
        carry = over(first, last, block_k, functools.partial(body, how=how),
                     carry)
    return carry


def _finish_fwd(acc, m, l, out_dtype):
    """(o tile, lse tile in natural-log units) from the fwd carry."""
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = (acc / l_safe).astype(out_dtype)
    lse = m * jnp.asarray(1.0 / _LOG2E, m.dtype) + jnp.log(l_safe)
    return o, lse


def _when(cond):
    """`pl.when`; a condition that is a Python bool (a grid of one step) is
    decided as the kernel is traced and leaves nothing in it."""
    if isinstance(cond, bool):
        return lambda f: f() if cond else None
    return pl.when(cond)


def _bwd_fused_core(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dq_ref, dk_ref, dv_ref, dq_acc, cols, stat, *,
                    sm_scale, causal, block_q, block_k, seq_len,
                    selection=()):
    """One head's backward, at every length: for each k tile, the q tiles
    the diagonal crosses masked and the q tiles below it unmasked (q tiles
    above are not visited); each recomputes s and dp ONCE and contracts
    them into dq, dk and dv: 5 dots, where a FlashAttention-2 split (a dq
    kernel over q rows, a dk/dv kernel over k columns) pays 7 and does a
    tile's mask, `exp2` and ds twice.

    The q-side refs (q, do, lse, delta, dq) hold all S rows; the k-side
    refs (k, v, dk, dv) hold what the grid step takes of them, and the
    kernel reads its form off their rows.  All S (up to `_WHOLE_SEQ_MAX`):
    one step, the k tiles unrolled and every extent static, so each kind of
    q tile is merged into one span (`_span`, S rows at most).  One tile
    (past it): the slice's k tiles are the grid's second axis and each
    loops over its q tiles (`_tile_loop`); under a window's band (`_band`)
    a step holds `_band_step` rows of k, and each k tile of them takes its
    query rows as one run (`_run`: five dots a band, one mask).  The steps
    pass from the LAST k tile to the first (`_kv_rows`), because a causal
    slice's last k tile has one q tile
    to visit and its first all of them, and the next slice's q, do and
    statistics (14 MB at S = 8,192, 192 / 128 wide) are fetched during a
    slice's last step: the longest step hides the fetch, not the shortest.

    ``cols`` picks the head's columns of q/k/v/do/dq/dk/dv and ``stat``
    its column of lse/delta (natural-log lse, f32 delta).  ``dq_acc`` is an
    f32 (S, head_dim) scratch: dq sums over k tiles there, zeroed at the
    slice's first step and scaled and written at its last.  q, k and dq, dk
    have one width, v, do and dv may have another.

    ``selection``: the ref of a mask that is data (`_mask_specs`), none
    without one.  All S: the sequence's (S, S) mask, sliced as q and k are.
    One tile: the k tile's column of (block_q, block_k) mask tiles, picked
    by the q tile."""
    k_rows_held = k_ref.shape[0]
    steps, tiles = seq_len // k_rows_held, k_rows_held // block_k
    band = _band(causal, seq_len, block_k, steps == 1, bool(selection))
    step, over = (0, _span) if steps == 1 else (
        pl.program_id(1), _run if band else _tile_loop)
    # sm_scale * log2(e) folded into the q rows: s is in base-2 units, q
    # also serves the dk dot (rescaled by ln2 at the end), and ds's
    # trailing *sm_scale is hoisted onto dq.
    scale = jnp.asarray(sm_scale * _LOG2E, q_ref.dtype)
    rule = _rule(causal)

    @_when(step == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    for tile in range(tiles):
        kj = (steps - 1 - step) * tiles + tile
        k_rows = pl.ds(tile * block_k, block_k)
        k = k_ref[k_rows, cols]
        v = v_ref[k_rows, cols]

        def q_span(start, rows, carry, how, noised):
            dk_acc, dv_acc = carry
            q_rows = pl.ds(start, rows)
            q = q_ref[q_rows, cols] * scale
            do = do_ref[q_rows, cols]
            lse = lse_ref[q_rows, stat] * _LOG2E      # (rows, 1), base 2
            delta = delta_ref[q_rows, stat]           # (rows, 1)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # (rows, bk) f32
            if how:
                s = _rule_mask(
                    s, rule, start - seq_len // 2 if noised else start,
                    at * block_k, how, noised)
            if selection:
                mask_ref = selection[0]
                chosen = mask_ref[q_rows, k_rows] if steps == 1 \
                    else mask_ref[start // block_q]
                s = jnp.where(chosen != 0, s, _NEG_INF)
            p = jnp.exp2((s - lse).astype(k.dtype))   # bf16; masked -> 0
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # (rows, bk) f32
            ds = p * (dp - delta).astype(k.dtype)     # (rows, bk) bf16
            dq_acc[q_rows, :] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc = dk_acc + jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dv_acc = dv_acc + jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dk_acc, dv_acc

        acc = (jnp.zeros((block_k, k.shape[-1]), jnp.float32),
               jnp.zeros((block_k, v.shape[-1]), jnp.float32))
        # the runs of q tiles the rule has visit this k tile, the crossed
        # ones masked
        at, spans = _q_spans(rule, kj, block_q, block_k, seq_len, band)
        for first, last, how, noised in spans:
            acc = over(first, last, block_q, functools.partial(
                q_span, how=how, noised=noised), acc)
        dk_acc, dv_acc = acc
        dk_ref[k_rows, cols] = (dk_acc * (1.0 / _LOG2E)).astype(dk_ref.dtype)
        dv_ref[k_rows, cols] = dv_acc.astype(dv_ref.dtype)

    @_when(step == steps - 1)
    def _():
        dq_ref[:, cols] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


def _form(name, causal):
    """A head-major kernel's scope (`KERNEL_FORMS`): a name of its own
    under a rule of blocks, of two kinds of row or of aligned windows (the
    blocks' name), so that a trace tells those kernels from the
    diagonal's."""
    if _windowed(causal):
        return f"{name}_window"
    return name if _rule(causal) in (None, CAUSAL) else f"{name}_blocks"


def _kernel_call(*form):
    """`jax.jit` for a function that makes the `pallas_call`s of one pass.
    A model calls attention once a layer with the same shapes and tiles: as
    a jitted function each kernel is traced and lowered to Mosaic once a
    step and called from every layer, instead of once a layer.  Everything
    that shapes the kernels is a static argument (``form``: the names a
    pass adds to the common ones), so the cache keys on it."""
    return functools.partial(
        jax.jit, static_argnames=("sm_scale", "causal", "block_q", "block_k",
                                  "interpret") + form)


# ---------------------------------------------------------------------------
# bhsd layout: arrays viewed (B*H, S, D), one head per grid step
# ---------------------------------------------------------------------------

def _fwd_rows(whole, seq_len, block_q, band=None, held=None):
    """[(q tile index, its rows in the q block, how it walks its k blocks)]
    of one grid step, the two forms of the forward.  ``whole``: the step
    holds the whole sequence and the kernel walks its q tiles, every extent
    static (`_span`).  Else the grid has a step per q tile, which loops
    over its k blocks (`_tile_loop`).  Under a window's ``band`` a step
    holds ``held`` rows (`_band_step`), whose q tiles each take their keys
    as one run (`_run`)."""
    if band:
        tiles = held // block_q
        return [(pl.program_id(1) * tiles + i, pl.ds(i * block_q, block_q),
                 _run) for i in range(tiles)]
    if not whole:
        return [(pl.program_id(1), slice(None), _tile_loop)]
    return [(i, pl.ds(i * block_q, block_q), _span)
            for i in range(seq_len // block_q)]


def _fwd_select(selection, whole, rows, block_k):
    """`_fwd_core`'s ``select`` from the ref of a mask that is data
    (`_mask_specs`; none without one).  ``whole``: the sequence's (S, S)
    mask, the q tile's ``rows`` of it sliced as k is.  Else the q tile's
    row of (block_q, block_k) mask tiles, picked by the k block."""
    if not selection:
        return None
    mask_ref, = selection
    if whole:
        return lambda start, n: mask_ref[rows, pl.ds(start, n)]
    return lambda start, n: mask_ref[start // block_k]


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, sm_scale, causal, block_q,
                block_k, seq_len, whole):
    *selection, o_ref, lse_ref = rest
    band = _band(causal, seq_len, block_q, whole, bool(selection))
    for qi, rows, over in _fwd_rows(whole, seq_len, block_q, band,
                                    q_ref.shape[0]):
        q = q_ref[rows, :] * jnp.asarray(sm_scale * _LOG2E, q_ref.dtype)
        acc, m, l = _fwd_core(
            q, lambda start, n: k_ref[pl.ds(start, n), :],
            lambda start, n: v_ref[pl.ds(start, n), :], qi, over,
            causal=causal, block_q=block_q, block_k=block_k, seq_len=seq_len,
            v_dim=v_ref.shape[-1],
            select=_fwd_select(selection, whole, rows, block_k), band=band)
        o_ref[rows, :], lse_ref[rows, :] = _finish_fwd(acc, m, l, o_ref.dtype)


def _bwd_fused_kernel(*refs, **tiling):
    """refs: q, k, v, do, lse, delta; a mask's (`_mask_specs`), if any; dq,
    dk, dv; the dq scratch."""
    _bwd_fused_core(*refs[:6], *refs[-4:], slice(None), pl.ds(0, 1),
                    selection=refs[6:-4], **tiling)


def _kv_rows(group, last=0):
    """Index map of the k / v / dk / dv block of grid step (g, i): the rows
    of k tile ``last - i`` (the backward's k tiles pass from the last to
    the first; ``last`` = 0: the step holds the whole sequence) of the
    key/value head that grid row g's query head reads.  The rows of q are
    (batch, head) flat and those of k (batch, key/value head), so query
    head h's key/value head h // group is row g // group."""
    row = (lambda g: g) if group == 1 else (lambda g: g // group)
    if last == 0:
        return lambda g, i: (row(g), 0, 0)
    return lambda g, i: (row(g), last - i, 0)


def _sum_groups(partials, like):
    """(B * H, S, D) float32 partials of dk or dv, one a query head ->
    ``like``'s (B, H_kv, S, D): each group's summed in float32."""
    B, Hkv, S, D = like.shape
    return jnp.sum(partials.reshape(B, Hkv, -1, S, D), axis=2).astype(
        like.dtype)


def _tile_major(mask, block_q, block_k, k_major=False):
    """mask (B, S, S) -> its (block_q, block_k) tiles, each contiguous:
    (B, S / block_q, S / block_k, block_q, block_k), or with the k tiles
    leading.  A kernel's loop then picks a tile by a leading index, which
    Mosaic takes at any offset; a slice of the mask's lanes it does not."""
    B, S, _ = mask.shape
    tiles = mask.reshape(B, S // block_q, block_q, S // block_k, block_k)
    return tiles.transpose((0, 3, 1, 2, 4) if k_major else (0, 1, 3, 2, 4))


def _mask_block_bytes(S, rows):
    """What a grid step holds of a mask, double-buffered: ``rows`` of its S
    columns (or S rows of as many columns), a byte a pair."""
    return 2 * rows * S


def _mask_specs(mask, heads, whole, block_q, block_k, k_major=False,
                last=0):
    """(the operand, its spec) of a mask that is data, for a head-major
    `pallas_call` whose grid rows are (batch entry, head) flat; nothing of
    either without one.  ``whole``: a grid step holds the sequence's (S, S)
    mask.  Else the mask goes in tile-major and a step holds the row of
    tiles of its q tile (the forward) or, ``k_major``, the column of tiles
    of k tile ``last - i`` (the backward, whose k tiles pass from the last
    to the first)."""
    if mask is None:
        return (), []
    mask = mask.astype(jnp.int8)
    S = mask.shape[1]
    if whole:
        return (mask,), [pl.BlockSpec(
            (None, S, S), lambda g, i: (g // heads, 0, 0))]
    tiles = _tile_major(mask, block_q, block_k, k_major)
    index = (lambda g, i: (g // heads, last - i, 0, 0, 0)) if k_major \
        else (lambda g, i: (g // heads, i, 0, 0, 0))
    return (tiles,), [pl.BlockSpec(
        (None, None, tiles.shape[2], block_q, block_k), index)]


@_kernel_call("whole")
def _pallas_forward(q, k, v, sm_scale, causal, block_q, block_k, whole,
                    interpret, mask=None):
    """``whole``: a grid step takes the whole sequence and walks its q
    tiles; else one q tile (the forward's two forms: `_fwd_rows`).  q is
    (B, H, S, D) and k (B, H_kv, S, D); v, and so o, may have another last
    dim.  ``mask``: (B, S, S), not 0 where a pair is attended."""
    B, H, S, D = q.shape
    Hkv, Dv = k.shape[1], v.shape[-1]
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * Hkv, S, D)
    vf = v.reshape(B * Hkv, S, Dv)
    rows = S if whole else _band_step(
        S, block_q, _band(causal, S, block_q, whole, mask is not None))
    grid = (B * H, S // rows)
    masks, mask_specs = _mask_specs(mask, H, whole, block_q, block_k)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_len=S, whole=whole,
    )
    qspec = pl.BlockSpec((None, rows, D), lambda g, i: (g, i, 0))
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[qspec,
                  pl.BlockSpec((None, S, D), _kv_rows(H // Hkv)),
                  pl.BlockSpec((None, S, Dv), _kv_rows(H // Hkv)),
                  *mask_specs],
        out_specs=[pl.BlockSpec((None, rows, Dv), lambda g, i: (g, i, 0)),
                   pl.BlockSpec((None, rows, 1), lambda g, i: (g, i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, S, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(
            S, D, Dv, q.dtype,
            extra=_mask_block_bytes(S, rows) if masks else 0),
    )
    with jax.named_scope(_form("fwd_rows", causal)):
        o, lse = call(qf, kf, vf, *masks)
    return o.reshape(B, H, S, Dv), lse.reshape(B, H, S)


@_kernel_call("k_rows")
def _pallas_backward(q, k, v, o, lse, do, sm_scale, causal, block_q, block_k,
                     k_rows, interpret, delta=None, mask=None):
    """The one-kernel backward.  ``k_rows``: the rows of k a grid step
    takes, the whole sequence or one k tile of it, a (b, h) slice's tiles
    in order (`_bwd_fused_core`).  v, o and do may have another last dim
    than q and k; k and v may have fewer heads (a group of q's heads reads
    each): every query head then writes its float32 part of dk and dv, and
    `_sum_groups` adds a group's.  ``mask``: as `_pallas_forward`'s."""
    B, H, S, D = q.shape
    Hkv, Dv = k.shape[1], v.shape[-1]
    group = H // Hkv
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * Hkv, S, D)
    vf = v.reshape(B * Hkv, S, Dv)
    dof = do.reshape(B * H, S, Dv)
    lsef = lse.reshape(B * H, S, 1)
    # delta = rowsum(do * o): cheap elementwise+reduce, XLA fuses it.
    # Callers looping over K/V chunks (ring attention) pass it precomputed
    # — it only depends on the q side, so per-chunk recompute is waste.
    if delta is None:
        delta = jnp.sum(
            do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = delta.reshape(B * H, S, 1)
    part = (lambda x: x.dtype) if group == 1 else (lambda x: jnp.float32)

    def spec(rows, width, index):
        return pl.BlockSpec((None, rows, width), index)

    steps = S // k_rows
    k_read, k_write = _kv_rows(group, steps - 1), _kv_rows(1, steps - 1)
    qk, vo, row = (spec(S, width, _kv_rows(1)) for width in (D, Dv, 1))
    masks, mask_specs = _mask_specs(
        mask, H, steps == 1, block_q, block_k, k_major=True, last=steps - 1)
    call = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          seq_len=S),
        grid=(B * H, steps),
        in_specs=[qk, spec(k_rows, D, k_read), spec(k_rows, Dv, k_read), vo,
                  row, row, *mask_specs],
        out_specs=[qk, spec(k_rows, D, k_write), spec(k_rows, Dv, k_write)],
        out_shape=[jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
                   jax.ShapeDtypeStruct((B * H, S, D), part(k)),
                   jax.ShapeDtypeStruct((B * H, S, Dv), part(v))],
        scratch_shapes=[pltpu.VMEM((S, D), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(
            S, D, Dv, q.dtype, bwd_steps=steps,
            extra=_mask_block_bytes(S, k_rows) if masks else 0),
    )
    with jax.named_scope(_form("bwd_fused", causal)):
        dq, dk, dv = call(qf, kf, vf, dof, lsef, delta, *masks)

    dq = dq.reshape(B, H, S, D)
    if group == 1:
        return dq, dk.reshape(B, H, S, D), dv.reshape(B, H, S, Dv)
    return dq, _sum_groups(dk, k), _sum_groups(dv, v)


# ---------------------------------------------------------------------------
# bshd layout: arrays viewed (B, S, H*D), 128-wide lane blocks, heads
# sliced from lanes in-kernel — no transposes anywhere
# ---------------------------------------------------------------------------

def _fwd_kernel_lanes(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale,
                      causal, heads_per_block, head_dim, block_q, block_k,
                      seq_len, whole):
    """Refs: q/o (rows, hpb*head_dim), k/v (S, hpb*head_dim), lse
    (rows, hpb).  Each 128-lane block carries hpb heads side by side;
    the per-head chains run sequentially so their tiles' temporaries
    reuse the same VMEM."""
    for h in range(heads_per_block):
        sl = pl.ds(h * head_dim, head_dim)
        for qi, rows, over in _fwd_rows(whole, seq_len, block_q):
            q = q_ref[rows, sl] * jnp.asarray(sm_scale * _LOG2E, q_ref.dtype)
            acc, m, l = _fwd_core(
                q, lambda start, n: k_ref[pl.ds(start, n), sl],
                lambda start, n: v_ref[pl.ds(start, n), sl], qi, over,
                causal=causal, block_q=block_q, block_k=block_k,
                seq_len=seq_len, v_dim=head_dim)
            o, lse = _finish_fwd(acc, m, l, o_ref.dtype)
            o_ref[rows, sl] = o
            lse_ref[rows, pl.ds(h, 1)] = lse


def _bwd_fused_kernel_lanes(*refs, heads_per_block, head_dim, **tiling):
    """The per-head chains run one after the other, so the tiles'
    temporaries and the dq scratch are one head's."""
    for h in range(heads_per_block):
        _bwd_fused_core(*refs, pl.ds(h * head_dim, head_dim), pl.ds(h, 1),
                        **tiling)


def _lanes_config(H, D):
    """heads_per_block so each lane block is exactly _LANE wide (the Pallas
    TPU minor-dim constraint); None when the layout can't tile that way."""
    if D > _LANE and D % _LANE == 0:
        # wide heads: block covers part of one head?  Not supported — the
        # in-kernel slice would split a head across blocks.
        return None
    if _LANE % D:
        return None
    hpb = _LANE // D
    if H % hpb:
        return None
    return hpb


@_kernel_call("whole")
def _pallas_forward_bshd(q, k, v, sm_scale, causal, block_q, block_k,
                         whole, interpret):
    B, S, H, D = q.shape
    hpb = _lanes_config(H, D)
    qf = q.reshape(B, S, H * D)
    kf = k.reshape(B, S, H * D)
    vf = v.reshape(B, S, H * D)
    G = H // hpb                      # lane-block groups per batch entry
    W = hpb * D                       # == _LANE
    rows = S if whole else block_q
    grid = (B * G, S // rows)
    kernel = functools.partial(
        _fwd_kernel_lanes, sm_scale=sm_scale, causal=causal,
        heads_per_block=hpb, head_dim=D, block_q=block_q, block_k=block_k,
        seq_len=S, whole=whole,
    )
    qspec = pl.BlockSpec((None, rows, W), lambda g, i: (g // G, i, g % G))
    kvspec = pl.BlockSpec((None, S, W), lambda g, i: (g // G, 0, g % G))
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[qspec, kvspec, kvspec],
        out_specs=[qspec,
                   pl.BlockSpec((None, rows, hpb),
                                lambda g, i: (g, i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H * D), q.dtype),
            jax.ShapeDtypeStruct((B * G, S, hpb), jnp.float32),
        ],
        interpret=interpret,
        # k and v of a lane block are held for the whole sequence: past
        # `_WHOLE_SEQ_MAX` they can outgrow the default (S = 8,192 wants
        # 18.3 of 16 MB); every shape a cell runs keeps the default
        compiler_params=_compiler_params(S, W, W, q.dtype),
    )
    with jax.named_scope("fwd_lanes"):
        o, lse = call(qf, kf, vf)
    # lse (B*G, S, hpb) -> (B, H, S): group-major heads, tiny tensor.
    lse = lse.reshape(B, G, S, hpb).transpose(0, 1, 3, 2).reshape(B, H, S)
    return o.reshape(B, S, H, D), lse


@_kernel_call()
def _pallas_backward_bshd(q, k, v, o, lse, do, sm_scale, causal, block_q,
                          block_k, interpret):
    """The one-kernel backward in the lane layout: a grid step takes the
    whole sequence (callers gate on `_resolve`'s ``whole``)."""
    B, S, H, D = q.shape
    hpb = _lanes_config(H, D)
    G = H // hpb
    W = hpb * D
    qf = q.reshape(B, S, H * D)
    kf = k.reshape(B, S, H * D)
    vf = v.reshape(B, S, H * D)
    dof = do.reshape(B, S, H * D)
    # lse (B, H, S) -> (B*G, S, hpb); delta likewise (tiny tensors).
    lsef = lse.reshape(B, G, hpb, S).transpose(0, 1, 3, 2).reshape(
        B * G, S, hpb)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = delta.reshape(B, S, G, hpb).transpose(0, 2, 1, 3).reshape(
        B * G, S, hpb)

    spec = pl.BlockSpec((None, S, W), lambda g, i: (g // G, 0, g % G))
    row = pl.BlockSpec((None, S, hpb), lambda g, i: (g, 0, 0))
    call = pl.pallas_call(
        functools.partial(_bwd_fused_kernel_lanes, sm_scale=sm_scale,
                          causal=causal, heads_per_block=hpb, head_dim=D,
                          block_q=block_q, block_k=block_k, seq_len=S),
        grid=(B * G, 1),
        in_specs=[spec, spec, spec, spec, row, row],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((B, S, H * D), q.dtype),
                   jax.ShapeDtypeStruct((B, S, H * D), k.dtype),
                   jax.ShapeDtypeStruct((B, S, H * D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((S, D), jnp.float32)],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )
    with jax.named_scope("bwd_fused_lanes"):
        dq, dk, dv = call(qf, kf, vf, dof, lsef, delta)
    return (dq.reshape(B, S, H, D), dk.reshape(B, S, H, D),
            dv.reshape(B, S, H, D))


# ---------------------------------------------------------------------------
# reference path + public API
# ---------------------------------------------------------------------------

def _repeat_groups(q, k, v):
    """k and v with each head repeated for its group of q's heads (axis 1
    is the heads'); as they are where the counts are equal."""
    group = q.shape[1] // k.shape[1]
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)


def _attended(rule, S):
    """The (S, S) pairs a rule attends, written out (the reference's): row
    r is of kind r // L at position r % L, L = S / kinds.  The diagonal's
    is the lower triangle it always was (a causal call's jaxpr is held to
    what it lowered to: `tests/test_gqa_flash.py`)."""
    if rule == CAUSAL:
        return jnp.tril(jnp.ones((S, S), bool))
    row = jnp.arange(S)
    if rule.window is not None:
        behind = row[:, None] - row[None]
        return (behind >= 0) & (behind < rule.window)
    if rule.aligned is not None:
        return (row[None] <= row[:, None]) & (
            row[None] // rule.aligned == row[:, None] // rule.aligned)
    L = S // rule.kinds
    noised, block = row // L, row % L // rule.block
    return jnp.where(noised[None] == 0,
                     block[None] <= block[:, None] - noised[:, None],
                     (noised[:, None] == 1) & (block[None] == block[:, None]))


def reference_attention(q, k, v, sm_scale, causal, mask=None):
    k, v = _repeat_groups(q, k, v)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        s = jnp.where(_attended(_rule(causal), q.shape[2]), s, _NEG_INF)
    if mask is not None:
        s = jnp.where(mask[:, None] != 0, s, _NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype), lse


def _reference_backward(q, k, v, lse, do, delta, sm_scale, causal,
                        mask=None):
    qf = q.astype(jnp.float32)
    kf, vf = _repeat_groups(q, k.astype(jnp.float32), v.astype(jnp.float32))
    dof = do.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * sm_scale
    if causal:
        s = jnp.where(_attended(_rule(causal), q.shape[2]), s, _NEG_INF)
    if mask is not None:
        s = jnp.where(mask[:, None] != 0, s, _NEG_INF)
    p = jnp.exp(s - lse[..., None])
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
    dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vf)
    ds = p * (dp - delta[..., None]) * sm_scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
    if k.shape[1] != q.shape[1]:
        dk, dv = _sum_groups(dk, k), _sum_groups(dv, v)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


class AttentionFallbackWarning(UserWarning):
    """A shape the flash kernels cannot tile ran the O(S^2) reference —
    on every platform, the TPU included."""


def _tiling_problem(S, block_q, block_k, held=0, rule=None) -> Optional[str]:
    """Why the Pallas kernels cannot tile S with these blocks, or None.
    ``held``: the bytes a kernel keeps in VMEM for all S rows beside its
    tiles (`_bwd_held_bytes`).  ``rule``: its kinds of row and its blocks
    must end where tiles end, which is what lets a tile be classed from its
    index (`_crossed`), and so must its aligned windows; a sliding window's
    width divides nothing and asks nothing."""
    L = S // rule.kinds if rule else S
    if rule and (rule.block, rule.kinds) != (1, 1) and (
            S % rule.kinds or L % block_q or L % block_k
            or block_q % rule.block or block_k % rule.block):
        return (f"the tiles do not divide the rule's {rule.kinds} kinds of "
                f"row into blocks of {rule.block}")
    if rule and rule.aligned is not None and (
            S % rule.aligned or rule.aligned % block_q
            or rule.aligned % block_k):
        return (f"the tiles do not divide the sequence into the rule's "
                f"aligned windows of {rule.aligned}")
    if held + _TILE_VMEM > _VMEM_MAX:
        # Mosaic would refuse it: "Ran out of memory in memory space vmem"
        return ("a (batch, head) slice's q, do, statistics and dq leave a "
                "tile no room in VMEM")
    if S % block_q or S % block_k:
        return "the blocks do not divide the sequence"
    # Degenerate blocks (odd/prime S drives _auto_block toward 1): a grid of
    # sub-tile steps loses to the dense path, and sub-8-sublane blocks risk
    # Mosaic compile errors.  Whole-sequence blocks (bq == S) stay allowed
    # for short-sequence/decode shapes.
    if (block_q < 128 and block_q not in (S, L)) \
            or (block_k < 128 and block_k not in (S, L)):
        return "the blocks are narrower than one 128-row tile"
    return None


def _warn_reference(shape, block_q, block_k, reason):
    warnings.warn(
        f"flash attention on shape {tuple(shape)} with blocks "
        f"({block_q}, {block_k}) runs the O(S^2) reference: {reason}",
        AttentionFallbackWarning, stacklevel=4)


def _auto_block(S: int, cap: int) -> int:
    """Largest block <= cap that divides S (so the Pallas path stays
    active for any S with a power-of-two-ish factor, not just S % cap == 0
    — falling back to dense reference attention costs O(S^2) HBM)."""
    b = min(cap, S)
    while b > 1 and S % b:
        b //= 2
    return max(b, 1)


def _auto_tiles(S: int, causal):
    """((block_q, block_k) of the forward, the same of the backward) for a
    call that names no blocks: a function of what a call can see of itself.
    Non-causal attention has no tile to skip and takes the largest block,
    and so does a sequence past `_WHOLE_SEQ_MAX`, whose 1024-blocks skip
    already.  A shorter causal sequence takes the tiles a sweep on a v5e
    found fastest (`tools/chip_kernels.py --sweep`; PERF.md §6, PR 31):
    smaller ones skip more of the square and pay more fixed cost.  Swept:
    S = 1,024 at head_dim 64 and 128, and S = 512 at head_dim 64; the same
    caps won at each, and other lengths take them unmeasured.  Past
    `_WHOLE_SEQ_MAX`, swept at S = 4,096 (D = 128), at S = 8,192 with
    q/k 192 and v 128 wide and at S = 8,192 with 32 query heads on 8
    key/value heads of 64 and on 2 of 128 (ms a layer, tiles of 256 / 512 /
    1,024; PERF.md §6, PR 45: the sweep is PR 44's, whose kernels these
    are).  The one-kernel backward: 7.10 / 4.83 / 5.07,
    34.53 / 28.12 / 27.99, 27.07 / 17.62 / 17.59 and 26.78 / 17.56 / 17.57
    (the two-kernel split it replaced: 8.04, 42.64, 28.68 and 28.59 at
    512); a q tile of another size than the k tile gains nothing (1,024 on
    512 within 0.3 % of square 512; 256 on 512 slower by a tenth, 512 on
    1,024 by a twentieth).  So 512: the fastest at 4,096 and within 0.5 %
    of 1,024 at 8,192.  The forward: 5.27 / 2.95 / 3.25, 22.24 / 13.09 /
    13.76, 19.37 / 9.94 / 10.42: 512 would be 5 to 9 % faster than the
    largest block, which it takes, under 0.7 % of a step and left.  Under
    a rule of two kinds of row (S = 2 x 8,192, 32 on 4 heads of 128, blocks
    of 4: PERF.md §6, PR 54) the forward at 512 / 1,024 took 21.81 / 23.68
    ms a layer and forward + backward 60.53 / 62.40 (backward 512 in
    both): 512-tiles visit 288 of the square's 1,024, 0.889 of the visited
    pairs attended, 1,024-tiles 80 of 256, 0.800, and there the forward
    takes 512.  Under a window (W = 1,024, one sequence, 32 on 4 heads of
    128: PERF.md §6, PR 56; `tools/chip_kernels.py --cases mellum_16k
    mellum_8k`) a tile is a trade of its own: a q tile of 256 visits 5 k
    tiles (0.80 of the visited pairs attended), of 512 three (0.667), of
    1,024 two (0.50), and the two crossed ones are masked whatever their
    size.  ms a layer at 256 / 512 / 1,024: the forward 6.72 / 4.68 / 5.75
    at S = 16,384 and 3.28 / 2.29 / 2.80 at 8,192; forward + backward
    16.52 / 12.70 / 14.97 and 7.92 / 6.04 / 6.88 (the same call with no
    window: 89.05 / 51.50 / 50.86 and 23.05 / 13.73 / 14.03).  So 512 in
    both passes under a window.  A window NARROWER than a pair of those
    tiles was swept too (W = 512, one sequence of 16,384, 64 query heads on
    8 of 128: PERF.md §6, PR 60; `tools/chip_kernels.py --cases laguna_16k`
    and `--sweep laguna-16k`): a q tile of 512 visits 2 k tiles, both
    crossed by both bounds (0.50 of the visited pairs attended), of 256
    three (0.667), of 128 five (0.80), and the smaller tiles' skipping does
    not pay their fixed cost: ms a layer at 128 / 256 / 512, the forward
    17.22 / 9.25 / 7.06, forward + backward 39.37 / 23.18 / 19.42; a q tile
    beside another k tile is no better (256 on 512: 7.10 and 13.37 against
    7.06 and 12.36 square; 512 on 256: 9.59 and 12.66).  Those were WALKS of
    tiles, two or three visits a row of tiles, each with its mask and its
    turn of the softmax.  Taken as ONE band (`_band`; PERF.md §6, PR 64;
    `tools/chip_kernels.py --sweep laguna-16k mellum-16k phi4-16k`) a tile
    is another trade: one visit whatever its size, W' + tile keys for W
    attended (at W = 512: 0.80 of the visited pairs at 128, 0.667 at 256,
    0.50 at 512).  ms a layer at S = 16,384, forward / backward, a tile a
    grid step at 128 / 256 / 512 beside the walk at 512: W = 512 (64 on 8
    of 128) 5.06 / 13.37, 4.02 / 9.06, 4.23 / 10.27 beside 7.06 / 12.36;
    W = 1,024 (32 on 4) 3.27 / 9.72, 3.11 / 6.21, 3.24 / 6.87 beside 4.68 /
    8.02; W = 512 with q, k 64 and v 128 wide (20 on 10) 1.71 / 4.18, 1.38 /
    2.83, 1.43 / 3.22 beside 2.32 / 3.86.  With `_BAND_STEP` = 1,024 rows a
    grid step, at 128 / 256: 3.65 / 8.55 and 3.16 / 8.24; 2.58 / 6.44 and
    2.49 / 5.78; 1.24 / 2.67 and 1.10 / 2.58.  So 256 in both passes
    wherever a band of 256-tiles is taken (W up to 3,072; swept at 512 and
    1,024, wider ones take it unmeasured), and a window too wide for a band
    keeps the walk at 512."""
    rule = _rule(causal)
    L = S // rule.kinds if rule else S      # tiles divide a kind's rows
    if rule and rule.aligned:               # and an aligned window
        L = min(L, rule.aligned)
    whole = _auto_block(L, 1024)
    if S > _WHOLE_SEQ_MAX:
        if S % 256 == 0 and _band(causal, S, 256, False):
            return (256, 256), (256, 256)
        bwd = _auto_block(L, 512)
        # two kinds of row leave a quarter of the square, a window a band
        # of it and aligned windows their triangles (at A = 2,048 tiles of
        # 512 visit 10 of a window's 16, 0.80 of the visited pairs
        # attended; of 1,024 three of 4, 0.667; not swept): there the
        # forward's smaller tile pays
        fwd = bwd if rule and (rule.kinds == 2 or rule.window
                               or rule.aligned) else whole
        return (fwd, fwd), (bwd, bwd)
    if rule is None:
        return (whole, whole), (whole, whole)

    def tile(cap):
        b = _auto_block(L, cap)
        return b if b >= 128 else whole  # under 128 rows is no tile

    fwd, bwd = tile(512), tile(256)
    return (fwd, fwd), (bwd, bwd)


def _resolve(q, S, causal, sm_scale, block_q, block_k):
    """(sm_scale, whole, forward tiles, backward tiles).  ``whole``: a grid
    step takes the whole sequence, which picks the forward's form
    (`_fwd_rows`), the rows of k a step of the backward takes (all, or a k
    tile) and the lane layout's backward; it is decided here, outside the
    jitted kernel calls, and reaches them as a static argument.  Explicit
    blocks override `_auto_tiles` in both passes."""
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    return (scale, S <= _WHOLE_SEQ_MAX) + tuple(
        (min(block_q, S) if block_q else bq, min(block_k, S) if block_k else bk)
        for bq, bk in _auto_tiles(S, causal))


def _tiles_visited(rule, S, block_q, block_k):
    """The tiles of the S x S score square a kernel under ``rule`` visits:
    `_k_spans`' runs, summed over the q tiles."""
    return sum(last - first for i in range(S // block_q)
               for first, last, _ in _k_spans(rule, i, block_q, block_k, S)[2])


def _count_tiles(S, block_q, block_k, causal, heads, band=None):
    """Add one kernel's tiles to the job timeline, as the step is traced:
    `attention.tiles` the (block_q x block_k) tiles of the S x S score
    square, `attention.tiles_skipped` those of them the rule empties (under
    the diagonal: those wholly above it), which the kernel does not visit,
    `attention.pairs_visited` the (query, key) pairs of the tiles it does
    visit, a head and sequence: what the kernel multiplies, whatever the
    rule attends of it.  Once per kernel in the traced program (not per
    head slice or grid step).  Beside them ``heads``: those of the q the
    kernel is given and of the k it reads from HBM (`attention.q_heads`,
    `attention.kv_heads`): a quarter where four query heads share a
    key/value head, equal where a caller repeated k and v first.  Under
    a window's ``band`` (`_band`: its rows, of the pass's own tile) the
    kernel multiplies every row of the sequence by a band, the clamped
    ends as they are: S x band pairs, in whole tiles rounded up, and
    `attention.window_band_kernels` counts the kernel beside
    `attention.window_kernels`."""
    tracing.count("attention.q_heads", heads[0])
    tracing.count("attention.kv_heads", heads[1])
    rule, tiles = _rule(causal), (S // block_q) * (S // block_k)
    if band:
        pairs = S * band
        visited = pl.cdiv(pairs, block_q * block_k)
    else:
        visited = _tiles_visited(rule, S, block_q, block_k)
        pairs = visited * block_q * block_k
    tracing.count("attention.tiles", tiles)
    tracing.count("attention.tiles_skipped", tiles - visited)
    tracing.count("attention.pairs_visited", pairs)
    if rule and rule.window is not None:
        tracing.count("attention.window_kernels")
        tracing.count("attention.window", rule.window)
        tracing.count("attention.window_pairs_visited", pairs)
        if band:
            tracing.count("attention.window_band_kernels")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=False, sm_scale=None,
                    block_q=None, block_k=None, mask=None):
    """Multi-head attention over (batch, heads, seq, head_dim) tensors; k
    and v may have fewer heads than q, a divisor of its count (query head h
    reads key/value head h // group).  ``mask``: (batch, seq, seq) int8,
    not 0 where a (query, key) pair is attended, the same for every head;
    with ``causal`` a pair must pass both.  Every query attends a key.

    Blocks the call does not name come from `_auto_tiles`.  The backward
    is one kernel (5 dots a tile instead of a split's 7): up to
    `_WHOLE_SEQ_MAX` a grid step takes a (b, h) slice whole, tiled inside;
    past it a grid step takes one k tile and dq sums over a slice's tiles
    in VMEM, which holds the slice's q, do, statistics and dq meanwhile
    (`_bwd_held_bytes`: 4 to 5.5 KB a row, so S = 16,384 fits at every
    width a cell has; S = 32,768 does not, where the split compiled, and
    its backward takes the reference under an `AttentionFallbackWarning`:
    `_tiling_problem`)."""
    o, _ = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, mask)
    return o


def _named_residuals(q, k, v, o, lse, mask=None, rows=False):
    """(o, the residuals) of a public forward rule, o and lse under
    `KEPT_RESIDUALS`.  The rule's result IS the named o: a checkpointed
    layer that keeps the names then needs nothing else of the kernel, and
    its replay drops the call.  q, k and v stay unnamed (recomputed), and so
    does a mask, which is among the residuals only when there is one.

    ``rows``: name lse as the head-major kernels write and read it,
    (B*H, S, 1), whose rows fill a lane each on the chip: 128 times the
    bytes of (B, H, S), and no pass over them to pack after the forward
    kernel and unpack before the backward.  On XL (S = 1,024, 48 layers, 52
    MB a layer in rows) those passes took back the 12.6 ms a step that the
    kept forward saved: 37,558 tokens/s packed for the parent's 37,607,
    38,648 in rows; at S = 8,192 they are a twentieth of a forward kernel
    and the rows 268 MB a layer (PERF.md §6, PR 35)."""
    o = checkpoint_name(o, KEPT_RESIDUALS[0])
    if rows:
        B, H, S = lse.shape
        lse = checkpoint_name(lse.reshape(B * H, S, 1),
                              KEPT_RESIDUALS[1]).reshape(B, H, S)
    else:
        lse = checkpoint_name(lse, KEPT_RESIDUALS[1])
    return o, (q, k, v, o, lse) if mask is None else (q, k, v, o, lse, mask)


def _rows_kept(S):
    """Whether a head-major call's lse is kept in the kernels' own layout
    (`_named_residuals`): up to `_WHOLE_SEQ_MAX`, where it is at most half
    a megabyte a (b, h) slice and the kernel too short to hide a pass."""
    return S <= _WHOLE_SEQ_MAX


def _flash_fwd_rule(q, k, v, causal, sm_scale, block_q, block_k, mask=None):
    """`flash_attention`'s forward rule.  The names go on here and not in
    `_flash_fwd`, which the ring calls once per rotating chunk: its
    partials are no residuals of anything."""
    _, res = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, mask)
    return _named_residuals(*res, rows=_rows_kept(q.shape[2]))


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, mask=None):
    """-> (o, (q, k, v, o, lse, and the mask if there is one))."""
    S = q.shape[2]
    scale, whole, (bq, bk), _ = _resolve(q, S, causal, sm_scale, block_q,
                                         block_k)
    reference = functools.partial(reference_attention, sm_scale=scale,
                                  causal=causal)
    problem = _tiling_problem(
        S, bq, bk, 0 if mask is None else _mask_block_bytes(
            S, S if whole else bq), _rule(causal))
    if problem:
        _warn_reference(q.shape, bq, bk, problem)
        o, lse = reference(q, k, v, mask=mask)
    else:
        _count_tiles(S, bq, bk, causal, (q.shape[1], k.shape[1]),
                     _band(causal, S, bq, whole, mask is not None))
        kernel = functools.partial(_pallas_forward, sm_scale=scale,
                                   causal=causal, block_q=bq, block_k=bk,
                                   whole=whole)
        if mask is None:
            o, lse = by_platform(kernel, reference, q, k, v)
        else:
            o, lse = by_platform(
                lambda q, k, v, mask, interpret: kernel(
                    q, k, v, interpret=interpret, mask=mask),
                lambda q, k, v, mask: reference(q, k, v, mask=mask),
                q, k, v, mask)
    return o, (q, k, v, o, lse) if mask is None else (q, k, v, o, lse, mask)


def _flash_bwd(causal, sm_scale, block_q, block_k, res, do, delta=None):
    """-> (dq, dk, dv)."""
    q, k, v, o, lse, *mask = res
    mask = mask[0] if mask else None
    S = q.shape[2]
    scale, whole, _, (bq, bk) = _resolve(q, S, causal, sm_scale, block_q,
                                         block_k)
    # delta = rowsum(do * o): cheap elementwise+reduce, XLA fuses it.
    # Callers looping over K/V chunks (ring attention) pass it precomputed
    # — it only depends on the q side, so per-chunk recompute is waste.
    if delta is None:
        delta = jnp.sum(
            do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    held = 0 if whole else _bwd_held_bytes(S, q.shape[-1], v.shape[-1],
                                           q.dtype)
    if mask is not None:
        held += _mask_block_bytes(S, S if whole else bk)
    problem = _tiling_problem(S, bq, bk, held, _rule(causal))
    if problem:
        _warn_reference(q.shape, bq, bk, problem)
        return _reference_backward(q, k, v, lse, do, delta, scale, causal,
                                   mask)
    band = _band(causal, S, bk, whole, mask is not None)
    _count_tiles(S, bq, bk, causal, (q.shape[1], k.shape[1]), band)

    def kernel(q, k, v, o, lse, do, delta, mask=None, *, interpret):
        return _pallas_backward(q, k, v, o, lse, do, scale, causal, bq, bk,
                                S if whole else _band_step(S, bk, band),
                                interpret, delta=delta, mask=mask)

    def reference(q, k, v, o, lse, do, delta, mask=None):
        return _reference_backward(q, k, v, lse, do, delta, scale, causal,
                                   mask)

    return by_platform(kernel, reference, q, k, v, o, lse, do, delta,
                       *(() if mask is None else (mask,)))


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, res, do):
    """`flash_attention`'s backward rule: a mask, if there is one, has no
    cotangent."""
    return (*_flash_bwd(causal, sm_scale, block_q, block_k, res, do), None)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _bshd_lanes_ok(q, S, bq, bk, causal=False):
    B, _, H, D = q.shape
    return (_lanes_config(H, D) is not None and S % 128 == 0
            and not _windowed(causal)      # head-major only: `_form`
            and _tiling_problem(S, bq, bk, rule=_rule(causal)) is None)


def _tr(x):
    return x.transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_bshd(q, k, v, causal=False, sm_scale=None,
                         block_q=None, block_k=None, mask=None):
    """Multi-head attention over (batch, seq, heads, head_dim) tensors —
    the layout models naturally produce from the fused qkv projection.

    ``mask`` as `flash_attention`'s.

    When the lane tiling applies (head_dim divides 128, heads fill whole
    lane blocks, k and v have q's heads, no mask; for the backward S <=
    `_WHOLE_SEQ_MAX`: the long backward is head-major only) the kernels
    index heads through 128-wide lane blocks and no
    (B,S,H,D) <-> (B,H,S,D) transpose ever materializes; otherwise the
    call transposes to the bhsd kernels (still flash, just with the
    transpose cost the lane path avoids)."""
    o, _ = _flash_fwd_bshd(q, k, v, causal, sm_scale, block_q, block_k, mask)
    return o


def _flash_fwd_bshd(q, k, v, causal, sm_scale, block_q, block_k, mask=None):
    S = q.shape[1]
    scale, whole, (bq, bk), _ = _resolve(q, S, causal, sm_scale, block_q,
                                         block_k)
    # the lane layout slices every operand's heads out of the same lanes,
    # and reads no mask
    if mask is None and k.shape == v.shape == q.shape \
            and _bshd_lanes_ok(q, S, bq, bk, causal):
        _count_tiles(S, bq, bk, causal, (q.shape[2], k.shape[2]))

        def reference(q, k, v):
            o, lse = reference_attention(_tr(q), _tr(k), _tr(v), scale,
                                          causal)
            return _tr(o), lse

        o, lse = by_platform(
            functools.partial(_pallas_forward_bshd, sm_scale=scale,
                              causal=causal, block_q=bq, block_k=bk,
                              whole=whole),
            reference, q, k, v)
        return _named_residuals(q, k, v, o, lse)
    # of o's two layouts the one named is the caller's (B, S, H, D), which
    # is also the rule's result; the backward transposes it to head-major
    # as it always did
    ot, (_, _, _, _, lse, *_) = _flash_fwd(_tr(q), _tr(k), _tr(v), causal,
                                           sm_scale, block_q, block_k, mask)
    return _named_residuals(q, k, v, _tr(ot), lse, mask, rows=_rows_kept(S))


def _flash_bwd_bshd(causal, sm_scale, block_q, block_k, res, do):
    q, k, v, o, lse, *mask = res
    S = q.shape[1]
    scale, whole, _, (bq, bk) = _resolve(q, S, causal, sm_scale, block_q,
                                         block_k)
    if whole and not mask and k.shape == v.shape == q.shape \
            and _bshd_lanes_ok(q, S, bq, bk, causal):
        _count_tiles(S, bq, bk, causal, (q.shape[2], k.shape[2]))

        def reference(q, k, v, o, lse, do):
            qt, kt, vt, ot, dot = map(_tr, (q, k, v, o, do))
            delta = jnp.sum(
                dot.astype(jnp.float32) * ot.astype(jnp.float32), axis=-1)
            return tuple(map(_tr, _reference_backward(
                qt, kt, vt, lse, dot, delta, scale, causal)))

        return (*by_platform(
            functools.partial(_pallas_backward_bshd, sm_scale=scale,
                              causal=causal, block_q=bq, block_k=bk),
            reference, q, k, v, o, lse, do), None)
    dq, dk, dv = _flash_bwd(
        causal, sm_scale, block_q, block_k,
        (_tr(q), _tr(k), _tr(v), _tr(o), lse, *mask), _tr(do))
    return _tr(dq), _tr(dk), _tr(dv), None


flash_attention_bshd.defvjp(_flash_fwd_bshd, _flash_bwd_bshd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_bshd_lse(q, k, v, causal=False, sm_scale=None,
                             block_q=None, block_k=None, mask=None):
    """`flash_attention_bshd` and the kernels' row statistics beside its
    result: (o, lse (B, H, S) float32, the log of each query's sum of
    exp(score) over the keys it attends, natural units).  For a caller that
    needs the probabilities again (an indexer's loss:
    `ops/sparse_index.py`).  A statistic: nothing is differentiated through
    lse, whose cotangent is dropped."""
    return _flash_fwd_bshd_lse(q, k, v, causal, sm_scale, block_q, block_k,
                               mask)[0]


def _flash_fwd_bshd_lse(q, k, v, causal, sm_scale, block_q, block_k,
                        mask=None):
    o, res = _flash_fwd_bshd(q, k, v, causal, sm_scale, block_q, block_k,
                             mask)
    return (o, res[4]), res


flash_attention_bshd_lse.defvjp(
    _flash_fwd_bshd_lse,
    lambda causal, sm_scale, block_q, block_k, res, g: _flash_bwd_bshd(
        causal, sm_scale, block_q, block_k, res, g[0]))

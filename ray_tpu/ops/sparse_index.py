"""Attention that selects its keys: a lightning indexer's scores, the exact
top-k of every query's row of them as a mask, and the indexer's own loss
(DeepSeek-V3.2-Exp's sparse attention, which Keye-VL-2.0's `sa_config`
names).  Shared by `models/keye_vl.py` and whatever selects next.

    I_{t,s} = sum_j w_{t,j} * relu(q_{t,j} . k_s)        for s <= t
    S_t     = the min(k, t + 1) keys s <= t of largest I_{t,s}; of equal
              scores the lower s first
    L_I     = mean_t KL(p_t || softmax_{s in S_t} I_{t,s}),  p_t the main
              attention's probabilities over S_t summed over its heads and
              L1-normalised, a constant

The mask is `ops/flash_attention.py`'s operand: (B, S, S) int8, 1 where t
attends s.  No step holds a head's scores of more than a tile, never an
S x S array a head; what it holds summed over the heads is the (B, S, S)
float32 of I and, under the gradient, of dL_I / dI.  Four Mosaic kernels
keep the rest in VMEM.  The SCORES are one kernel
a layer (`_pallas_scores`: every indexer head's q . k', its relu, its
weight and the sum over the heads a (q tile, k tile) at a time, the tiles
above the diagonal written as -inf and not computed) and their backward
another (`_pallas_scores_bwd`, under `index_scores`' own `jax.custom_vjp`:
the products made again once a tile and contracted into dq, dk and dw, the
one key head's dk summed in VMEM over a sequence's tiles).  The SELECTION
is one kernel a layer (`_pallas_select`): a q tile's row of scores lands in
VMEM once, the key tiles above the diagonal not read, and the ordering,
both searches, the compare and the int8 write happen there; the kernel
writes the tile's rows of the mask where they lie in (B, S, S), the free
rows' triangle too: nothing of the selection but the scores' read and the
mask's write goes through HBM, and no XLA pass follows.  The LOSS is one
kernel a layer, whole (`_pallas_loss`): its target, the main attention's
probabilities summed over its heads (every head's QK', its exponent and
the sum), stays in VMEM a q tile's whole row at a time beside the row's
selected scores, the key tiles above the diagonal not visited; the row's
sums run beside it, and on the row's last tile the kernel writes each
query's KL and the gradient to the scores where it lies in (B, S, S):
nothing else of the loss goes through HBM, and no XLA pass follows.  All
four run as the flash kernels do (`ops.by_platform`): compiled where the
step is lowered for a TPU, interpreted elsewhere up to the tests' sizes,
and the same arithmetic in plain XLA by blocks of ``block`` query rows
(`sa_config`'s `q_chunk_size`), one sequence's block at a time
(`_by_blocks`: `_scores_reference`, `_scores_reference_bwd`,
`_select_reference`, `_loss_reference` over `_target_reference`) beyond
them and for a shape a kernel cannot tile (`_scores_tiles`,
`_select_tiles`, `_target_tiles`).

The selection is a threshold search and no `jax.lax.top_k`: a top-k gives
the keys' indices, 2,048 a row for 16,384 rows a layer, and a mask of them
is a scatter, which the chip runs serially; and XLA:TPU's top-k at a k of
thousands is a sort of the whole row.  The k-th largest score of a row is
found by its bits from the top (the float32 scores taken as integers that
order as they do; a pass counts the keys that reach a candidate), and the
mask is a compare with it; of the keys that TIE with the k-th the lowest
are taken, up to the last that still fits, which the same search finds
over the keys' places.  In the kernel a pass is one bit, one compare and
one count over the row in VMEM (32 over the scores, 14 over the places at
8,192 keys: a load costs nothing beside the vector unit there, and two
bits a pass were slower); in the plain XLA form two bits, three compares
and three counts over the block through HBM (16 and 7).  Every row pays
both searches whatever its scores are: a step's time does not depend on
how many rows have a tie (one row in a thousand at float32 sums of
bfloat16 products, so two blocks in five, which a branch taken only then
made the step's time wander by).  The result is `jax.lax.top_k`'s set,
exactly, in either form.

Counts itself on the job timeline as the step is traced:
`attention.indexer_heads`, `attention.score_tiles`,
`attention.score_tiles_skipped` (`index_scores`), `attention.keys_selected`,
`attention.pairs_causal`, `attention.pairs_selected`, `attention.mask_bytes`,
`attention.select_rows_fused` (`select_top_k`: the last the query rows whose
selection the kernel made, 0 where the shape took the plain form),
`attention.target_tiles`, `attention.target_tiles_skipped`,
`attention.loss_rows_fused` (`indexer_loss`: the loss kernel's tiles, and
the query rows whose loss and gradient it made, 0 where the shape took
the plain form; a recomputed layer is traced once).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import by_platform
from ray_tpu.util import tracing

# the loss kernel's (q tile, k tile) at most (`tools/chip_kernels.py --sweep
# target-8k` on a v5e, ms a layer of the keye cell's two sequences, loss and
# gradient, and the seconds it took to compile: 128 x 512 4.86 / 1.9,
# 128 x 1,024 4.95 / 3.1, 256 x 256 4.67 / 2.4, 256 x 512 4.63 / 3.4,
# 256 x 1,024 4.72 / 5.7; 512 x 512 asks for 77 MiB; the target alone took
# 3.58 and the passes after it 6.2 to 11.4: PERF.md §6, PR 51), and the scoped
# VMEM it may ask for: a step holds every head's q tile, a k tile of every key
# head and three times the q tile's whole row in float32 (34 MiB at the cell's
# 8,192 keys; the v5e has 128)
_TARGET_TILE = (256, 512)
_TARGET_VMEM_MAX = 64 << 20
# the scores' kernels' (q tile, k tile) at most (`tools/chip_kernels.py
# --sweep scores-8k` on a v5e, forward / backward ms a layer of the keye
# cell's two sequences and the seconds the backward took to compile:
# 128 x 512 2.13 / 6.23 / 1.4, 256 x 256 2.18 / 7.71 / 1.7, 256 x 512 2.01 /
# 5.49 / 2.7, 256 x 1,024 2.03 / 5.38 / 5.3, 512 x 512 1.95 / 5.05 / 6.1,
# 512 x 1,024 2.01 / 5.15 / 11.3: the last 8 % of the backward cost a run
# 3 s of compiling before its first step; PERF.md §6, PR 49), and the scoped
# VMEM they may ask for: a step of the backward holds every head's q tile,
# its dq in float32 and the sequence's dk
_SCORES_TILE = (256, 512)
_SCORES_VMEM_MAX = 64 << 20
# the selection's kernel's (q tile, k tile) at most and the bits of a threshold
# it finds a pass (`tools/chip_kernels.py --sweep select-8k` on a v5e, ms a
# layer of the keye cell's two sequences and the seconds it took to compile,
# at one bit a pass: 64 x 1,024 3.22 / 0.5, 128 x 256 3.30 / 0.5, 128 x 512
# 2.86 / 0.5, 128 x 1,024 2.72 / 0.5, 128 x 2,048 2.78 / 0.8, 256 x 512 3.77 /
# 0.7, 256 x 1,024 4.16 / 0.7, 512 x 512 5.17 / 0.9: a pass's counts of 128
# rows are 16 registers, of 256 half the file; at two bits, three counts a
# pass: 64 x 1,024 4.03 / 0.5, 128 x 1,024 4.15 / 0.7, 256 x 1,024 6.34 / 1.3,
# 512 x 512 8.10 / 1.8; the plain XLA form 10.03 alone and 13.2 in the step;
# PERF.md §6, PR 61), and the scoped VMEM it may ask for: a q tile's row of
# integers twice and its row of the mask (9 MiB at the cell's 8,192 keys)
_SELECT_TILE = (128, 1024)
_SELECT_BITS = 1
_SELECT_VMEM_MAX = 64 << 20


def _by_blocks(fn, block, rows, whole=(), first=0):
    """``fn(start, *a block of each of rows, *one sequence's of whole)``
    for every block of ``block`` query rows of every sequence, one at a
    time (a `lax.map` over the blocks in one over the sequences): a
    block's temporaries are alive once.  ``rows``: arrays (B, R, ...), row
    i of them query ``first + i``; ``whole``: arrays (B, ...) every block
    reads all of.  -> fn's results, each (B, R / block, ...)."""
    n = rows[0].shape[1] // block
    starts = first + block * jnp.arange(n)

    def sequence(args):
        rows_b, whole_b = args
        cut = tuple(x.reshape(n, block, *x.shape[1:]) for x in rows_b)
        return jax.lax.map(lambda r: fn(r[0], *r[1], *whole_b),
                           (starts, cut))

    return jax.lax.map(sequence, (tuple(rows), tuple(whole)))


def _rows(y):
    """(B, blocks, block, ...) -> (B, rows, ...)."""
    return y.reshape(y.shape[0], y.shape[1] * y.shape[2], *y.shape[3:])


def _block(S, block):
    block = min(block, S)
    if S % block:
        raise ValueError(f"a sequence of {S} is no whole number of blocks "
                         f"of {block} query rows")
    return block


def _causal(start, rows, S):
    """(rows, S): whether query ``start + i`` sees key s."""
    return (start + jnp.arange(rows))[:, None] >= jnp.arange(S)[None]


def _count_tiles(name, B, S, tiles):
    """Add a layer's ``name`` tiles to the job timeline, as the step is
    traced: `attention.<name>_tiles` the (q tile, k tile) grid steps of the
    S x S square that the kernel computes, over the B sequences, and
    `attention.<name>_tiles_skipped` those wholly above the diagonal, which
    it fills and does not compute.  Both 0 without ``tiles``: the shape
    took the plain reference.  Called by `index_scores` and `indexer_loss`
    themselves, once a traced layer: a custom rule's functions are all
    traced under a gradient."""
    on = above = 0
    if tiles:
        block_q, block_k = tiles
        on = sum(min(S // block_k, ((i + 1) * block_q - 1) // block_k + 1)
                 for i in range(S // block_q))
        above = (S // block_q) * (S // block_k) - on
    tracing.count(f"attention.{name}_tiles", B * on)
    tracing.count(f"attention.{name}_tiles_skipped", B * above)


def _tile(extent, cap, unit, whole=None):
    """The largest power-of-two part of ``cap`` that divides ``extent``, or
    None where that is neither the array's ``whole`` dimension (``extent``
    if not given) nor a multiple of what Mosaic tiles the dimension by."""
    t = min(cap, extent)
    while extent % t:
        t //= 2
    return t if t == (whole or extent) or t % unit == 0 else None


def _lanes(d):
    """A width padded to whole lanes, as VMEM holds it."""
    return -(-d // 128) * 128


def _kernel_or_reference(kernel, reference, tiles):
    """What runs a kernel's work: ``kernel`` at ``tiles`` where the call is
    lowered for a TPU and as `ops.by_platform` says elsewhere, or
    ``reference`` on every platform for a shape without tiles."""
    if tiles is None:
        return reference
    return functools.partial(by_platform, functools.partial(
        kernel, block_q=tiles[0], block_k=tiles[1]), reference)


def _block_scores(start, q, w, k):
    """One block of queries' scores in plain XLA: q (rows, J, D), w
    (rows, J) float32 and k (S, D), the block's first query ``start`` ->
    (rows, S) float32."""
    products = jnp.einsum("qjd,sd->jqs", q, k,
                          preferred_element_type=jnp.float32)
    total = jnp.sum(w.T[:, :, None] * jax.nn.relu(products), axis=0)
    return jnp.where(_causal(start, *total.shape), total, -jnp.inf)


def _scores_reference(q, k, w, *, block):
    """`index_scores` in plain XLA by blocks of ``block`` query rows, and
    what the kernels are held to: a block's (J, block, S) float32 products
    go through HBM, one block at a time."""
    return _rows(_by_blocks(_block_scores, block, (q, w), (k,)))


def _scores_reference_bwd(q, k, w, g, *, block):
    """The gradients of `_scores_reference` to q, k and w under the
    cotangent g (B, S, S), block by block: a block's products are made
    again, differentiated and dropped; the blocks' parts of dk are summed
    in float32."""
    def one(start, q, w, g, k):
        back = jax.vjp(functools.partial(_block_scores, start), q, w, k)[1]
        return back(g)

    dq, dw, dk = _by_blocks(one, block, (q, w, g), (k,))
    return _rows(dq), jnp.sum(dk, axis=1, dtype=jnp.float32).astype(
        k.dtype), _rows(dw)


def _tile_causal(i, j, block_q, block_k):
    """(block_q, block_k): whether the query of q tile i sees the key of k
    tile j."""
    shape = (block_q, block_k)
    return i * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0) \
        >= j * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _scores_kernel(q_ref, k_ref, w_ref, o_ref, *, block_q, block_k):
    """One (q tile, k tile) of a sequence's scores, the heads inside: q_ref
    (block_q, J D) the heads side by side in the lanes as they lie, k_ref
    (block_k, D), w_ref (block_q, J) float32, o_ref (block_q, block_k)
    float32.  A tile wholly above the diagonal is written as -inf and
    nothing of it is computed (its k block is the last visited tile's, not
    fetched again: `_pallas_scores`' index maps)."""
    i, j = pl.program_id(1), pl.program_id(2)
    visited = j * block_k < (i + 1) * block_q
    D = k_ref.shape[1]

    @pl.when(visited)
    def _():
        k = k_ref[...]
        total = None
        for h in range(w_ref.shape[1]):     # unrolled: a head's products
            p = jax.lax.dot_general(        # pass while the last one's sum
                q_ref[:, h * D:(h + 1) * D], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            term = w_ref[:, h:h + 1] * jax.nn.relu(p)
            total = term if total is None else total + term
        o_ref[...] = jnp.where(_tile_causal(i, j, block_q, block_k), total,
                               -jnp.inf)

    @pl.when(jnp.logical_not(visited))
    def _():
        o_ref[...] = jnp.full_like(o_ref, -jnp.inf)


def _scores_bwd_kernel(q_ref, k_ref, w_ref, g_ref, dq_ref, dk_ref, dw_ref,
                       dq_acc, dw_acc, *, block_q, block_k):
    """One (q tile, k tile) of the scores' backward, the heads inside: each
    head's products made again ONCE and contracted into dq, dk and dw.
    q_ref and dq_ref (block_q, J D), k_ref (block_k, D), w_ref and dw_ref
    (block_q, J) float32, g_ref (block_q, block_k) float32; dk_ref (S, D)
    float32 stays in VMEM while a sequence's tiles pass and every tile adds
    its rows (it is the one key head's: 2 MB at 8,192 x 64), zeroed at the
    sequence's first step.  The k tiles are the inner grid axis: dq and dw
    sum over them in the float32 scratch ``dq_acc``, ``dw_acc``, zeroed at
    a q tile's first k tile and written at its last on the diagonal; the
    tiles above it are not visited.  relu's gradient at 0 is 0."""
    i, j = pl.program_id(1), pl.program_id(2)
    last = ((i + 1) * block_q - 1) // block_k
    D = k_ref.shape[1]

    @pl.when((i == 0) & (j == 0))
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    @pl.when(j <= last)
    def _():
        k = k_ref[...]
        g = jnp.where(_tile_causal(i, j, block_q, block_k), g_ref[...], 0.0)
        dk = jnp.zeros(k.shape, jnp.float32)
        for h in range(w_ref.shape[1]):
            cols = slice(h * D, (h + 1) * D)
            q = q_ref[:, cols]
            p = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            r = jnp.where(p > 0, g, 0.0)
            dw_acc[:, h:h + 1] += jnp.sum(r * p, axis=1, keepdims=True)
            a = (r * w_ref[:, h:h + 1]).astype(q.dtype)
            dq_acc[:, cols] += jnp.dot(a, k,
                                       preferred_element_type=jnp.float32)
            dk = dk + jax.lax.dot_general(
                a, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        dk_ref[rows, :] += dk

    @pl.when(j == last)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)
        dw_ref[...] = dw_acc[...]


def _scores_vmem_bytes(q, block_q, block_k):
    """What a grid step of `_scores_bwd_kernel` (the larger of the two)
    holds in VMEM: its blocks twice (a width padded to whole lanes), the
    float32 scratch, the sequence's dk and a tile's float32
    temporaries."""
    _, S, J, D = q.shape
    width = q.dtype.itemsize
    tile = block_q * _lanes(block_k) * 4
    blocks = 2 * block_q * _lanes(J * D) * width \
        + block_k * _lanes(D) * width + 2 * block_q * _lanes(J) * 4 + tile
    scratch = block_q * (_lanes(J * D) + _lanes(J)) * 4
    return 2 * blocks + scratch + 2 * S * _lanes(D) * 4 + 6 * tile


def _scores_tiles(q, block):
    """(q tile, k tile) of the scores' kernels for the indexer's queries q
    (B, S, J, D) by blocks of ``block`` rows, or None for a shape they
    cannot tile, which takes `_scores_reference` on every platform: a q
    tile divides the block and a k tile the sequence, each the largest
    power-of-two part of `_SCORES_TILE`'s that does; a tile that is not the
    whole sequence is a multiple of what Mosaic tiles it by (16 rows of a
    two-byte q, 128 lanes of scores); a head's columns do not straddle a
    128-lane tile of q's row; a step's blocks fit `_SCORES_VMEM_MAX`."""
    S, D = q.shape[1], q.shape[3]
    tiles = (_tile(block, _SCORES_TILE[0], 16, whole=S),
             _tile(S, _SCORES_TILE[1], 128))
    if None in tiles or (128 % D and D % 128) \
            or _scores_vmem_bytes(q, *tiles) > _SCORES_VMEM_MAX:
        return None
    return tiles


def _last_on_diagonal(i, j, block_q, block_k):
    """k tile j of q tile i, or for one above the diagonal the last on it:
    a skipped step's blocks are those already held, fetched once."""
    return jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def _pallas_scores(q, k, w, *, block_q, block_k, interpret):
    """`_scores_reference` as one Mosaic kernel a layer: grid (sequences,
    q tiles, k tiles); q as it lies, (B, S, J D) with a head's columns
    sliced from the lanes in the kernel: nothing is transposed."""
    B, S, J, D = q.shape
    visited = functools.partial(_last_on_diagonal, block_q=block_q,
                                block_k=block_k)
    call = pl.pallas_call(
        functools.partial(_scores_kernel, block_q=block_q, block_k=block_k),
        grid=(B, S // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, J * D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, D),
                         lambda b, i, j: (b, visited(i, j), 0)),
            pl.BlockSpec((None, block_q, J), lambda b, i, j: (b, i, 0))],
        out_specs=pl.BlockSpec((None, block_q, block_k),
                               lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, S, S), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_SCORES_VMEM_MAX))
    return call(q.reshape(B, S, J * D), k, w)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def _pallas_scores_bwd(q, k, w, g, *, block_q, block_k, interpret):
    """`_scores_reference_bwd` as one Mosaic kernel a layer: the same grid,
    the k tiles innermost and in order (dq, dw and the sequence's dk sum
    over them in VMEM) -> (dq in q's type, dk in k's, dw float32)."""
    B, S, J, D = q.shape
    visited = functools.partial(_last_on_diagonal, block_q=block_q,
                                block_k=block_k)
    heads = pl.BlockSpec((None, block_q, J * D), lambda b, i, j: (b, i, 0))
    weights = pl.BlockSpec((None, block_q, J), lambda b, i, j: (b, i, 0))
    call = pl.pallas_call(
        functools.partial(_scores_bwd_kernel, block_q=block_q,
                          block_k=block_k),
        grid=(B, S // block_q, S // block_k),
        in_specs=[
            heads,
            pl.BlockSpec((None, block_k, D),
                         lambda b, i, j: (b, visited(i, j), 0)),
            weights,
            pl.BlockSpec((None, block_q, block_k),
                         lambda b, i, j: (b, i, visited(i, j)))],
        out_specs=[heads,
                   pl.BlockSpec((None, S, D), lambda b, i, j: (b, 0, 0)),
                   weights],
        out_shape=[jax.ShapeDtypeStruct((B, S, J * D), q.dtype),
                   jax.ShapeDtypeStruct((B, S, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, S, J), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, J * D), jnp.float32),
                        pltpu.VMEM((block_q, J), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_SCORES_VMEM_MAX))
    dq, dk, dw = call(q.reshape(B, S, J * D), k, w, g)
    return dq.reshape(q.shape), dk.astype(k.dtype), dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _scores(q, k, w, block, tiles):
    return _scores_fwd(q, k, w, block, tiles)[0]


def _scores_fwd(q, k, w, block, tiles):
    scores = _kernel_or_reference(_pallas_scores, functools.partial(
        _scores_reference, block=block), tiles)
    return scores(q, k, w), (q, k, w)


def _scores_bwd(block, tiles, residuals, g):
    backward = _kernel_or_reference(_pallas_scores_bwd, functools.partial(
        _scores_reference_bwd, block=block), tiles)
    return backward(*residuals, g)


_scores.defvjp(_scores_fwd, _scores_bwd)


def index_scores(q, k, w, block=512):
    """q (B, S, J, D) the indexer's J query heads, k (B, S, D) its one key
    head, w (B, S, J) the heads' weights -> I (B, S, S) float32, -inf
    above the diagonal: sum_j w_j relu(q_j . k), the products in float32
    from q's and k's type, the weighted sum in float32.  Differentiable in
    q, k and w under a rule of its own that keeps q, k and w alone: the
    heads' products are made again by the backward pass, and in either
    pass are one tile's in VMEM (`_pallas_scores`, `_pallas_scores_bwd`),
    or one block's through HBM where `_scores_tiles` declines the shape
    (`_scores_reference`)."""
    B, S, J = q.shape[:3]
    block = _block(S, block)
    tiles = _scores_tiles(q, block)
    tracing.count("attention.indexer_heads", J)
    _count_tiles("score", B, S, tiles)
    return _scores(q, k, w.astype(jnp.float32), block, tiles)


def _ordered(x):
    """float32 -> uint32 that order as the floats do, -0.0 with 0.0."""
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    signed = bits ^ ((bits >> 31) & 0x7FFFFFFF)
    return jax.lax.bitcast_convert_type(signed, jnp.uint32) \
        ^ jnp.uint32(0x80000000)


def _kth_largest(u, k, bits=32):
    """u (rows, S) uint32 under 2^``bits`` (even), k (rows,) int32 in [1, S]
    -> (rows,) uint32: each row's k-th largest value, the largest T that k
    of the row's values reach.  Two bits of T a pass, from the top: of the
    three candidates that set them, those that k values still reach are the
    lower ones, so their number is the two bits."""
    def two_bits(i, T):
        shift = (bits - 2 - 2 * i).astype(jnp.uint32)
        reached = [jnp.sum(u >= (T | (jnp.uint32(j) << shift))[:, None],
                           axis=1, dtype=jnp.int32) >= k for j in (1, 2, 3)]
        return T | (sum(r.astype(jnp.uint32) for r in reached) << shift)
    return jax.lax.fori_loop(0, bits // 2, two_bits,
                             jnp.zeros(u.shape[:1], jnp.uint32))


def _select_reference(scores, *, top_k, block):
    """`select_top_k` in plain XLA by blocks of ``block`` query rows, what
    `_pallas_select` is held to and what a shape it declines runs: a
    block's scores, their integers, the ties' places and every pass of
    both searches over them go through HBM, and the blocks are joined to
    the free rows' triangle by a copy of the whole mask."""
    B, S, _ = scores.shape
    free = min(top_k // block * block, S)   # rows whose whole block is free

    # a key's place counted from the END (S - s, under 2^place_bits): of
    # the keys that tie, those of largest place are the lowest keys
    place_bits = 2 * -(-S.bit_length() // 2)

    def select(start, scores):
        causal = _causal(start, block, S)
        k = jnp.minimum(top_k, start + jnp.arange(block) + 1)
        u = _ordered(scores)
        kth = _kth_largest(u, k)[:, None]
        above = u > kth
        need = k - jnp.sum(above, axis=1, dtype=jnp.int32)    # of the ties
        place = jnp.where(u == kth, jnp.uint32(S) - jnp.arange(
            S, dtype=jnp.uint32)[None], jnp.uint32(0))
        last = _kth_largest(place, need, place_bits)[:, None]  # >= 1
        return ((above | (place >= last)) & causal).astype(jnp.int8)

    parts = [jnp.broadcast_to(_causal(0, free, S).astype(jnp.int8),
                              (B, free, S))]
    if free < S:
        parts.append(_rows(_by_blocks(select, block, (scores[:, free:],),
                                      first=free)))
    return jnp.concatenate(parts, axis=1)


_INT_MIN = -2 ** 31


def _select_kernel(scores_ref, mask_ref, row, out, threshold, landed, sent,
                   *, top_k, block_q, block_k, bits):
    """One q tile's rows of a sequence's mask, the selection whole.
    scores_ref (B, S, S) float32 and mask_ref (B, S, S) int8 where XLA put
    them, read and written by this kernel's own copies, a (block_q,
    block_k) tile each; only the key tiles at or under the q tile's
    diagonal are read (the scores above it are -inf by `index_scores`'
    word: no key there is ever among a row's top_k), and a q tile wholly
    within the first ``top_k`` rows reads none: its rows attend every key
    they see.

    A searched tile: its row of scores lands in ``row`` ((2 slots, k
    tiles, block_q, block_k) int32: the float32 bits as they are), the
    NEXT searched tile's row starting for the other slot before anything
    is counted, and is turned in place into integers that order, signed,
    as the floats do (-0.0 with 0.0: `_ordered` less its last flip).  The
    k-th largest of each query's row is found by its bits from the top,
    ``bits`` a pass (`search`: the candidates that set them, a count a
    candidate of the keys that reach it, a lane at a time in (block_q,
    128) and across the lanes once a pass).  The row is then turned into
    what the SAME search finishes on: S + 1 for a key above the k-th, the
    key's place from the end (S - s) for one that ties with it, 0 under
    it; the k-th largest of those is the last place that still fits, and
    a key is selected where it reaches that.  Every row pays both
    searches; no branch looks at a score.

    Every tile: the compare with the rows' ``threshold`` (the least
    integer for a free tile: every key), the causal `and` and the int8
    write into ``out`` ((k tiles, block_q, block_k)), a copy a tile to its
    place in HBM, which the next q tile's search hides: they are waited
    for before ``out`` is written again, and at the grid's last step.  The
    tiles above the diagonal are zeroed once a sequence: a later q tile's
    diagonal lies further right."""
    b, i = pl.program_id(0), pl.program_id(1)
    n_q, n_k = pl.num_programs(1), out.shape[0]
    step, steps = b * n_q + i, pl.num_programs(0) * n_q
    S = n_k * block_k
    tile = (block_q, block_k)
    width = min(block_k, 128)
    slot = step % 2
    ints = row.at[slot]
    as_bits = scores_ref.bitcast(jnp.int32)

    def last_tile(i):
        return ((i + 1) * block_q - 1) // block_k

    def searched(i):
        return (i + 1) * block_q > top_k

    def visited(body, i=i):     # body(j) over the key tiles of q tile i
        def one(j, carry):
            body(j)
            return carry
        jax.lax.fori_loop(0, last_tile(i) + 1, one, 0)

    def fetch(b, i, slot, j):
        return pltpu.make_async_copy(
            as_bits.at[b, pl.ds(i * block_q, block_q),
                       pl.ds(j * block_k, block_k)],
            row.at[slot, j], landed.at[slot, j])

    def send(j):
        return pltpu.make_async_copy(
            out.at[j], mask_ref.at[b, pl.ds(i * block_q, block_q),
                                   pl.ds(j * block_k, block_k)], sent)

    def sent_all():
        for j in range(n_k):    # same-sized, so any of them counts one
            send(j).wait()

    def search(k, least, n_bits):
        """(block_q, 1): each row's k-th largest of ``ints``' integers,
        which lie in [least, least + 2^n_bits)."""
        def one(p, found):
            shift = n_bits - bits * (p + 1)
            candidates = [jnp.broadcast_to(found ^ (jnp.int32(c) << shift),
                                           (block_q, width))
                          for c in range(1, 1 << bits)]

            def count(j, reached):
                x = ints[j]
                return tuple(r + functools.reduce(jnp.add, (
                    jnp.where(x[:, c:c + width] >= candidate, 1, 0)
                    for c in range(0, block_k, width)))
                    for r, candidate in zip(reached, candidates))

            reached = jax.lax.fori_loop(
                0, last_tile(i) + 1, count,
                (jnp.zeros((block_q, width), jnp.int32),) * len(candidates))
            # the candidates that k keys reach are the lower ones: their
            # number is the pass's bits
            digit = sum((jnp.sum(r, axis=1, keepdims=True) >= k).astype(
                jnp.int32) for r in reached)
            return found ^ (digit << shift)

        return jax.lax.fori_loop(0, n_bits // bits, one,
                                 jnp.full((block_q, 1), least, jnp.int32))

    @pl.when((step == 0) & searched(i))
    def _():
        visited(lambda j: fetch(b, i, slot, j).start())

    wraps = i + 1 == n_q
    next_b, next_i = jnp.where(wraps, b + 1, b), jnp.where(wraps, 0, i + 1)

    @pl.when((step + 1 < steps) & searched(next_i))
    def _():
        visited(lambda j: fetch(next_b, next_i, 1 - slot, j).start(), next_i)

    @pl.when(jnp.logical_not(searched(i)))
    def _():
        threshold[...] = jnp.full_like(threshold, _INT_MIN)

    @pl.when(searched(i))
    def _():
        k = jnp.minimum(top_k, i * block_q + 1 + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0))

        def order(j):
            fetch(b, i, slot, j).wait()
            x = ints[j]
            x = jnp.where(x == _INT_MIN, 0, x)      # -0.0
            ints[j] = x ^ ((x >> 31) & 0x7FFFFFFF)

        visited(order)
        kth = search(k, _INT_MIN, 32)

        def place(j):
            x = ints[j]
            from_end = (S - j * block_k) - jax.lax.broadcasted_iota(
                jnp.int32, tile, 1)
            ints[j] = jnp.where(x > kth, S + 1,
                                jnp.where(x == kth, from_end, 0))

        visited(place)
        threshold[...] = search(k, 0, -(-(S + 1).bit_length() // bits) * bits)

    @pl.when(step > 0)
    def _():
        sent_all()

    def write(j, carry):
        @pl.when(j <= last_tile(i))
        def _():
            # a free tile's ``ints`` are whatever the slot held: every
            # integer reaches the least
            chosen = (ints[j] >= threshold[...]) \
                & _tile_causal(i, j, block_q, block_k)
            out[j] = jnp.where(chosen, 1, 0).astype(jnp.int8)

        @pl.when((j > last_tile(i)) & (i == 0))
        def _():
            out[j] = jnp.zeros(tile, jnp.int8)

        send(j).start()
        return carry

    jax.lax.fori_loop(0, n_k, write, 0)

    @pl.when(step == steps - 1)
    def _():
        sent_all()


def _select_vmem_bytes(S, block_q, block_k):
    """What `_select_kernel` holds in VMEM: a q tile's whole row of
    integers twice (this tile's and the next one's, landing), its row of
    the mask, the rows' threshold a lane tile wide, and a tile's int32
    temporaries."""
    tile = block_q * _lanes(block_k) * 4
    return (2 * 4 + 1) * S * block_q + block_q * 128 * 4 + 8 * tile


def _select_tiles(S):
    """(q tile, k tile) of the selection's kernel for a sequence of S, or
    None for a shape it cannot tile, which takes `_select_reference` on
    every platform: each tile the largest power-of-two part of
    `_SELECT_TILE`'s that divides the sequence and a multiple of what
    Mosaic tiles the mask by (32 rows, 128 lanes), the whole sequence
    included: the kernel's own copies cut the tiles out of HBM; the rows it
    holds fit `_SELECT_VMEM_MAX`."""
    block_q, block_k = (_tile(S, cap, 1) for cap in _SELECT_TILE)
    if block_q % 32 or block_k % 128 \
            or _select_vmem_bytes(S, block_q, block_k) > _SELECT_VMEM_MAX:
        return None
    return block_q, block_k


@functools.partial(jax.jit, static_argnames=("top_k", "block_q", "block_k",
                                             "bits", "interpret"))
def _pallas_select(scores, *, top_k, block_q, block_k, interpret,
                   bits=_SELECT_BITS):
    """`_select_reference` as one Mosaic kernel a layer: grid (sequences, q
    tiles), in order: a q tile's row lives in VMEM over both searches, the
    next one's lands beside it and its mask's copies run over the next
    tile's.  -> the mask (B, S, S) int8, written where it lies: no
    attention kernel's first result by
    `benchmark/families/lfm2_moe.py:is_attention_kernel`."""
    B, S, _ = scores.shape
    n_k = S // block_k
    return pl.pallas_call(
        functools.partial(_select_kernel, top_k=top_k, block_q=block_q,
                          block_k=block_k, bits=bits),
        grid=(B, S // block_q),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((B, S, S), jnp.int8),
        scratch_shapes=[pltpu.VMEM((2, n_k, block_q, block_k), jnp.int32),
                        pltpu.VMEM((n_k, block_q, block_k), jnp.int8),
                        pltpu.VMEM((block_q, 1), jnp.int32),
                        pltpu.SemaphoreType.DMA((2, n_k)),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_SELECT_VMEM_MAX))(scores)


def select_top_k(scores, top_k, block=512):
    """scores (B, S, S) float32, -inf above the diagonal (`index_scores`)
    -> the mask (B, S, S) int8: 1 where query t attends key s, the
    min(``top_k``, t + 1) keys s <= t of largest score, of equal scores the
    lower s (`jax.lax.top_k`'s set).  A constant: no gradient passes.  The
    first ``top_k`` queries attend every key they see: their rows are the
    causal triangle and search nothing.  One kernel a layer that reads the
    scores once and writes the mask and nothing else (`_pallas_select`),
    or ``block`` queries at a time through HBM by `_select_reference`
    where `_select_tiles` declines the shape."""
    B, S, _ = scores.shape
    block = _block(S, block)
    tiles = _select_tiles(S)
    selected = sum(min(top_k, t + 1) for t in range(S))
    tracing.count("attention.keys_selected", top_k)
    tracing.count("attention.pairs_causal", B * S * (S + 1) // 2)
    tracing.count("attention.pairs_selected", B * selected)
    tracing.count("attention.mask_bytes", B * S * S)
    tracing.count("attention.select_rows_fused", B * S if tiles else 0)
    select = _kernel_or_reference(
        functools.partial(_pallas_select, top_k=top_k),
        functools.partial(_select_reference, top_k=top_k, block=block),
        tiles)
    return select(jax.lax.stop_gradient(scores))


def _target_reference(q, k, lse, mask, start, *, scale):
    """The target of one block of queries in plain XLA, as `_loss_kernel`
    makes it a tile at a time: q (H, S, D) and k (H_kv, S, D) one
    sequence's, head-major; lse (rows, H) and mask (rows, S) the block's,
    whose first query is ``start`` -> (rows, S) float32, the heads'
    probabilities over the selected keys summed: the products in float32,
    the exponent's argument rounded to q's type (as the main attention's
    kernels make them), the exponent and the sum in float32."""
    H, _, D = q.shape
    Hkv, rows = k.shape[0], mask.shape[0]
    q = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=1)
    s = jnp.einsum("ngqd,nsd->ngqs", q.reshape(Hkv, H // Hkv, rows, D), k,
                   preferred_element_type=jnp.float32)
    s = (s * scale - lse.T.reshape(Hkv, H // Hkv, rows, 1)).astype(q.dtype)
    return jnp.sum(jnp.where(mask != 0, jnp.exp(s.astype(jnp.float32)), 0.0),
                   axis=(0, 1))


def _loss_kernel(q_ref, k_ref, lse_ref, mask_ref, scores_ref, kl_ref,
                 grad_ref, z, w, m, l, t_row, i_row, g_row, sem, *, scale,
                 inv_rows, block_q, block_k):
    """One (q tile, k tile) of a sequence's loss, the heads inside, and on a
    q tile's last k tile its rows' loss and gradient.  q_ref (block_q, H D)
    and k_ref (block_k, H_kv D) the heads side by side in the lanes as they
    lie, lse_ref (block_q, H), mask_ref and scores_ref (block_q, block_k);
    kl_ref (block_q, 1); grad_ref the whole
    (B, S, S) float32 where XLA put it, written by this kernel's own
    copies.  The k tiles are the inner grid axis, in order; those above a q
    tile's diagonal are not visited (their k, mask and scores blocks are the
    last visited tile's, not fetched again: `_pallas_loss`' index maps).

    A visited tile: the target t, every head's exponent of its QK' summed
    (the argument rounded to q's type as the main attention's kernels make
    it) and masked once; kept in ``t_row`` beside the selected scores
    (``i_row``, -inf elsewhere): the q tile's WHOLE row, (k tiles, block_q,
    block_k) float32 in VMEM.  Three sums a row run beside it in float32:
    z = sum t, w = sum t (log t - I) (0 where t is: xlogy's rule) and the
    selected scores' maximum and sum of exponents ``m``, ``l``; each a lane
    at a time, (block_q, 128): a tile's lane tiles are added or compared
    elementwise, and the 128 lanes are brought together once a row, on its
    last tile (0.3 ms a layer of the keye cell's less than a reduction
    across the lanes every tile).  With p = t / z and log_q = I - lse(I)
    the row's KL(p || softmax I) is w / z - log z + lse(I).

    The last visited tile: the gradient (exp(log_q) - p) ``inv_rows`` over
    the row held, 0 off the selected pairs, a k tile at a time into
    ``g_row`` and from there to its place in HBM by a copy a tile, which
    the next q tile's products hide: they are waited for before ``g_row``
    is written again, and at the grid's last step.  The tiles above the
    diagonal are zeroed once a sequence: a later q tile's diagonal lies
    further right."""
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    last = ((i + 1) * block_q - 1) // block_k
    heads, n_k = lse_ref.shape[1], t_row.shape[0]
    D = q_ref.shape[1] // heads
    group = heads * D // k_ref.shape[1]
    width = z.shape[1]
    lane_tiles = range(0, block_k, width)

    def by_lane(combine, x):    # (block_q, block_k) -> (block_q, width)
        return functools.reduce(combine, (x[:, c:c + width]
                                          for c in lane_tiles))

    def copy(tile):
        return pltpu.make_async_copy(
            g_row.at[tile],
            grad_ref.at[b, pl.ds(i * block_q, block_q),
                        pl.ds(tile * block_k, block_k)], sem)

    def land():
        for tile in range(n_k):     # same-sized, so any of them counts one
            copy(tile).wait()

    @pl.when(j == 0)
    def _():
        for ref in (z, w, l):
            ref[...] = jnp.zeros_like(ref)
        m[...] = jnp.full_like(m, -jnp.inf)

    @pl.when(j <= last)
    def _():
        total = None
        for h in range(heads):      # unrolled: a head's products pass
            q = q_ref[:, h * D:(h + 1) * D]     # while the last one's
            g = h // group                      # exponents do
            s = jax.lax.dot_general(
                q, k_ref[:, g * D:(g + 1) * D], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = (s * scale - lse_ref[:, h:h + 1]).astype(q.dtype)
            p = jnp.exp(s.astype(jnp.float32))
            total = p if total is None else total + p
        chosen = mask_ref[...] != 0
        scores = scores_ref[...]
        t = jnp.where(chosen, total, 0.0)
        logits = jnp.where(chosen, scores, -jnp.inf)
        t_row[j] = t
        i_row[j] = logits
        z[...] += by_lane(jnp.add, t)
        w[...] += by_lane(jnp.add, jnp.where(
            t == 0, 0.0, t * (jnp.log(t) - scores)))
        top = jnp.maximum(m[...], by_lane(jnp.maximum, logits))
        base = jnp.where(top == -jnp.inf, 0.0, top)     # nothing selected yet
        l[...] = l[...] * jnp.exp(m[...] - base) + sum(
            jnp.exp(logits[:, c:c + width] - base) for c in lane_tiles)
        m[...] = top

    @pl.when(j == last)
    def _():
        across = functools.partial(jnp.sum, axis=1, keepdims=True)
        total = across(z[...])
        top = jnp.max(m[...], axis=1, keepdims=True)    # a row selects a key
        lse = top + jnp.log(across(l[...] * jnp.exp(m[...] - top)))
        kl_ref[...] = across(w[...]) / total - jnp.log(total) + lse
        share = 1.0 / total

        @pl.when((b > 0) | (i > 0))
        def _():
            land()

        def one(tile, carry):
            @pl.when(tile <= last)
            def _():
                logits = i_row[tile]
                g_row[tile] = jnp.where(
                    logits > -jnp.inf,
                    (jnp.exp(logits - lse) - t_row[tile] * share) * inv_rows,
                    0.0)

            @pl.when((tile > last) & (i == 0))
            def _():
                g_row[tile] = jnp.zeros(g_row.shape[1:], jnp.float32)

            copy(tile).start()
            return carry

        jax.lax.fori_loop(0, n_k, one, 0)

        @pl.when((b == pl.num_programs(0) - 1)
                 & (i == pl.num_programs(1) - 1))
        def _():
            land()


def _target_vmem_bytes(q, k, block_q, block_k):
    """What a grid step of `_loss_kernel` holds in VMEM: its blocks twice
    (a width padded to whole lanes), the q tile's whole row three times
    (target, selected scores, gradient) and its sums in float32, and a
    tile's float32 temporaries."""
    S, H, D = q.shape[1:]
    tile = block_q * _lanes(block_k)
    blocks = (block_q * _lanes(H * D) + block_k * _lanes(k.shape[2] * D)) \
        * q.dtype.itemsize \
        + block_q * (_lanes(H) + _lanes(1)) * 4 + tile * (1 + 4)
    scratch = 3 * (S // block_k) * tile * 4 + 4 * block_q * 128 * 4
    return 2 * blocks + scratch + 6 * tile * 4


def _target_tiles(q, k):
    """(q tile, k tile) of the loss's kernel for queries q (B, S, H, D) and
    keys k (B, S, H_kv, D), or None for a shape it cannot tile, which takes
    `_loss_reference` on every platform: each tile the largest power-of-two
    part of `_TARGET_TILE`'s that divides the sequence; a tile that is not
    the whole sequence is a multiple of what Mosaic tiles the mask by (32
    rows, 128 lanes); a head's columns do not straddle a 128-lane tile of
    q's row; a step's blocks and the row it holds fit `_TARGET_VMEM_MAX`."""
    S, D = q.shape[1], q.shape[3]
    tiles = (_tile(S, _TARGET_TILE[0], 32), _tile(S, _TARGET_TILE[1], 128))
    if None in tiles or (128 % D and D % 128) \
            or _target_vmem_bytes(q, k, *tiles) > _TARGET_VMEM_MAX:
        return None
    return tiles


@functools.partial(jax.jit, static_argnames=("scale", "inv_rows", "block_q",
                                             "block_k", "interpret"))
def _pallas_loss(q, k, lse, mask, scores, *, scale, inv_rows, block_q,
                 block_k, interpret):
    """`_loss_reference` as one Mosaic kernel a layer: grid (sequences, q
    tiles, k tiles), all in order: a q tile's row and sums live in VMEM
    over its k tiles, and its gradient's copies over the next q tile's.  q
    (B, S, H, D) and k (B, S, H_kv, D) as they lie, a head's columns
    sliced from the lanes in the kernel: nothing is transposed but the row
    statistics, lse (B, H, S).  -> (each query's KL (B, S), the gradient
    (B, S, S) float32 written where it lies: no attention kernel's first
    result by `benchmark/families/keye_vl.py:is_attention_kernel`)."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    visited = functools.partial(_last_on_diagonal, block_q=block_q,
                                block_k=block_k)
    tile = pl.BlockSpec((None, block_q, block_k),
                        lambda b, i, j: (b, i, visited(i, j)))
    row = pltpu.VMEM((S // block_k, block_q, block_k), jnp.float32)
    # a lane tile wide, or the whole of a k tile that is less
    sums = pltpu.VMEM((block_q, min(block_k, 128)), jnp.float32)
    call = pl.pallas_call(
        functools.partial(_loss_kernel, scale=scale, inv_rows=inv_rows,
                          block_q=block_q, block_k=block_k),
        grid=(B, S // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, H * D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, Hkv * D),
                         lambda b, i, j: (b, visited(i, j), 0)),
            pl.BlockSpec((None, block_q, H), lambda b, i, j: (b, i, 0)),
            tile, tile],
        out_specs=[pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0)),
                   pl.BlockSpec(memory_space=pl.ANY)],
        out_shape=[jax.ShapeDtypeStruct((B, S, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, S, S), jnp.float32)],
        scratch_shapes=[sums, sums, sums, sums, row, row, row,
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_TARGET_VMEM_MAX))
    kl, grad = call(q.reshape(B, S, H * D), k.reshape(B, S, Hkv * D),
                    lse.transpose(0, 2, 1), mask.astype(jnp.int8), scores)
    return kl.reshape(B, S), grad


def _loss_reference(q, k, lse, mask, scores, *, scale, inv_rows, block):
    """The loss's rows and its gradient in plain XLA by blocks of ``block``
    queries, what `_pallas_loss` is held to and what a shape it declines
    runs: q (B, S, H, D), k (B, S, H_kv, D), lse (B, H, S), mask and scores
    (B, S, S) -> (each query's KL(p || softmax of its
    selected scores) (B, S), the gradient to ``scores`` times ``inv_rows``
    (B, S, S) float32).  A block's target and every pass after it go
    through HBM."""
    def one(start, scores, mask, lse, q, k):
        chosen = mask != 0
        p = _target_reference(q, k, lse, mask, start, scale=scale)
        p = p / jnp.sum(p, axis=1, keepdims=True)
        logits = jnp.where(chosen, scores, -jnp.inf)
        log_q = logits - jax.scipy.special.logsumexp(logits, axis=1,
                                                     keepdims=True)
        kl = jnp.sum(jnp.where(chosen, jax.scipy.special.xlogy(p, p)
                               - p * log_q, 0.0), axis=1)
        return kl, jnp.where(chosen, (jnp.exp(log_q) - p) * inv_rows, 0.0)

    # q and k head-major, as the masked flash kernels take them: the main
    # attention's own transposes, made once
    kl, grad = _by_blocks(
        one, block, (scores, mask, lse.transpose(0, 2, 1)),
        (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)))
    return _rows(kl), _rows(grad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _indexer_loss(scores, mask, q, k, lse, scale, block):
    return _indexer_loss_fwd(scores, mask, q, k, lse, scale, block)[0]


def _indexer_loss_fwd(scores, mask, q, k, lse, scale, block):
    """-> (the mean over the queries of KL(p || softmax of the selected
    scores), its gradient to ``scores`` (B, S, S) float32)."""
    rows = scores.shape[0] * scores.shape[1]
    inv_rows = 1.0 / rows
    both = _kernel_or_reference(
        functools.partial(_pallas_loss, scale=scale, inv_rows=inv_rows),
        functools.partial(_loss_reference, scale=scale, inv_rows=inv_rows,
                          block=block), _target_tiles(q, k))
    kl, grad = both(q, k, lse, mask, scores)
    return jnp.sum(kl) / rows, grad


_indexer_loss.defvjp(
    _indexer_loss_fwd,
    lambda scale, block, grad, g: (g * grad, None, None, None, None))


def indexer_loss(scores, mask, q, k, lse, block=512):
    """The indexer's loss: mean over the B x S queries of
    KL(p_t || softmax_{s in S_t} I_{t,s}).  ``scores`` I (B, S, S) float32
    and ``mask`` (B, S, S) as `index_scores` and `select_top_k` give them;
    q (B, S, H, D), k (B, S, H_kv, D) and lse (B, H, S) the main
    attention's queries, keys and row statistics over the SELECTED keys
    (`parallel/attention.py:attention(..., mask=, with_lse=True)`), at
    D^-1/2.  p_t: the heads' probabilities over S_t summed and L1-normalised
    (each head's sum to 1, up to the kernel's rounding).  Differentiable in
    ``scores`` alone: q, k and lse are constants here whatever the caller
    passes, so nothing of this loss reaches the main attention.  The
    gradient, (softmax - p) / (B S) over the selected pairs, is made with
    the loss and held (B, S, S) float32 until the backward pass reads it:
    the heads' probabilities are made once a trace of the forward pass (a
    recomputed layer makes them again in its replay), by one kernel a
    layer that writes the rows' losses and the gradient and nothing else
    (`_pallas_loss`: no head's scores and no row of the target leave
    VMEM), or ``block`` queries at a time through HBM by `_loss_reference`
    where `_target_tiles` declines the shape."""
    B, S = scores.shape[:2]
    block = _block(S, block)
    tiles = _target_tiles(q, k)
    _count_tiles("target", B, S, tiles)
    tracing.count("attention.loss_rows_fused", B * S if tiles else 0)
    q, k, lse = jax.lax.stop_gradient((q, k, lse))
    return _indexer_loss(scores, mask, q, k, lse, q.shape[-1] ** -0.5, block)

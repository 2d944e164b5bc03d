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
attends s.  Everything here goes by blocks of ``block`` query rows
(`sa_config`'s `q_chunk_size`), one sequence's block at a time
(`_by_blocks`): what a step holds of a head's scores is one block's, never
an S x S array a head; what it holds summed over the heads is the (B, S, S)
float32 of I and, under the gradient, of dL_I / dI.  The scores, the
selection and the loss's normalisation, KL and gradient are plain XLA.  The
loss's TARGET, the main attention's probabilities of a block summed over
its heads, is one Mosaic kernel a block (`_pallas_target`): every head's
QK', its exponent and the sum over the heads stay in VMEM, and the key
tiles above the block's diagonal are not visited.  It runs as the flash
kernels do (`ops.by_platform`): compiled where the step is lowered for a
TPU, interpreted elsewhere up to the tests' sizes, and the same
arithmetic in plain XLA (`_target_reference`) beyond them and for a shape
the kernel cannot tile (`_target_tiles`).

The selection is a threshold search and no `jax.lax.top_k`: a top-k gives
the keys' indices, 2,048 a row for 16,384 rows a layer, and a mask of them
is a scatter, which the chip runs serially; and XLA:TPU's top-k at a k of
thousands is a sort of the whole row.  The k-th largest score of a row is
found by its bits, two at a time (16 passes of three compares and three
counts over the block; the float32 scores taken as integers that order as
they do), and the mask is a compare with it; of the keys that TIE with the
k-th the lowest are taken, up to the last that still fits, which the same
search finds over the keys' places (7 passes at 8,192 keys).  Every row
pays both searches whatever its scores are: a step's time does not depend
on how many rows have a tie (one row in a thousand at float32 sums of
bfloat16 products, so two blocks in five, which a branch taken only then
made the step's time wander by).  The result is `jax.lax.top_k`'s set,
exactly.

Counts itself on the job timeline as the step is traced:
`attention.indexer_heads` (`index_scores`), `attention.keys_selected`,
`attention.pairs_causal`, `attention.pairs_selected`, `attention.mask_bytes`
(`select_top_k`), `attention.target_tiles`, `attention.target_tiles_skipped`
(`indexer_loss`; a recomputed layer is traced once).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import by_platform
from ray_tpu.util import tracing

# the target kernel's (q tile, k tile) at most (`tools/chip_kernels.py
# --sweep target-8k` on a v5e, ms for every block of the keye cell's two
# sequences: 128 x 512 6.81, 256 x 256 6.67, 256 x 512 6.63, 256 x 1,024
# 6.76, 512 x 512 6.56, whose unrolled body takes twice as long to compile:
# PERF.md §6, PR 48), and the scoped VMEM it may ask for: a step holds
# every head's q tile and a k tile of every key head
_TARGET_TILE = (256, 512)
_TARGET_VMEM_MAX = 64 << 20


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


def index_scores(q, k, w, block=512):
    """q (B, S, J, D) the indexer's J query heads, k (B, S, D) its one key
    head, w (B, S, J) the heads' weights -> I (B, S, S) float32, -inf
    above the diagonal: sum_j w_j relu(q_j . k), the products in float32
    from q's and k's type, the weighted sum in float32.  Differentiable in
    q, k and w; a block's (J, block, S) products are made again by the
    backward pass and never kept."""
    S, J = q.shape[1:3]
    block = _block(S, block)
    tracing.count("attention.indexer_heads", J)

    @jax.checkpoint
    def scores(start, q, w, k):
        products = jnp.einsum("qjd,sd->jqs", q, k,
                              preferred_element_type=jnp.float32)
        total = jnp.sum(w.T[:, :, None] * jax.nn.relu(products), axis=0)
        return jnp.where(_causal(start, block, S), total, -jnp.inf)

    return _rows(_by_blocks(scores, block, (q, w.astype(jnp.float32)), (k,)))


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


def select_top_k(scores, top_k, block=512):
    """scores (B, S, S) float32, -inf above the diagonal (`index_scores`)
    -> the mask (B, S, S) int8: 1 where query t attends key s, the
    min(``top_k``, t + 1) keys s <= t of largest score, of equal scores the
    lower s (`jax.lax.top_k`'s set).  A constant: no gradient passes.  The
    first ``top_k`` queries attend every key they see: their blocks are the
    causal triangle and search nothing."""
    B, S, _ = scores.shape
    block = _block(S, block)
    free = min(top_k // block * block, S)   # rows whose whole block is free
    selected = sum(min(top_k, t + 1) for t in range(S))
    tracing.count("attention.keys_selected", top_k)
    tracing.count("attention.pairs_causal", B * S * (S + 1) // 2)
    tracing.count("attention.pairs_selected", B * selected)
    tracing.count("attention.mask_bytes", B * S * S)

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
        parts.append(_rows(_by_blocks(
            select, block, (jax.lax.stop_gradient(scores)[:, free:],),
            first=free)))
    return jnp.concatenate(parts, axis=1)


def _target_reference(q, k, lse, mask, start, *, scale):
    """The target of one block of queries in plain XLA, and what
    `_pallas_target` is held to: q (H, S, D) and k (H_kv, S, D) one
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


def _target_kernel(start_ref, q_ref, k_ref, lse_ref, mask_ref, o_ref, *,
                   scale, block_q, block_k):
    """One (q tile, k tile) of a block's target, the heads inside: q_ref
    (H, block_q, D), k_ref (H_kv, block_k, D), lse_ref (block_q, H),
    mask_ref and o_ref (block_q, block_k).  A tile wholly above the
    diagonal is written as zeros and nothing of it is computed (its k and
    mask blocks are the last visited tile's, not fetched again:
    `_pallas_target`'s index maps).  The mask is applied once, to the sum:
    a pair that is not selected is 0 whatever its exponents were."""
    heads = q_ref.shape[0]
    group = heads // k_ref.shape[0]
    i, j = pl.program_id(0), pl.program_id(1)
    last_query = start_ref[0] + (i + 1) * block_q - 1

    @pl.when(j * block_k <= last_query)
    def _():
        total = None
        for h in range(heads):      # unrolled: a head's products pass
            q = q_ref[h]            # while the last one's exponents do
            s = jax.lax.dot_general(
                q, k_ref[h // group], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = (s * scale - lse_ref[:, h:h + 1]).astype(q.dtype)
            p = jnp.exp(s.astype(jnp.float32))
            total = p if total is None else total + p
        o_ref[...] = jnp.where(mask_ref[...] != 0, total, 0.0)

    @pl.when(j * block_k > last_query)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _target_vmem_bytes(q, k, block_q, block_k):
    """What a grid step of `_target_kernel` holds in VMEM: its blocks twice
    (a width padded to whole lanes) and a tile's float32 temporaries."""
    lanes = lambda d: -(-d // 128) * 128
    H, D = q.shape[2:]
    blocks = (H * block_q + k.shape[2] * block_k) * lanes(D) \
        * q.dtype.itemsize \
        + block_q * lanes(H) * 4 + block_q * lanes(block_k) * (1 + 4)
    return 2 * blocks + 6 * block_q * lanes(block_k) * 4


def _target_tiles(q, k, block):
    """(q tile, k tile) of the target kernel for queries q (B, S, H, D) and
    keys k (B, S, H_kv, D) by blocks of ``block`` rows, or None for a shape
    it cannot tile, which takes `_target_reference` on every platform: a q
    tile divides the block and a k tile the sequence, each the largest
    power-of-two part of `_TARGET_TILE`'s that does; a tile that is not the
    whole extent is a multiple of what Mosaic tiles the mask by (32 rows,
    128 lanes); a step's blocks fit `_TARGET_VMEM_MAX`."""
    def tile(extent, cap, unit):
        t = min(cap, extent)
        while extent % t:
            t //= 2
        return t if t == extent or t % unit == 0 else None

    tiles = tile(block, _TARGET_TILE[0], 32), tile(q.shape[1],
                                                   _TARGET_TILE[1], 128)
    if None in tiles or _target_vmem_bytes(q, k, *tiles) > _TARGET_VMEM_MAX:
        return None
    return tiles


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "block_k",
                                             "interpret"))
def _pallas_target(q, k, lse, mask, start, *, scale, block_q, block_k,
                   interpret):
    """`_target_reference` as one Mosaic kernel: grid (the block's q tiles,
    the sequence's k tiles), ``start`` a scalar the index maps read: a q
    tile's rows of the sequence's q, and for a k tile above its diagonal
    the blocks of the last tile on it, so that a skipped step fetches
    nothing.  The first result is (rows, S) float32: no attention kernel's
    by `benchmark/families/keye_vl.py:is_attention_kernel`."""
    H, S, D = q.shape
    Hkv, rows = k.shape[0], mask.shape[0]

    def visited(i, j, start):
        return jnp.minimum(j, (start[0] + (i + 1) * block_q - 1) // block_k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(rows // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec((H, block_q, D),
                         lambda i, j, start: (0, start[0] // block_q + i, 0)),
            pl.BlockSpec((Hkv, block_k, D),
                         lambda i, j, start: (0, visited(i, j, start), 0)),
            pl.BlockSpec((block_q, H), lambda i, j, start: (i, 0)),
            pl.BlockSpec((block_q, block_k),
                         lambda i, j, start: (i, visited(i, j, start)))],
        out_specs=pl.BlockSpec((block_q, block_k),
                               lambda i, j, start: (i, j)))
    call = pl.pallas_call(
        functools.partial(_target_kernel, scale=scale, block_q=block_q,
                          block_k=block_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, S), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_TARGET_VMEM_MAX))
    start, mask = start.reshape(1).astype(jnp.int32), mask.astype(jnp.int8)
    with jax.named_scope("target"):
        return call(start, q, k, lse, mask)


def _count_target_tiles(B, S, tiles):
    """Add a layer's target tiles to the job timeline, as the step is
    traced: `attention.target_tiles` the (q tile, k tile) grid steps of the
    S x S square that `_target_kernel` computes, over the B sequences, and
    `attention.target_tiles_skipped` those wholly above the diagonal, which
    it writes as zeros.  Both 0 without ``tiles``: the shape took
    `_target_reference`.  Called by `indexer_loss` itself, once a traced
    layer: its custom rule's two functions are both traced under a
    gradient."""
    on = above = 0
    if tiles:
        block_q, block_k = tiles
        on = sum(min(S // block_k, ((i + 1) * block_q - 1) // block_k + 1)
                 for i in range(S // block_q))
        above = (S // block_q) * (S // block_k) - on
    tracing.count("attention.target_tiles", B * on)
    tracing.count("attention.target_tiles_skipped", B * above)


def _loss_blocks(scores, mask, q, k, lse, scale, block):
    """-> (sum over the queries of KL(p || softmax of the selected scores),
    its gradient to ``scores`` (B, S, S) float32) by blocks of queries."""
    tiles = _target_tiles(q, k, block)
    reference = functools.partial(_target_reference, scale=scale)
    if tiles is None:
        target = reference
    else:
        target = functools.partial(by_platform, functools.partial(
            _pallas_target, scale=scale, block_q=tiles[0], block_k=tiles[1]),
            reference)

    def one(start, scores, mask, lse, q, k):
        chosen = mask != 0
        p = target(q, k, lse, mask, start)
        p = p / jnp.sum(p, axis=1, keepdims=True)
        logits = jnp.where(chosen, scores, -jnp.inf)
        log_q = logits - jax.scipy.special.logsumexp(logits, axis=1,
                                                     keepdims=True)
        kl = jnp.sum(jnp.where(chosen, jax.scipy.special.xlogy(p, p)
                               - p * log_q, 0.0))
        return kl, jnp.where(chosen, jnp.exp(log_q) - p, 0.0)

    # q and k head-major, as the masked flash kernels take them: the main
    # attention's own transposes, made once
    kl, grad = _by_blocks(
        one, block, (scores, mask, lse.transpose(0, 2, 1)),
        (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)))
    return jnp.sum(kl), _rows(grad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _indexer_loss(scores, mask, q, k, lse, scale, block):
    return _indexer_loss_fwd(scores, mask, q, k, lse, scale, block)[0]


def _indexer_loss_fwd(scores, mask, q, k, lse, scale, block):
    total, grad = _loss_blocks(scores, mask, q, k, lse, scale, block)
    rows = scores.shape[0] * scores.shape[1]
    return total / rows, grad / rows


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
    recomputed layer makes them again in its replay), a block of
    ``block`` queries at a time by the target kernel (`_pallas_target`:
    no head's scores of a block leave VMEM), or by `_target_reference`
    where `_target_tiles` declines the shape."""
    B, S = scores.shape[:2]
    block = _block(S, block)
    _count_target_tiles(B, S, _target_tiles(q, k, block))
    q, k, lse = jax.lax.stop_gradient((q, k, lse))
    return _indexer_loss(scores, mask, q, k, lse, q.shape[-1] ** -0.5, block)

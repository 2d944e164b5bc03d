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
attends s.  Everything here is plain XLA by blocks of ``block`` query rows
(`sa_config`'s `q_chunk_size`), one sequence's block at a time
(`_by_blocks`): what a step holds of a head's scores is one block's, never
an S x S array a head; what it holds summed over the heads is the (B, S, S)
float32 of I and, under the gradient, of dL_I / dI.

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
(`select_top_k`; a recomputed layer is traced once).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.util import tracing


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


def _loss_blocks(scores, mask, q, k, lse, scale, block):
    """-> (sum over the queries of KL(p || softmax of the selected scores),
    its gradient to ``scores`` (B, S, S) float32) by blocks of queries."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]

    def one(start, scores, mask, q, lse, k):
        chosen = mask != 0
        # the main attention's probabilities, as its kernels make them: the
        # products in float32, the exponent's argument in q's type
        s = jnp.einsum("qngd,snd->ngqs", q.reshape(block, Hkv, H // Hkv, D),
                       k, preferred_element_type=jnp.float32)
        s = (s * scale - lse.T.reshape(Hkv, H // Hkv, block, 1)).astype(
            q.dtype)
        p = jnp.sum(jnp.where(chosen, jnp.exp(s.astype(jnp.float32)), 0.0),
                    axis=(0, 1))
        p = p / jnp.sum(p, axis=1, keepdims=True)
        logits = jnp.where(chosen, scores, -jnp.inf)
        log_q = logits - jax.scipy.special.logsumexp(logits, axis=1,
                                                     keepdims=True)
        kl = jnp.sum(jnp.where(chosen, jax.scipy.special.xlogy(p, p)
                               - p * log_q, 0.0))
        return kl, jnp.where(chosen, jnp.exp(log_q) - p, 0.0)

    kl, grad = _by_blocks(one, block, (scores, mask, q,
                                       lse.transpose(0, 2, 1)), (k,))
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
    the heads' probabilities are made once."""
    block = _block(scores.shape[1], block)
    q, k, lse = jax.lax.stop_gradient((q, k, lse))
    return _indexer_loss(scores, mask, q, k, lse, q.shape[-1] ** -0.5, block)

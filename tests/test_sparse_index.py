"""`ray_tpu/ops/sparse_index.py` at small sizes on the CPU: the index scores
against a dense einsum, their two kernels interpreted against the blocked
reference and the dense form, the threshold search against a stable sort and
`jax.lax.top_k` (ties, zeros of either sign, every k), the selection's
kernel interpreted against that search in plain XLA, the indexer's loss and
its gradient against the same written densely, the loss's kernel (the target, the rows' losses and the gradient in one)
interpreted against its plain reference, what it counts on the job
timeline, and the loss at the cell's shape exported for a TPU."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import sparse_index as si
from ray_tpu.parallel.attention import attention

B, S, J, DI = 2, 64, 4, 8
H, HKV, D = 8, 1, 16          # a group of 8 query heads a key/value head
TRI = np.tril(np.ones((S, S), bool))


pytestmark = pytest.mark.usefixtures("highest_precision")


def indexer_inputs(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, S, J, DI)),
            jax.random.normal(ks[1], (B, S, DI)),
            jax.random.normal(ks[2], (B, S, J)))


def dense_scores(q, k, w):
    return jnp.einsum("bqj,bjqs->bqs", w, jax.nn.relu(
        jnp.einsum("bqjd,bsd->bjqs", q, k)))


def sorted_selection(scores, top_k):
    """The first min(top_k, t + 1) entries of a stable descending sort."""
    scores = jnp.where(TRI, scores, -jnp.inf)
    rank = jnp.argsort(jnp.argsort(-scores, axis=-1, stable=True), axis=-1)
    return np.asarray((rank < jnp.minimum(top_k, jnp.arange(S) + 1)[:, None])
                      & TRI)


def top_ks_set(scores, k):
    """`jax.lax.top_k`'s k keys of every row as a mask (B, S, S) of bools."""
    _, chosen = jax.lax.top_k(scores, k)
    want = np.zeros(scores.shape, bool)
    np.put_along_axis(want, np.asarray(chosen), True, axis=-1)
    return want


@pytest.mark.parametrize("block", [16, 64, 512])
def test_index_scores_match_a_dense_einsum(block):
    q, k, w = indexer_inputs()
    got = si.index_scores(q, k, w, block=block)
    assert got.dtype == jnp.float32 and got.shape == (B, S, S)
    assert np.array_equal(np.isneginf(got), np.broadcast_to(~TRI, got.shape))
    want = dense_scores(q, k, w)
    assert float(jnp.max(jnp.abs(jnp.where(TRI, got - want, 0)))) < 1e-5


def test_index_scores_gradients_match_autodiff_of_the_dense_form():
    q, k, w = indexer_inputs(1)
    weight = jax.random.normal(jax.random.PRNGKey(5), (B, S, S))

    def loss(scores):
        return jnp.sum(jnp.where(TRI, scores * weight, 0.0))

    got = jax.grad(lambda *a: loss(si.index_scores(*a, block=16)),
                   (0, 1, 2))(q, k, w)
    want = jax.grad(lambda *a: loss(dense_scores(*a)), (0, 1, 2))(q, k, w)
    for g, v in zip(got, want):
        assert float(jnp.max(jnp.abs(g - v))) < 1e-4


def scores_inputs(B, S, J, D, dtype, seed=10):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    tri = jnp.tril(jnp.ones((S, S), bool))
    return (jax.random.normal(ks[0], (B, S, J, D)).astype(dtype),
            jax.random.normal(ks[1], (B, S, D)).astype(dtype),
            jax.random.normal(ks[2], (B, S, J)),
            # a cotangent as the loss's: nothing above the diagonal
            jnp.where(tri, jax.random.normal(ks[3], (B, S, S)), 0.0), tri)


@pytest.mark.parametrize("B,S,J,D,dtype,block_q,block_k", [
    (1, 512, 4, 16, jnp.float32, 128, 128),     # 4 x 4 tiles: 4 diagonal,
    (1, 512, 4, 16, jnp.bfloat16, 128, 128),    # 6 full, 6 skipped
    (2, 256, 8, 16, jnp.float32, 64, 128),      # two q tiles a k tile
    (2, 256, 8, 16, jnp.bfloat16, 64, 128),
    (1, 512, 2, 64, jnp.bfloat16, 128, 256),    # the cell's depth
    (1, 256, 16, 8, jnp.float32, 128, 64),      # two k tiles a q tile
])
def test_the_scores_kernels_are_their_reference_and_the_dense_form(
        B, S, J, D, dtype, block_q, block_k):
    """`_pallas_scores` and `_pallas_scores_bwd`, interpreted, at tiles
    that leave several each way, against `_scores_reference` and its
    backward (what `index_scores` ran before the kernels) and against the
    dense `einsum` form under autodiff: -inf exactly above the diagonal,
    the scores to 2e-6 of the largest, the gradients to float32's rounding, and in
    bfloat16 to its rounding of the backward's operand."""
    q, k, w, g, tri = scores_inputs(B, S, J, D, dtype)
    tiles = dict(block_q=block_q, block_k=block_k, interpret=True)
    got = si._pallas_scores(q, k, w, **tiles)
    want = si._scores_reference(q, k, w, block=block_q)
    assert got.dtype == jnp.float32 and got.shape == (B, S, S)
    assert np.array_equal(np.isneginf(got), np.broadcast_to(~tri, got.shape))
    f32 = q.astype(jnp.float32), k.astype(jnp.float32), w
    dense, back = jax.vjp(dense_scores, *f32)
    largest = float(jnp.max(jnp.abs(jnp.where(tri, dense, 0))))
    for other in (want, dense):
        assert float(jnp.max(jnp.abs(jnp.where(tri, got - other, 0)))) \
            <= 2e-6 * largest
    grads = si._pallas_scores_bwd(q, k, w, g, **tiles)
    blocked = si._scores_reference_bwd(q, k, w, g, block=block_q)
    close = 1e-4 if dtype == jnp.float32 else 2e-2
    for name, a, b, c in zip("qkw", grads, blocked, back(g)):
        assert a.dtype == b.dtype == (jnp.float32 if name == "w" else dtype)
        assert a.shape == b.shape == c.shape
        scale = float(jnp.max(jnp.abs(c)))
        for other in (b, c):
            assert float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - other))) <= close * scale, name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_product_that_is_exactly_zero_takes_no_gradient(dtype):
    """relu's gradient at 0 is 0, as `jax.nn.relu`'s: a query of zeros
    (every product of its row 0.0) and a key of zeros (of its column)
    get nothing and give nothing, in the kernels and in the reference."""
    B, S, J, D = 1, 256, 4, 16
    q, k, w, g, _ = scores_inputs(B, S, J, D, dtype, seed=11)
    q, k = q.at[:, 100].set(0), k.at[:, 37].set(0)
    for dq, dk, dw in (
            si._pallas_scores_bwd(q, k, w, g, block_q=128, block_k=128,
                                  interpret=True),
            si._scores_reference_bwd(q, k, w, g, block=128),
            jax.grad(lambda *a: jnp.sum(jnp.where(
                g != 0, si.index_scores(*a, block=128) * g, 0.0)),
                (0, 1, 2))(q, k, w)):
        assert not np.asarray(dq[:, 100], np.float32).any()
        assert not np.asarray(dw[:, 100]).any()
        assert not np.asarray(dk[:, 37], np.float32).any()
        assert np.asarray(dq[:, 101], np.float32).any()
        assert np.asarray(dk[:, 38], np.float32).any()


def test_the_scores_backward_masks_a_cotangent_above_the_diagonal():
    """-inf above the diagonal is a constant: whatever cotangent arrives
    there reaches nothing."""
    q, k, w, g, tri = scores_inputs(1, 256, 4, 16, jnp.float32, seed=12)
    loud = jnp.where(tri, g, 7.0)
    for backward in (
            functools.partial(si._pallas_scores_bwd, block_q=128,
                              block_k=128, interpret=True),
            functools.partial(si._scores_reference_bwd, block=64)):
        for a, b in zip(backward(q, k, w, loud), backward(q, k, w, g)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_a_sequence_that_is_no_whole_number_of_blocks_is_refused():
    q, k, w = indexer_inputs()
    with pytest.raises(ValueError, match="whole number of blocks"):
        si.index_scores(q, k, w, block=48)


@pytest.mark.parametrize("top_k", [1, 7, 16, 20, 33, 63, 64, 100])
@pytest.mark.parametrize("ties", [False, True])
def test_selection_is_the_stable_sorts_and_top_ks_set(top_k, ties):
    """Every k; with scores rounded to halves, so that many tie at the
    threshold: of equal scores the lower keys are taken."""
    scores = si.index_scores(*indexer_inputs(2), block=16)
    if ties:
        scores = jnp.where(TRI, jnp.round(scores * 2) / 2 + 0.0, -jnp.inf)
    mask = si.select_top_k(scores, top_k, block=16)
    assert mask.dtype == jnp.int8 and mask.shape == (B, S, S)
    assert np.array_equal(np.asarray(mask) != 0,
                          sorted_selection(scores, top_k))
    # `jax.lax.top_k` on the rows that have top_k keys to choose from
    k = min(top_k, S)
    rows = np.arange(S) + 1 >= k
    assert np.array_equal((np.asarray(mask) != 0)[:, rows],
                          top_ks_set(scores, k)[:, rows])
    assert int(mask.sum()) == B * sum(min(top_k, t + 1) for t in range(S))


def test_equal_scores_take_the_lower_key():
    scores = jnp.where(TRI, jnp.zeros((1, S, S)), -jnp.inf)
    mask = si.select_top_k(scores, 5, block=16)
    want = TRI & (np.arange(S)[None] < 5)
    assert np.array_equal(np.asarray(mask[0]) != 0, want)


def test_zeros_of_either_sign_tie():
    """-0.0 and 0.0 are one score: the lower key wins, whatever its sign
    (a sum of w * relu products is -0.0 where every w is negative)."""
    row = jnp.array([0.0, -0.0, 1.0, -0.0, 0.0, -1.0, 0.0, -0.0])
    scores = jnp.where(np.tril(np.ones((8, 8), bool)),
                       jnp.broadcast_to(row, (1, 8, 8)), -jnp.inf)
    mask = si.select_top_k(scores, 3, block=8)
    assert np.asarray(mask[0, 7]).tolist() == [1, 1, 1, 0, 0, 0, 0, 0]


def test_the_kth_largest_of_any_bits():
    rng = np.random.default_rng(0)
    u = rng.integers(0, 2 ** 32, (16, 50), dtype=np.uint32)
    u[0, :5] = 0xFFFFFFFF
    u[1, :7] = 0
    k = rng.integers(1, 51, 16).astype(np.int32)
    got = si._kth_largest(jnp.asarray(u), jnp.asarray(k))
    want = np.sort(u, axis=1)[np.arange(16), 50 - k]
    assert np.array_equal(np.asarray(got), want)


def test_the_order_of_the_bits_is_the_order_of_the_floats():
    x = jnp.array([-jnp.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, jnp.inf])
    u = np.asarray(si._ordered(x)).astype(np.int64)
    assert (np.diff(u) >= 0).all() and u[3] == u[4]
    assert (np.diff(u)[[0, 1, 2, 4, 5, 6]] > 0).all()


def test_top_k_past_the_sequence_is_the_causal_triangle():
    scores = si.index_scores(*indexer_inputs(3), block=16)
    for top_k in (S, 4 * S):
        mask = si.select_top_k(scores, top_k, block=16)
        assert np.array_equal(np.asarray(mask) != 0,
                              np.broadcast_to(TRI, (B, S, S)))


def test_scores_that_fall_with_distance_select_a_window():
    """Scores that fall with the key's distance: each query takes its own
    16 nearest keys, a band under the diagonal (a window rule, as the same
    operand would hold it)."""
    near = -jnp.abs(jnp.arange(S)[:, None] - jnp.arange(S)[None]) * 1.0
    scores = jnp.where(TRI, jnp.broadcast_to(near, (1, S, S)), -jnp.inf)
    mask = si.select_top_k(scores, 16, block=16)
    distance = np.arange(S)[:, None] - np.arange(S)[None]
    assert np.array_equal(np.asarray(mask[0]) != 0,
                          (distance >= 0) & (distance < 16))


def selection_scores(B, S, kind, seed=20):
    """Scores (B, S, S) of ``kind``, -inf above the diagonal: random;
    rounded to halves (many tie at the threshold); one value in every row;
    zeros of either sign beside a few other values; and -inf under the
    diagonal too, on a third of the pairs (rows with fewer finite scores
    than top_k: the -inf keys tie, and the lower win)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    scores = jax.random.normal(ks[0], (B, S, S))
    if kind == "ties":
        scores = jnp.round(scores * 2) / 2 + 0.0
    if kind == "one_value":
        scores = jnp.broadcast_to(
            jax.random.normal(ks[1], (B, S, 1)), (B, S, S))
    if kind == "signed_zeros":
        sign = jnp.where(jax.random.bernoulli(ks[1], 0.5, (B, S, S)),
                         0.0, -0.0)
        scores = jnp.where(jnp.abs(scores) < 1.2, sign, jnp.round(scores))
    if kind == "neg_inf_below":
        scores = jnp.where(jax.random.bernoulli(ks[1], 0.33, (B, S, S)),
                           -jnp.inf, scores)
    return jnp.where(jnp.tril(jnp.ones((S, S), bool)), scores, -jnp.inf)


@pytest.mark.parametrize("kind", ["random", "ties", "one_value",
                                  "signed_zeros", "neg_inf_below"])
@pytest.mark.parametrize("B,S,block,block_q,block_k,top_k", [
    (1, 256, 64, 64, 128, 32),      # top_k below a tile, two q tiles a k tile
    (2, 256, 64, 64, 128, 64),      # a tile: the first is free
    (1, 256, 128, 32, 256, 100),    # above one: a tile half free, searched
    (2, 512, 128, 128, 128, 200),   # four tiles each way
    (1, 512, 64, 256, 128, 256),    # two k tiles a q tile, one free
    (1, 256, 64, 128, 128, 700),    # past the sequence: nothing searched
])
def test_the_selection_kernel_is_its_reference_and_top_ks_set(
        B, S, block, block_q, block_k, top_k, kind):
    """`_pallas_select`, interpreted, at tiles that leave several each way
    and at either number of bits a pass, against `_select_reference` (the
    plain XLA form by blocks, what `select_top_k` ran before the kernel
    and runs for a shape it declines): the same bytes; and against
    `jax.lax.top_k`'s set on the rows that have top_k keys to choose
    from (of the scores + 0.0: XLA's sort on this host puts -0.0 under
    0.0, the selection holds them one score)."""
    scores = selection_scores(B, S, kind)
    want = si._select_reference(scores, top_k=top_k, block=block)
    for bits in (1, 2):
        got = si._pallas_select(scores, top_k=top_k, block_q=block_q,
                                block_k=block_k, bits=bits, interpret=True)
        assert got.dtype == jnp.int8 and got.shape == (B, S, S)
        assert np.array_equal(np.asarray(got), np.asarray(want)), bits
    k = min(top_k, S)
    rows = np.arange(S) + 1 >= k
    assert np.array_equal((np.asarray(got) != 0)[:, rows],
                          top_ks_set(scores + 0.0, k)[:, rows])
    assert int(jnp.sum(got, dtype=jnp.int32)) \
        == B * sum(min(top_k, t + 1) for t in range(S))


def attention_inputs(seed=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, S, H, D)),
            jax.random.normal(ks[1], (B, S, HKV, D)),
            jax.random.normal(ks[2], (B, S, HKV, D)))


def dense_loss(scores, mask, q, k):
    """KL(mean over heads of the attention's probabilities over the
    selected keys || softmax of the selected scores), mean over queries."""
    chosen = mask != 0
    B, S, H, D = q.shape
    q, k = q.astype(jnp.float32), k.astype(jnp.float32)
    s = jnp.einsum("bqhd,bshd->bhqs", q,
                   jnp.repeat(k, H // k.shape[2], axis=2)) * D ** -0.5
    p = jnp.mean(jax.nn.softmax(jnp.where(chosen[:, None], s, -jnp.inf), -1),
                 axis=1)
    log_q = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), -1)
    return jnp.sum(jnp.where(chosen, jax.scipy.special.xlogy(p, p)
                             - p * log_q, 0.0)) / (B * S)


@pytest.mark.parametrize("top_k", [16, 64])
def test_the_indexers_loss_and_its_gradient_match_the_dense_form(top_k):
    scores = si.index_scores(*indexer_inputs(5), block=16)
    mask = si.select_top_k(scores, top_k, block=16)
    q, k, v = attention_inputs()
    _, lse = attention(q, k, v, mask=mask, with_lse=True)
    got, grad = jax.value_and_grad(
        lambda sc: si.indexer_loss(sc, mask, q, k, lse, block=16))(scores)
    want, want_grad = jax.value_and_grad(
        lambda sc: dense_loss(sc, mask, q, k))(scores)
    assert float(got) > 0.01
    assert float(jnp.abs(got - want)) < 2e-5 * float(want)
    assert float(jnp.max(jnp.abs(grad - want_grad))) < 1e-7
    # nothing outside the selected pairs
    assert not np.asarray(grad)[np.asarray(mask) == 0].any()


def test_the_loss_is_zero_where_the_indexer_is_the_attention():
    """Index scores that ARE the (one head's) attention scores: the two
    distributions over the selected keys are one."""
    q, k, v = (x[:, :, :1] for x in attention_inputs(6))
    scores = jnp.where(TRI, jnp.einsum("bqhd,bshd->bqs", q, k) * D ** -0.5,
                       -jnp.inf)
    mask = si.select_top_k(scores, 16, block=16)
    _, lse = attention(q, k, v, mask=mask, with_lse=True)
    assert abs(float(si.indexer_loss(scores, mask, q, k, lse, block=16))) \
        < 1e-5


def test_nothing_of_the_loss_reaches_the_main_attention():
    scores = si.index_scores(*indexer_inputs(7), block=16)
    mask = si.select_top_k(scores, 16, block=16)
    q, k, v = attention_inputs()
    _, lse = attention(q, k, v, mask=mask, with_lse=True)
    grads = jax.grad(
        lambda q, k, lse: si.indexer_loss(scores, mask, q, k, lse, block=16),
        (0, 1, 2))(q, k, lse)
    for g in grads:
        assert not np.asarray(g).any()


def loss_inputs(B, S, H, Hkv, D, dtype, kind, block, seed=8):
    """(scores, mask, q, k, lse) of a main attention under a selection of
    ``kind``: `select_top_k`'s of random scores at a top_k below, at and
    above a block, of one key, of every key (the rows before the top_k-th
    have fewer), of scores rounded to halves (many tie at the threshold);
    a causal tile with nothing selected; and every key selected beside one
    whose products are so large that every other selected pair's target
    is exactly 0."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(key, (B, S, heads, D)).astype(dtype)
               for key, heads in zip(ks, (H, Hkv, Hkv)))
    tri = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(tri, jax.random.normal(ks[3], (B, S, S)), -jnp.inf)
    if kind == "ties":
        scores = jnp.where(tri, jnp.round(scores * 2) / 2 + 0.0, -jnp.inf)
    if kind == "zero_target":
        # every query is the one direction key 5 lies far along
        u = jax.random.normal(ks[4], (D,)).astype(dtype)
        q = q * 0.1 + u
        k = k.at[:, 5].set(40.0 * u)
    if kind == "empty_tile":
        # the later half of the queries attend none of the first half of
        # the keys: a causal tile with nothing selected
        late = jnp.arange(S) >= S // 2
        mask = jnp.broadcast_to(
            tri & ~(late[:, None] & ~late[None]), (B, S, S)).astype(jnp.int8)
    else:
        top_k = {"top_k": S // 4, "single_key": 1, "triangle": 4 * S,
                 "zero_target": 4 * S, "ties": S // 4,
                 "below_a_block": block // 2, "a_block": block,
                 "above_a_block": block + block // 2}[kind]
        mask = si.select_top_k(scores, top_k, block=block)
    _, lse = attention(q, k, v, mask=mask, with_lse=True)
    return scores, mask, q, k, lse


def operands(scores, mask, q, k, lse):
    """`_pallas_loss`' and `_loss_reference`'s, in their order."""
    return q, k, lse, mask, scores


@pytest.mark.parametrize("heads,D,dtype,S,block,kind", [
    ((8, 2), 64, jnp.float32, 128, 128, "top_k"),       # one q tile
    ((8, 2), 128, jnp.bfloat16, 256, 128, "top_k"),     # two
    ((4, 4), 64, jnp.bfloat16, 256, 256, "top_k"),      # a block of both
    ((4, 4), 128, jnp.float32, 512, 256, "top_k"),      # four
    ((8, 2), 64, jnp.bfloat16, 512, 128, "top_k"),
    ((8, 2), 128, jnp.float32, 256, 128, "empty_tile"),
    ((4, 4), 64, jnp.bfloat16, 256, 128, "empty_tile"),
    ((8, 2), 64, jnp.float32, 256, 128, "single_key"),
    ((4, 4), 128, jnp.bfloat16, 256, 256, "single_key"),
    ((8, 2), 64, jnp.float32, 256, 128, "triangle"),
    ((4, 4), 128, jnp.bfloat16, 256, 128, "triangle"),
    ((8, 2), 64, jnp.float32, 256, 128, "below_a_block"),
    ((8, 2), 64, jnp.bfloat16, 256, 128, "a_block"),
    ((4, 4), 64, jnp.float32, 512, 128, "above_a_block"),
    ((8, 2), 64, jnp.float32, 256, 128, "ties"),
    ((4, 4), 64, jnp.bfloat16, 256, 128, "ties"),
    ((8, 2), 64, jnp.float32, 256, 128, "zero_target"),
])
def test_the_loss_kernel_is_its_reference_and_the_dense_form(
        heads, D, dtype, S, block, kind):
    """`_pallas_loss`, interpreted, in tiles of 128 x 128 (a row of
    several k tiles held, the tiles above a q tile's diagonal not visited)
    against `_loss_reference`, the plain XLA form by blocks (what
    `indexer_loss` ran before the kernel, and runs for a shape it
    declines): each row's loss and the gradient, to float32's rounding of
    their sums, in bfloat16 to 1e-5 a head of the target; nothing outside
    the mask.  And `indexer_loss` through it against the dense float32
    form."""
    (H, Hkv), B = heads, 1
    inputs = loss_inputs(B, S, H, Hkv, D, dtype, kind, block)
    scores, mask, q, k, lse = inputs
    sizes = dict(scale=D ** -0.5, inv_rows=1.0 / (B * S))
    kl, grad = si._pallas_loss(*operands(*inputs), **sizes, block_q=128,
                               block_k=128, interpret=True)
    want_kl, want_grad = si._loss_reference(*operands(*inputs), **sizes,
                                            block=block)
    assert kl.dtype == grad.dtype == jnp.float32
    assert kl.shape == (B, S) and grad.shape == (B, S, S)
    assert not np.asarray(grad)[np.asarray(mask) == 0].any()
    if kind == "zero_target":
        # xlogy(0, 0) = 0: a selected pair whose target underflowed
        target = si._target_reference(
            q[0].transpose(1, 0, 2), k[0].transpose(1, 0, 2), lse[0].T,
            mask[0], 0, scale=D ** -0.5)
        assert np.asarray((target == 0) & (mask[0] != 0)).sum() > S
    assert np.isfinite(np.asarray(kl)).all()
    tolerance = 1e-6 if dtype == jnp.float32 else 1e-5
    assert float(jnp.max(jnp.abs(kl - want_kl))) <= 4 * tolerance * H
    assert float(jnp.max(jnp.abs(grad - want_grad))) <= tolerance * H / S
    # the loss, the kernel at `_target_tiles`' own tiles where the
    # interpreter takes the size, against the dense form
    loss, grad = jax.value_and_grad(
        lambda sc: si.indexer_loss(sc, mask, q, k, lse, block=block))(scores)
    dense, dense_grad = jax.value_and_grad(
        lambda sc: dense_loss(sc, mask, q, k))(scores)
    close = 2e-5 if dtype == jnp.float32 else 2e-2
    assert float(jnp.abs(loss - dense)) <= close * max(float(dense), 1e-3)
    assert float(jnp.max(jnp.abs(grad - dense_grad))) <= close / S
    assert not np.asarray(grad)[np.asarray(mask) == 0].any()


@pytest.mark.parametrize("block_q,block_k", [(64, 128), (128, 256),
                                             (256, 128)])
def test_the_loss_kernel_at_tiles_that_are_not_square(block_q, block_k):
    """Two q tiles a k tile, two k tiles a q tile, and a q tile of two
    diagonals: the same rows' losses and gradient whatever the tiles."""
    inputs = loss_inputs(2, 256, 4, 2, 64, jnp.float32, "top_k", 128)
    sizes = dict(scale=64 ** -0.5, inv_rows=1.0 / 512)
    kl, grad = si._pallas_loss(*operands(*inputs), **sizes,
                               block_q=block_q, block_k=block_k,
                               interpret=True)
    want_kl, want_grad = si._loss_reference(*operands(*inputs), **sizes,
                                            block=128)
    assert float(jnp.max(jnp.abs(kl - want_kl))) <= 2e-5
    assert float(jnp.max(jnp.abs(grad - want_grad))) <= 1e-8


def test_a_recomputed_layers_two_walks_make_one_loss():
    """Under `jax.checkpoint` the loss is made by the first walk and the
    gradient by the replay: the square of the loss shows the replay's (its
    gradient is twice the replayed loss times the loss's own), which is
    the first walk's and the dense form's."""
    scores, mask, q, k, lse = loss_inputs(2, 64, H, HKV, D, jnp.float32,
                                          "top_k", 64)
    loss = lambda sc: si.indexer_loss(sc, mask, q, k, lse, block=64)
    first = loss(scores)
    squared, grad = jax.value_and_grad(jax.checkpoint(
        lambda sc: loss(sc) ** 2))(scores)
    dense, dense_grad = jax.value_and_grad(
        lambda sc: dense_loss(sc, mask, q, k))(scores)
    assert float(squared) == float(first ** 2)
    assert float(jnp.abs(first - dense)) < 2e-5 * float(dense)
    assert float(jnp.max(jnp.abs(grad - 2 * first * dense_grad))) \
        < 1e-7 * float(first)
    assert not np.asarray(grad)[np.asarray(mask) == 0].any()


def test_what_it_counts_as_the_step_is_traced(monkeypatch):
    counted = {}
    monkeypatch.setattr(
        si.tracing, "count",
        lambda name, n=1: counted.__setitem__(name, counted.get(name, 0) + n))
    scores = jax.eval_shape(
        lambda *a: si.index_scores(*a, block=16), *indexer_inputs())
    jax.eval_shape(lambda s: si.select_top_k(s, 16, block=16), scores)
    selected = sum(min(16, t + 1) for t in range(S))
    assert counted == {
        "attention.indexer_heads": J, "attention.keys_selected": 16,
        # q tiles of 16 rows, the whole sequence a k tile
        "attention.score_tiles": B * S // 16,
        "attention.score_tiles_skipped": 0,
        "attention.pairs_causal": B * S * (S + 1) // 2,
        "attention.pairs_selected": B * selected,
        "attention.mask_bytes": B * S * S,
        # 64 keys are no whole 128-lane tile: the plain form's, all of them
        "attention.select_rows_fused": 0}
    # the cell's sizes: 43.75 % of the causal pairs
    full = sum(min(2048, t + 1) for t in range(8192))
    assert full == 14_681_088
    assert round(100 * full / (8192 * 8193 // 2), 2) == 43.75

    def rows_selected_fused(B, S, block):
        counted.clear()
        jax.eval_shape(lambda s: si.select_top_k(s, 2048, block=block),
                       jax.ShapeDtypeStruct((B, S, S), jnp.float32))
        return counted["attention.select_rows_fused"]

    # the cell's: `_SELECT_TILE` tiles, every row of both sequences
    assert si._select_tiles(8192) == si._SELECT_TILE
    assert rows_selected_fused(2, 8192, 512) == 2 * 8192
    assert rows_selected_fused(1, 16384, 512) == 16384
    assert si._select_tiles(256) == (128, 256)
    # 8,256 keys are no whole number of 128-lane tiles, and two rows of a
    # q tile's integers at 65,536 keys are more than VMEM may hold: the
    # reference, no row counted
    assert si._select_tiles(8256) is None and si._select_tiles(65536) is None
    assert rows_selected_fused(1, 8256, 64) == 0

    def target_tiles(S, block, heads=(H, HKV), dim=D):
        counted.clear()
        shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
        jax.eval_shape(        # under the gradient, as a step traces it
            jax.grad(functools.partial(si.indexer_loss, block=block)),
            shape(1, S, S), jax.ShapeDtypeStruct((1, S, S), jnp.int8),
            shape(1, S, heads[0], dim), shape(1, S, heads[1], dim),
            shape(1, heads[0], S))
        # every row's loss and gradient are the kernel's, or none's
        assert counted["attention.loss_rows_fused"] \
            == (S if counted["attention.target_tiles"] else 0)
        return (counted["attention.target_tiles"],
                counted["attention.target_tiles_skipped"])

    # the cell's: 256 x 512 tiles of the 8,192 square, two q tiles a k
    # tile: 272 on or under the diagonal and 240 above it a sequence
    assert target_tiles(8192, 512, (32, 4), 128) == (272, 240)
    assert target_tiles(2048, 256) == (2 * (1 + 2 + 3 + 4), 2 * (3 + 2 + 1))
    assert target_tiles(S, 16) == (1, 0)     # the whole sequence a tile

    def score_tiles(B, S, block, J=16, dim=64, dtype=jnp.bfloat16):
        counted.clear()
        shape = jax.ShapeDtypeStruct
        jax.eval_shape(        # under the gradient, as a step traces it:
            jax.grad(lambda *a: jnp.sum(jnp.tril(     # counted once
                si.index_scores(*a, block=block))), (0, 1, 2)),
            shape((B, S, J, dim), dtype), shape((B, S, dim), dtype),
            shape((B, S, J), jnp.float32))
        assert counted.pop("attention.indexer_heads") == J
        assert set(counted) == {"attention.score_tiles",
                                "attention.score_tiles_skipped"}
        return (counted["attention.score_tiles"],
                counted["attention.score_tiles_skipped"])

    # the cell's: `_SCORES_TILE` tiles of the 8,192 square of both sequences
    bq, bk = si._SCORES_TILE
    on = sum(min(8192 // bk, ((i + 1) * bq - 1) // bk + 1)
             for i in range(8192 // bq))
    assert score_tiles(2, 8192, 512) == (
        2 * on, 2 * ((8192 // bq) * (8192 // bk) - on))
    assert si._scores_tiles(jax.ShapeDtypeStruct(
        (2, 8192, 16, 64), jnp.bfloat16), 512) == (bq, bk)
    # blocks of 24 rows are no whole number of 16-row tiles; 8,256 keys
    # are no whole number of 128-lane tiles: the reference, nothing counted
    assert score_tiles(1, 96, 24) == (0, 0)
    assert score_tiles(1, 8256, 64) == (0, 0)
    # every head's q tile and dq in float32 that VMEM does not hold
    assert si._scores_tiles(jax.ShapeDtypeStruct(
        (1, 8192, 512, 128), jnp.bfloat16), 512) is None
    # 576 keys are no whole number of 128-lane tiles: the reference
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    assert si._target_tiles(shape(1, 576, H, D),
                            shape(1, 576, HKV, D)) is None
    # a q tile of every head that VMEM does not hold
    assert si._target_tiles(shape(1, 512, 256, 512),
                            shape(1, 512, 256, 512)) is None
    # nor the three rows of a q tile at 65,536 keys (the cell's fits)
    cell = lambda S: (jax.ShapeDtypeStruct((2, S, 32, 128), jnp.bfloat16),
                      jax.ShapeDtypeStruct((2, S, 4, 128), jnp.bfloat16))
    assert si._target_tiles(*cell(8192)) == si._TARGET_TILE
    assert si._target_tiles(*cell(65536)) is None
    assert target_tiles(576, 64) == (0, 0)


def exported_loss(S, block):
    """The module of `indexer_loss` and its gradient to the scores at the
    keye cell's heads (32 on 4 of 128, a batch of 2, bfloat16), shapes
    only, exported for a TPU from this host."""
    shape = jax.ShapeDtypeStruct
    args = (shape((2, S, S), jnp.float32), shape((2, S, S), jnp.int8),
            shape((2, S, 32, 128), jnp.bfloat16),
            shape((2, S, 4, 128), jnp.bfloat16),
            shape((2, 32, S), jnp.float32))
    with jax.default_matmul_precision("default"):
        return jax.export.export(jax.jit(jax.value_and_grad(
            functools.partial(si.indexer_loss, block=block))),
            platforms=["tpu"])(*args).mlir_module()


def test_the_loss_lowers_to_mosaic_for_tpu_at_the_cells_shape():
    """2 x 8,192 by blocks of 512: the loss and its gradient are ONE Mosaic
    custom call, no product is left to XLA, no head's scores and no block
    of the target exist, and nothing but the kernel makes a (B, S, S)
    float32: the cotangent's product with the gradient it wrote is the one
    other operation of that shape."""
    module = exported_loss(8192, 512)
    assert module.count("stablehlo.custom_call @tpu_custom_call") == 1
    assert "stablehlo.dot_general" not in module
    assert "512x8192" not in module
    # (the kernel's two results are a tuple's)
    made = {op for op, result in re.findall(
        r"= (stablehlo\.\w+).*?(tensor<[^>]*>) loc\(", module)
        if result == "tensor<2x8192x8192xf32>"}
    assert made == {"stablehlo.broadcast_in_dim", "stablehlo.multiply"}


def test_a_shape_the_loss_kernel_declines_lowers_to_the_reference():
    """8,256 keys (129 blocks of 64) are no whole number of 128-lane
    tiles: the plain XLA form by blocks on every platform, the TPU
    included."""
    module = exported_loss(8256, 64)
    assert "tpu_custom_call" not in module
    assert "stablehlo.dot_general" in module


def exported_selection(S, block):
    """The module of `select_top_k` at the keye cell's batch and top_k,
    shapes only, exported for a TPU from this host."""
    return jax.export.export(
        jax.jit(lambda s: si.select_top_k(s, 2048, block=block)),
        platforms=["tpu"])(
            jax.ShapeDtypeStruct((2, S, S), jnp.float32)).mlir_module()


def test_the_selection_lowers_to_mosaic_for_tpu_at_the_cells_shape():
    """2 x 8,192 by blocks of 512: the selection is ONE Mosaic custom call
    and nothing else: no loop, no block of the scores, and no operation
    but the kernel makes a mask (no triangle joined to it)."""
    module = exported_selection(8192, 512)
    assert module.count("stablehlo.custom_call @tpu_custom_call") == 1
    assert "stablehlo.while" not in module
    assert "512x8192" not in module
    assert "stablehlo.concatenate" not in module
    made = {op for op, result in re.findall(
        r"= (stablehlo\.\w+).*?(tensor<[^>]*>) loc\(", module)
        if result.endswith("xi8>")}
    assert made == {"stablehlo.custom_call"}


def test_a_shape_the_selection_kernel_declines_lowers_to_the_reference():
    """8,256 keys (129 blocks of 64) are no whole number of 128-lane
    tiles: the threshold search in plain XLA by blocks on every platform,
    the TPU included."""
    module = exported_selection(8256, 64)
    assert "tpu_custom_call" not in module
    assert "stablehlo.while" in module
    assert "stablehlo.concatenate" in module


def exported_scores(S, block):
    """The module of `index_scores` and its gradients at the keye cell's
    indexer (16 heads of 64 on one key head, a batch of 2, bfloat16),
    shapes only, exported for a TPU from this host."""
    shape = jax.ShapeDtypeStruct
    args = (shape((2, S, 16, 64), jnp.bfloat16),
            shape((2, S, 64), jnp.bfloat16), shape((2, S, 16), jnp.float32),
            shape((2, S, S), jnp.float32))
    with jax.default_matmul_precision("default"):
        return jax.export.export(jax.jit(jax.value_and_grad(
            lambda q, k, w, g: jnp.sum(jnp.where(
                g != 0, si.index_scores(q, k, w, block=block) * g, 0.0)),
            (0, 1, 2))), platforms=["tpu"])(*args).mlir_module()


def test_the_scores_lower_to_mosaic_for_tpu_at_the_cells_shape():
    """2 x 8,192 by blocks of 512: the scores and their backward are a
    Mosaic custom call each, no product is left to XLA and no head's
    products of a block exist."""
    module = exported_scores(8192, 512)
    assert module.count("stablehlo.custom_call @tpu_custom_call") == 2
    assert "stablehlo.dot_general" not in module
    assert "16x512x8192" not in module


def test_a_shape_the_scores_kernels_decline_lowers_to_the_reference():
    """8,256 keys (129 blocks of 64) are no whole number of 128-lane
    tiles: the blocked XLA form on every platform, the TPU included."""
    module = exported_scores(8256, 64)
    assert "tpu_custom_call" not in module
    assert "stablehlo.dot_general" in module
    assert "16x64x8256" in module

"""`ops/moe_rows.py`: the two row movements of a held share as Pallas
kernels (interpreted here) against the XLA forms they stand for, to the
last bit: forward, and through `ops/moe.py`'s `_take` and `_put` both
cotangents; the shapes the gate declines; what the job timeline is told;
and `moe_dispatch` end to end with the kernels engaged."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import interpreted, moe
from ray_tpu.ops import moe_rows as mr
from ray_tpu.util import tracing

# `pallas_call`s a traced pass holds: the one compiled for a TPU and the one
# interpreted elsewhere (`ops.by_platform`)
A_PASS = 2
# (E, k, experts held, of): each routed cell's hidden width, choices a
# token and share (`benchmark/configs/*.json`)
CELLS = {
    "mellum2": (2304, 8, 16, 64),
    "keye": (2048, 8, 16, 128),
    "sdar": (2048, 8, 16, 128),
    "kanana": (2048, 6, 16, 128),
    "lfm2": (2048, 4, 8, 64),
    "nemotron": (2688, 6, 8, 128),
}


def same(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def routing(T, k, n_experts, seed=0, favoured=0, bias=0.0):
    """(weights, experts) (T, k) of a router over ``n_experts``, the first
    ``favoured`` of them ``bias`` ahead."""
    scores = jax.random.normal(jax.random.PRNGKey(seed), (T, n_experts)) \
        + bias * (jnp.arange(n_experts) < favoured)
    weights, experts = jax.lax.top_k(jax.nn.sigmoid(scores), k)
    return weights / jnp.sum(weights, axis=1, keepdims=True), experts


def buffer_of(T, k, count, n_experts, seed=0):
    """`where` of `ops/moe.py:_over_held_rows` for a random routing of T
    tokens whose held share fits its buffer, its C and the weights."""
    weights, experts = routing(T, k, n_experts, seed)
    held = (0, count)
    C = moe.buffer_rows(T * k, count, n_experts)
    by_expert, sizes = moe._sort_by_expert(experts, n_experts, held)
    n_held = jnp.sum(sizes[:count])
    assert int(n_held) <= C
    where = moe._buffer_index(C, k, by_expert, n_held)
    return where, C, weights


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_valid", [0, 5, 16, 40, 48])
def test_take_rows_is_the_gather_and_its_mask(n_valid, dtype):
    """No row, a part of the first tile, a whole tile, a part of a later
    one and all C rows valid, in tiles of 16 rows: the rows to the bit and
    zeros behind them."""
    T, C, E = 32, 48, 256
    x = jax.random.normal(jax.random.PRNGKey(0), (T, E), dtype)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (C,), 0, T)
    got = mr._take(x, tokens, jnp.int32(n_valid), tile=16, interpret=True)
    same(got, mr._take_reference(x, tokens, n_valid))
    assert not np.asarray(got[n_valid:], np.float32).any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("scaled", [False, True])
def test_sum_rows_is_the_sum_over_the_choices_in_their_order(scaled, dtype):
    """Tokens with none, one, some and all k of their choices among the
    rows, two tiles of 16 tokens: the float32 sum in the order of the
    choices, with and without scales, to the bit (both forms compiled: the
    CPU's compiler contracts a product and a sum op by op otherwise)."""
    T, C, E, k = 32, 48, 256, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    rows = jax.random.normal(ks[0], (C, E), dtype)
    slot = jax.random.randint(ks[1], (T, k), 0, 3 * C)
    slot = slot.at[0].set(C).at[1].set(jnp.arange(k)).at[2].set(
        jnp.array([C, 7, C, C])).at[17].set(jnp.arange(k)[::-1] + 9)
    there = np.asarray(slot < C).sum(axis=1)
    assert {0, 1, k} <= set(there.tolist())
    scale = jax.random.uniform(ks[2], (T, k)) if scaled else None
    got = mr._sum(rows, jnp.int32(C), slot, scale, tile=16, interpret=True)
    same(got, jax.jit(mr._sum_reference)(rows, C, slot, scale))
    assert not np.asarray(got, np.float32)[there == 0].any()


@pytest.mark.parametrize("cell", CELLS)
def test_the_kernels_at_a_cells_width_choices_and_share(cell):
    """Each routed cell's hidden width, choices a token and held share, in
    bfloat16 as the cells run, at 64 tokens: the buffer `moe_dispatch`
    would make of a random routing, taken and summed back by the kernels
    and by the XLA forms."""
    E, k, count, n_experts = CELLS[cell]
    T = 64
    where, C, weights = buffer_of(T, k, count, n_experts)
    tokens, slot, n_held, _ = where
    assert mr._tile(C, E, jnp.bfloat16, mr._TAKE_TILE) == C
    x = jax.random.normal(jax.random.PRNGKey(2), (T, E), jnp.bfloat16)
    xs = mr._take(x, tokens, n_held, tile=C, interpret=True)
    same(xs, mr._take_reference(x, tokens, n_held))
    for scales in (None, weights):
        same(mr._sum(xs, n_held, slot, scales, tile=T, interpret=True),
             jax.jit(mr._sum_reference)(xs, n_held, slot, scales))


def _declined(monkeypatch):
    """Every shape declined: `ops/moe.py` runs the XLA forms (what was
    traced with the kernels is forgotten: a `custom_vjp` keeps its
    trace)."""
    monkeypatch.setattr(mr, "_tile", lambda *a: None)
    jax.clear_caches()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_take_and_put_and_their_cotangents_are_the_xla_forms(
        monkeypatch, dtype):
    """`_take` and `_put` of `ops/moe.py` with the kernels engaged, value
    and every cotangent (of x; of the rows and of the weights), against the same
    calls with the kernels declined, to the bit: four passes, a
    `pallas_call` each, and one more each that lays bfloat16 rows out for
    it."""
    T, k, E = 64, 4, 128
    where, C, scale = buffer_of(T, k, 2, 8)
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x, dy = (jax.random.normal(key, (T, E), dtype) for key in ks[:2])
    ys, dxs = (jax.random.normal(key, (C, E), dtype) for key in ks[2:])

    def both(x, ys, scale, dxs, dy):
        xs, take_back = jax.vjp(lambda x: moe._take(x, where), x)
        y, put_back = jax.vjp(
            lambda ys, scale: moe._put(ys, scale, where), ys, scale)
        return xs, take_back(dxs)[0], y, *put_back(dy)

    args = (x, ys, scale, dxs, dy)
    assert interpreted(x) and interpreted(ys)
    assert str(jax.make_jaxpr(both)(*args)).count("pallas_call") \
        == 4 * A_PASS * (2 if dtype == jnp.bfloat16 else 1)
    got = jax.jit(both)(*args)
    _declined(monkeypatch)
    assert "pallas_call" not in str(jax.make_jaxpr(both)(*args))
    want = jax.jit(lambda *a: both(*a))(*args)
    for g, w in zip(got, want):
        same(g, w)
    assert np.asarray(got[1], np.float32).any() \
        and np.asarray(got[4]).any()


@pytest.mark.parametrize("shape", [
    (64, 100),          # a row that is no whole number of lane tiles
    (24, 128),          # rows that are no multiple of 16
    (272, 128),         # rows that do not divide into tiles
])
def test_a_shape_the_gate_declines_takes_the_xla_form(shape):
    """...and says so on the job timeline: no `pallas_call`, the results
    the XLA forms', `moe.row_kernel_declined` counted and no pass."""
    rows, E = shape
    x = jax.random.normal(jax.random.PRNGKey(0), (32, E))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (rows,), 0, 32)
    slot = jax.random.randint(jax.random.PRNGKey(2), (rows, 2), 0, 64)
    with tracing.timeline_span("train.fit", root=True):
        took = jax.make_jaxpr(mr.take_rows)(x, tokens, 7)
        summed = jax.make_jaxpr(mr.sum_rows)(x, 32, slot)
        assert tracing.counter("moe.row_kernel_declined") == 2
        assert tracing.counter("moe.row_kernel_passes") == 0
    assert "pallas_call" not in str(took) + str(summed)
    same(mr.take_rows(x, tokens, 7), mr._take_reference(x, tokens, 7))
    same(jax.jit(mr.sum_rows)(x, 32, slot),
         jax.jit(mr._sum_reference)(x, 32, slot))
    assert mr._tile(rows, E, jnp.int8, 256) is None \
        and mr._tile(256, 128, jnp.int8, 256) is None


def test_past_the_interpreters_size_another_platform_runs_the_xla_form():
    """A shape the kernels take, too large to interpret: lowered for the
    CPU it is the XLA form and no pass is counted; for a TPU the Mosaic
    kernels."""
    T, k, E = 1024, 4, 128
    x = jax.random.normal(jax.random.PRNGKey(0), (T, E))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (T,), 0, T)
    slot = jax.random.randint(jax.random.PRNGKey(2), (T, k), 0, 2 * T)
    assert not interpreted(x)
    f = jax.jit(lambda x, tokens, slot: mr.sum_rows(
        mr.take_rows(x, tokens, 100), 100, slot))
    with tracing.timeline_span("train.fit", root=True):
        text = f.lower(x, tokens, slot).as_text()
        assert tracing.counter("moe.row_kernel_passes") == 0
        assert tracing.counter("moe.row_kernel_declined") == 0
    assert "tpu_custom_call" not in text
    exported = jax.export.export(f, platforms=["tpu"])(x, tokens, slot)
    assert exported.mlir_module().count("tpu_custom_call") == 2


def _experts(count, E, W, dtype):
    """(gate (count, E, W), down (count, W, E), run(gate, down) ->
    `run_experts` of `moe_dispatch`: gelu(x gate) down over ragged
    groups)."""
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    gate = (0.3 * jax.random.normal(ks[0], (count, E, W))).astype(dtype)
    down = (0.3 * jax.random.normal(ks[1], (count, W, E))).astype(dtype)

    def run(gate, down):
        return lambda xs, sizes: jax.lax.ragged_dot(
            jax.nn.gelu(jax.lax.ragged_dot(xs, gate, sizes)), down, sizes)
    return gate, down, run


@pytest.mark.parametrize("router", ["balanced", "overflowing"])
def test_moe_dispatch_with_the_kernels_is_the_loop_over_the_experts(
        monkeypatch, router):
    """2 of 16 experts held, 64 tokens x 4 choices of 128 wide, float32: y
    and the gradients in x, the weights and both expert matrices against
    every held expert run over every token, under a router that sends the
    share what its buffer holds (the kernels' branch runs) and under one
    that sends it more (the T*k branch, in the same program); the same
    call with the kernels declined agrees to a last place, and the
    timeline counts four passes of the kernels and none declined."""
    T, k, E, W, count, n_experts = 64, 4, 128, 32, 2, 16
    held = (0, count)
    weights, experts = routing(T, k, n_experts, seed=7, favoured=count,
                               bias=4.0 if router == "overflowing" else 0.0)
    C = moe.buffer_rows(T * k, count, n_experts)
    sent = int(np.asarray(experts < count).sum())
    assert (sent > C) == (router == "overflowing") and sent > 0
    gate, down, run = _experts(count, E, W, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (T, E))
    seed = jax.random.normal(jax.random.PRNGKey(6), (T, E))

    def dispatched(x, weights, gate, down):
        y, _ = moe.moe_dispatch(x, weights, experts, n_experts,
                                run(gate, down), held=held)
        return jnp.sum(y * seed), y

    def looped(x, weights, gate, down):
        y = jnp.zeros((T, E))
        for e in range(count):
            w = jnp.sum(jnp.where(experts == e, weights, 0), axis=1)
            y = y + w[:, None] * (jax.nn.gelu(x @ gate[e]) @ down[e])
        return jnp.sum(y * seed), y

    grad = lambda f: jax.jit(jax.value_and_grad(f, (0, 1, 2, 3),
                                                has_aux=True))
    args = (x, weights, gate, down)
    with tracing.timeline_span("train.fit", root=True):
        (_, y), grads = grad(dispatched)(*args)
        assert tracing.counter("moe.row_kernel_passes") >= 4
        assert tracing.counter("moe.row_kernel_declined") == 0
    with jax.default_matmul_precision("highest"):
        (_, y0), grads0 = grad(looped)(*args)
    for name, g, g0 in zip(("y", "x", "weights", "gate", "down"),
                           (y, *grads), (y0, *grads0)):
        assert float(jnp.max(jnp.abs(g - g0))) < 2e-5 * (
            1 + float(jnp.max(jnp.abs(g0)))), name
    _declined(monkeypatch)
    (_, y1), grads1 = grad(lambda *a: dispatched(*a))(*args)
    for g, g1 in zip((y, *grads), (y1, *grads1)):
        # a last place: the CPU's compiler contracts a product and a sum
        # differently in the two programs (the tests above have the bits)
        np.testing.assert_allclose(g, g1, rtol=0, atol=2e-6 * (
            1 + float(jnp.max(jnp.abs(g1)))))

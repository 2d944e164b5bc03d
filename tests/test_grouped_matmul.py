"""`ops/grouped_matmul.py`: the grouped matmul of a routed layer's experts
as Pallas kernels (interpreted here) against `jax.lax.ragged_dot` and its
`jax.grad`: the forward, the rows' gradient and the stacks' gradient over
groups that end inside a tile, empty groups, one group, no rows at all and
buffers longer than their groups' rows (zeros past the end, and what lies
past the end never read); the walk of the rows itself; the shapes the gate
declines; what the job timeline is told; two seeded faults."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import grouped_matmul as gm
from ray_tpu.ops import interpreted
from ray_tpu.util import tracing

K, N = 128, 256
# name: (rows of the buffer, rows of each group, rows of a tile)
CASES = {
    "ends_inside_tiles": (128, (10, 50, 4, 37), 32),
    "ends_on_tile_edges": (128, (32, 64, 32), 32),
    "empty_groups": (128, (0, 40, 0, 0, 24, 0), 32),
    "one_group_has_all": (128, (0, 128, 0), 32),
    "no_rows_at_all": (64, (0, 0, 0), 16),
    "buffer_twice_its_rows": (128, (20, 30, 14), 16),
    "buffer_eight_times_its_rows": (256, (5, 0, 20, 7), 16),
    "a_group_over_many_tiles": (256, (3, 200, 53), 32),
    "one_tile": (64, (20, 30), 64),
    # tiles of two sub-blocks (`_SUB`), those of no row of a group skipped
    "sub_blocks_of_a_tile": (512, (100, 0, 130, 150), 256),
    "sub_blocks_of_no_group": (512, (10, 20), 256),
}
PRODUCTS = ("forward", "rows_gradient", "stacks_gradient")
TYPES = {"float32": (jnp.float32, 1e-4), "bfloat16": (jnp.bfloat16, 0.02)}


def operands(R, sizes, dtype, seed=0):
    """(rows, stacks, cotangent, group_sizes), and the rows and the
    cotangent again with NaN where no group's row lies."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    n, total = len(sizes), sum(sizes)
    x = jax.random.normal(ks[0], (R, K), dtype)
    w = (0.3 * jax.random.normal(ks[1], (n, K, N))).astype(dtype)
    dy = jax.random.normal(ks[2], (R, N), dtype)
    held = (jnp.arange(R) < total)[:, None]
    clean = (jnp.where(held, x, 0), w, jnp.where(held, dy, 0),
             jnp.asarray(sizes, jnp.int32))
    return clean, (jnp.where(held, x, jnp.nan), jnp.where(held, dy, jnp.nan))


def wanted(x, w, dy, sizes):
    """`ragged_dot` and both of its gradients, over zeros past the end."""
    y, back = jax.vjp(lambda x, w: jax.lax.ragged_dot(x, w, sizes), x, w)
    return {"forward": y, **dict(zip(PRODUCTS[1:], back(dy)))}


def kernels(x, w, dy, sizes, tile):
    """The three kernels interpreted, by hand at ``tile`` rows a tile."""
    walk = gm._walk(sizes, x.shape[0], tile)
    return {
        "forward": lambda: gm._gmm(x, w, *walk, block=N, interpret=True),
        "rows_gradient": lambda: gm._gmm(
            dy, w, *walk, block=K, transposed=True, interpret=True),
        "stacks_gradient": lambda: gm._tgmm(
            x, dy, *walk, block=N, n=w.shape[0], interpret=True),
    }


def close(got, want, tol):
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    assert np.max(np.abs(got - want)) <= tol * max(1.0, np.max(np.abs(want)))


@pytest.mark.parametrize("dtype", sorted(TYPES))
@pytest.mark.parametrize("product", PRODUCTS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_a_product_is_ragged_dots(case, product, dtype):
    """...with what lies past the last group's last row poisoned: none of
    it is read into a result, and the result's rows there are zeros."""
    R, sizes, tile = CASES[case]
    dtype, tol = TYPES[dtype]
    (x, w, dy, group_sizes), (x_nan, dy_nan) = operands(R, sizes, dtype)
    got = kernels(x_nan, w, dy_nan, group_sizes, tile)[product]()
    close(got, wanted(x, w, dy, group_sizes)[product], tol)
    if product != "stacks_gradient":
        assert not np.asarray(got, np.float32)[sum(sizes):].any()


@pytest.mark.parametrize("block, step", [(128, 512), (256, 128)])
@pytest.mark.parametrize("product", PRODUCTS)
def test_a_matrix_cut_into_columns_is_the_same_product(product, block, step,
                                                       monkeypatch):
    """What `_plan` does to a matrix too large for VMEM whole (the
    result's columns in two blocks of a lane tile each, the visits walked
    again for each), and what the stacks' gradient does at a tile that a
    boundary crosses: its rows a step of the loop at a time."""
    jax.clear_caches()
    monkeypatch.setattr(gm, "_MASKED_STEP", step)
    R, sizes, tile = CASES["ends_inside_tiles"]
    (x, w, dy, group_sizes), (x_nan, dy_nan) = operands(R, sizes, jnp.float32)
    x, x_nan = (jnp.concatenate([a, a[:, ::-1]], axis=1) for a in (x, x_nan))
    w = jnp.concatenate([w, w[:, ::-1]], axis=1)          # K of 256 too
    walk = gm._walk(group_sizes, R, tile)
    got = {
        "forward": lambda: gm._gmm(x_nan, w, *walk, block=block,
                                   interpret=True),
        "rows_gradient": lambda: gm._gmm(
            dy_nan, w, *walk, block=block, transposed=True, interpret=True),
        "stacks_gradient": lambda: gm._tgmm(
            x_nan, dy_nan, *walk, block=block, n=len(sizes), interpret=True),
    }[product]()
    jax.clear_caches()
    close(got, wanted(x, w, dy, group_sizes)[product], 1e-4)
    big = gm._plan(4096, 8192, 8192, jnp.bfloat16)
    assert big.tile == 256 and big.forward < 8192 and big.stacks < 8192
    assert [gm._columns(width, lambda step: step <= 512)
            for width in (768, 896, 1536, 2304)] == [384, 128, 512, 384]


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_walk_visits_every_tile_and_every_group(case):
    """In row order; a tile once for each group that has a row in it, a
    group of no rows once, a tile of no group once; nothing else."""
    R, sizes, tile = CASES[case]
    n, tiles = len(sizes), R // tile
    group, row_tile, bounds = (np.asarray(a) for a in gm._walk(
        jnp.asarray(sizes, jnp.int32), R, tile))
    ends = np.cumsum(sizes)
    assert bounds.tolist() == [0, *ends, max(-(-ends[-1] // tile) - 1, 0),
                               bounds[-1]]
    assert len(group) == len(row_tile) == tiles + n
    assert (np.diff(group) >= 0).all() and (np.diff(row_tile) >= 0).all()
    live = list(zip(group[:bounds[-1]], row_tile[:bounds[-1]]))
    want = []
    for g, (size, end) in enumerate(zip(sizes, ends)):
        first = min((end - size) // tile, tiles - 1)
        last = (end - 1) // tile if size else first
        want += [(g, t) for t in range(first, last + 1)]
    assert live == want
    # behind them: each tile that holds no row once, then the last one
    behind = row_tile[bounds[-1]:].tolist()
    empty = list(range(-(-ends[-1] // tile), tiles))
    assert behind[:len(empty)] == empty
    assert set(behind[len(empty):]) <= {tiles - 1}


@pytest.mark.parametrize("dtype", sorted(TYPES))
def test_the_gradient_of_the_call_is_the_gradient_of_ragged_dot(dtype):
    """Through `over` and its `custom_vjp`, one walk for two products, at
    the tile the shape chooses: the values, both gradients, and on the job
    timeline a pass for each product and none declined."""
    dtype, tol = TYPES[dtype]
    R, sizes = 512, (40, 0, 300, 17)
    (x, w, dy, group_sizes), _ = operands(R, sizes, dtype)
    # every product's first operand (R, 128): what a CPU interprets
    w, down = w[:, :, :K], jnp.swapaxes(w[:, :, K:], 1, 2)
    assert interpreted(x) and gm._row_tile(R) == 256

    def loss(matmul):
        def f(x, w, down):
            y = matmul(jnp.tanh(matmul(x, w)), down)
            return jnp.sum(y.astype(jnp.float32) ** 2), y
        return jax.value_and_grad(f, (0, 1, 2), has_aux=True)

    with tracing.timeline_span("train.fit", root=True):
        (_, y), grads = loss(gm.over(group_sizes, R))(x, w, down)
        assert tracing.counter("moe.grouped_kernel_passes") == 6
        assert tracing.counter("moe.grouped_kernel_declined") == 0
    (_, y0), grads0 = loss(
        lambda a, w: jax.lax.ragged_dot(a, w, group_sizes))(x, w, down)
    close(y, y0, tol)
    for g, g0 in zip(grads, grads0):
        close(g, g0, 4 * tol)
    close(gm.grouped_matmul(x, w, group_sizes),
          jax.lax.ragged_dot(x, w, group_sizes), tol)


@pytest.mark.parametrize("shape, dtype", [
    ((64, 100, 128), jnp.float32),      # K is no whole number of lane tiles
    ((64, 128, 200), jnp.float32),      # nor N
    ((72, 128, 128), jnp.float32),      # rows that no tile divides
    ((400, 128, 128), jnp.float32),     # the same, past the smallest tile
    ((64, 128, 128), jnp.float16),      # neither bfloat16 nor float32
])
def test_a_shape_the_gate_declines_is_ragged_dots(shape, dtype):
    """...and says so on the job timeline: no `pallas_call`, the result
    `ragged_dot`'s, `moe.grouped_kernel_declined` counted and no pass."""
    R, k, n = shape
    sizes = jnp.asarray((10, 0, R // 2), jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(0), (R, k), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, k, n), dtype)
    with tracing.timeline_span("train.fit", root=True):
        traced = jax.make_jaxpr(gm.grouped_matmul)(x, w, sizes)
        assert tracing.counter("moe.grouped_kernel_declined") == 1
        assert tracing.counter("moe.grouped_kernel_passes") == 0
    assert "pallas_call" not in str(traced) and "ragged_dot" in str(traced)
    np.testing.assert_array_equal(
        np.asarray(gm.grouped_matmul(x, w, sizes), np.float32),
        np.asarray(jax.lax.ragged_dot(x, w, sizes), np.float32))
    assert gm._plan(R, k, n, dtype) is None


def test_operands_of_two_types_are_declined():
    x = jnp.ones((64, 128), jnp.bfloat16)
    w = jnp.ones((2, 128, 128), jnp.float32)
    sizes = jnp.asarray((10, 20), jnp.int32)
    assert "pallas_call" not in str(
        jax.make_jaxpr(gm.grouped_matmul)(x, w, sizes))


def test_past_the_interpreters_size_another_platform_runs_ragged_dot():
    """A shape the kernels take, too large to interpret: lowered for the
    CPU it is `ragged_dot` and its transposes and no pass is counted; for
    a TPU the three Mosaic kernels."""
    R, sizes = 1024, jnp.asarray((100, 0, 300, 60), jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(0), (R, K))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, K, N))
    assert not interpreted(x)
    f = jax.jit(jax.grad(lambda x, w: jnp.sum(
        gm.grouped_matmul(x, w, sizes) ** 2), (0, 1)))
    with tracing.timeline_span("train.fit", root=True):
        text = f.lower(x, w).as_text()
        assert tracing.counter("moe.grouped_kernel_passes") == 0
        assert tracing.counter("moe.grouped_kernel_declined") == 0
    assert "tpu_custom_call" not in text
    for g, g0 in zip(f(x, w), jax.grad(lambda x, w: jnp.sum(
            jax.lax.ragged_dot(x, w, sizes) ** 2), (0, 1))(x, w)):
        close(g, g0, 1e-4)
    exported = jax.export.export(f, platforms=["tpu"])(x, w)
    assert exported.mlir_module().count("tpu_custom_call") == 3


@pytest.mark.parametrize("cell, R, n, E, W", [
    ("mellum2", 65536, 16, 2304, 896),
    ("mellum2_overflowed", 131072, 16, 2304, 896),
    ("sdar", 65536, 16, 2048, 768),
    ("keye", 32768, 16, 2048, 768),
    ("kanana", 24576, 16, 2048, 768),
    ("lfm2", 16384, 8, 2048, 1536),
    ("nemotron", 12288, 8, 2688, 1920),
    ("olmoe", 131072, 64, 2048, 1024),
])
def test_every_routed_cells_shapes_are_taken(cell, R, n, E, W):
    """Both of a layer's shapes at the width `layers._widened` runs, in
    bfloat16, with blocks that fit the budget of VMEM."""
    for k, m in ((E, W), (W, E)):
        plan = gm._plan(R, k, m, jnp.bfloat16)
        assert plan and plan.tile in gm._ROW_TILES
        assert m % plan.forward == 0 and k % plan.transposed == 0 \
            and m % plan.stacks == 0


# -- seeded faults: each has to fail the property above ----------------------

def _fails(case, product, dtype="float32"):
    with pytest.raises(AssertionError):
        test_a_product_is_ragged_dots(case, product, dtype)


def test_a_boundary_mask_off_by_one_row_is_caught(monkeypatch):
    jax.clear_caches()
    real = gm._in_group
    monkeypatch.setattr(gm, "_in_group",
                        lambda lo, hi, rows: real(lo, hi + 1, rows))
    try:
        for product in PRODUCTS:
            _fails("ends_inside_tiles", product)
    finally:
        jax.clear_caches()


def test_a_stacks_gradient_not_reset_between_groups_is_caught(monkeypatch):
    jax.clear_caches()
    monkeypatch.setattr(gm, "_first_of", lambda ref, v: v == 0)
    try:
        _fails("ends_inside_tiles", "stacks_gradient")
    finally:
        jax.clear_caches()

"""`ops/selective_scan.py`: Mamba-1's selective scan.  The Pallas kernels
(interpreted here) against the recurrence run position by position in numpy
and against the plain `lax.scan` they stand for, y and all six gradients,
float32 and bfloat16, over several blocks of positions so that the state
and its cotangent cross a block's edge; what the kernels take; a declined
shape counted; a recomputed layer's replay; and what a TPU is given."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import layers
from ray_tpu.ops import interpreted
from ray_tpu.util import tracing

# the package's attribute of this name is the function (`ops/__init__.py`)
ss = importlib.import_module("ray_tpu.ops.selective_scan")

# (b, S, C, N): the least channels the kernels take (8 sublanes of 128
# lanes), four blocks of 8 positions a sequence
TAKEN = (1, 32, 1024, 16)
BATCHED = (2, 16, 1024, 16)
NAMES = ("u", "dt", "A", "B", "C", "D")
# largest |kernel - plain| over the largest |plain|: float32 differs by the
# order of its sums; bfloat16 inputs are read alike by both, their results
# rounded once
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2 ** -7}
A_PASS = 2      # `pallas_call`s of a traced pass: a TPU's and the interpreter's


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks of 8 positions: a test's sequence is several of them."""
    monkeypatch.setattr(ss, "_TIME_BLOCKS", (8,))


def make(shape=TAKEN, dtype=jnp.float32, seed=0):
    """(u, dt, A, B, C, D) and a cotangent of y."""
    b, S, C, N = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return ((jax.random.normal(ks[0], (b, S, C)).astype(dtype),
             jax.nn.softplus(jax.random.normal(ks[1], (b, S, C)) - 2.0),
             -jnp.exp(0.5 * jax.random.normal(ks[2], (C, N))),
             jax.random.normal(ks[3], (b, S, N)).astype(dtype),
             jax.random.normal(ks[4], (b, S, N)).astype(dtype),
             jax.random.normal(ks[5], (C,))),
            jax.random.normal(ks[6], (b, S, C)).astype(dtype))


def by_positions(u, dt, A, B, C, D):
    """The recurrence in numpy float64, one position after another."""
    u, dt, A, B, C, D = (np.asarray(x, np.float64) for x in
                         (u, dt, A, B, C, D))
    b, S, Cn = u.shape
    s = np.zeros((b, Cn, A.shape[1]))
    y = np.zeros_like(u)
    for t in range(S):
        s = np.exp(dt[:, t, :, None] * A) * s \
            + (dt[:, t] * u[:, t])[..., None] * B[:, t, None, :]
        y[:, t] = (s * C[:, t, None, :]).sum(-1) + D * u[:, t]
    return y


def value_and_grads(f, args, dy):
    y, vjp = jax.vjp(f, *args)
    return (y, *vjp(dy))


def close(got, want, tol):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


def n_kernels(f, *args):
    return str(jax.make_jaxpr(f)(*args)).count("pallas_call")


@pytest.mark.parametrize("shape", [TAKEN, BATCHED])
def test_the_kernel_is_the_recurrence_position_by_position(shape):
    args, _ = make(shape)
    assert ss._blocks(shape[1], shape[2], shape[3]) == (8, 128)
    assert n_kernels(ss.selective_scan, *args) == A_PASS
    y = ss.selective_scan(*args)
    assert y.shape == args[0].shape and y.dtype == args[0].dtype
    np.testing.assert_allclose(y, by_positions(*args), atol=2e-5)
    np.testing.assert_allclose(ss._reference(*args), by_positions(*args),
                               atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [TAKEN, BATCHED])
def test_the_kernels_are_the_plain_scan(shape, dtype):
    """y and all six gradients; the gradients come in their primals'
    shapes and types."""
    args, dy = make(shape, dtype)
    got = value_and_grads(ss.selective_scan, args, dy)
    want = value_and_grads(ss._reference, args, dy)
    for name, g, w, primal in zip(("y",) + NAMES, got, want,
                                  (args[0],) + args):
        assert g.dtype == primal.dtype and g.shape == primal.shape, name
        close(g, w, TOL[dtype])


def test_two_lane_blocks_and_one_are_the_same(monkeypatch):
    """C = 2,048 as one grid step of 256 lanes and as two of 128: dB and dC
    are summed over the lane blocks by XLA."""
    args, dy = make((1, 16, 2048, 16))
    one = value_and_grads(ss.selective_scan, args, dy)
    assert ss._blocks(16, 2048, 16) == (8, 256)
    monkeypatch.setattr(ss, "_LANES_MAX", 128)
    assert ss._blocks(16, 2048, 16) == (8, 128)
    jax.clear_caches()
    two = value_and_grads(ss.selective_scan, args, dy)
    for g, w in zip(two, one):
        close(g, w, TOL[jnp.float32])


def test_the_state_crosses_a_blocks_edge_both_ways():
    """An input at position 3 moves y at position 20, two blocks on, and
    its gradient gathers position 20's cotangent."""
    args, _ = make()
    u = args[0]
    bumped = ss.selective_scan(u.at[0, 3, 5].add(1.0), *args[1:])
    moved = np.abs(np.asarray(bumped - ss.selective_scan(*args)))[0, :, 5]
    assert moved[:3].max() == 0 and moved[20] > 0
    grad = jax.grad(lambda u: ss.selective_scan(u, *args[1:])[0, 20, 5])(u)
    assert float(jnp.abs(grad[0, 3, 5])) > 0
    assert float(jnp.abs(grad[0, 21:]).max()) == 0


@pytest.mark.parametrize("S,C,N,blocks", [
    (32, 1024, 16, (8, 128)),
    (64, 5120, 16, (8, 640)),
    (32, 2048, 32, (8, 256)),
    (32, 1024, 4, None),                # 8 positions' B_t: a quarter of a row
    (32, 1024, 33, None),               # more state than the loop writes out
    (32, 1000, 16, None),               # channels that fill no whole tiles
    (32, 512, 16, None),
    (20, 1024, 16, None),               # a sequence of no whole blocks
])
def test_what_the_kernels_take(S, C, N, blocks):
    assert ss._blocks(S, C, N) == blocks


def test_the_published_shape_takes_the_largest_block_its_states_fit(
        monkeypatch):
    monkeypatch.undo()
    T, lanes = ss._blocks(16384, 5120, 16)
    assert lanes == 640 and T in ss._TIME_BLOCKS
    assert (T + 1) * 16 * 8 * lanes * 4 <= ss._STATES_BYTES


@pytest.mark.parametrize("shape,taken", [
    (TAKEN, 1),
    ((1, 32, 512, 16), 0),                   # 512 channels: half a tile
    ((1, 20, 1024, 16), 0),                  # 20 positions
])
def test_a_call_counts_itself_and_a_declined_shape_is_the_plain_scan(
        shape, taken):
    """A declined shape: no `pallas_call` in the traced call, the plain
    scan's result and gradients to the last bit, and `sscan.fallbacks` 1."""
    args, dy = make(shape)
    names = ("sscan.kernels", "sscan.fallbacks", "sscan.positions")
    jax.eval_shape(ss.selective_scan, *args)
    assert [tracing.counter(n) for n in names] == [0, 0, 0]     # no job
    with tracing.timeline_span("train.fit", root=True):
        kernels = n_kernels(
            lambda *a: value_and_grads(ss.selective_scan, a, dy), *args)
        assert [tracing.counter(n) for n in names] == [
            taken, 1 - taken, shape[0] * shape[1]]
    assert kernels == taken * 2 * A_PASS
    for g, w in zip(value_and_grads(ss.selective_scan, args, dy),
                    value_and_grads(ss._reference, args, dy)):
        if taken:
            close(g, w, TOL[jnp.float32])
        else:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_operands_that_do_not_fit_are_refused():
    (u, dt, A, B, C, D), _ = make()
    with pytest.raises(ValueError, match="do not fit"):
        ss.selective_scan(u, dt, A, B[:, :, :4], C, D)
    with pytest.raises(ValueError, match="do not fit"):
        ss.selective_scan(u, dt[:, :16], A, B, C, D)


def test_a_replayed_layer_gives_the_same_gradients():
    """Under `checkpoint_layer` the backward pass makes the scan again (the
    states that enter the blocks carry no kept name): the gradients are
    those of the layer walked once."""
    args, dy = make()

    def layer(*a):
        return jnp.sum(jnp.square(ss.selective_scan(*a)) * dy)

    walked = jax.jit(jax.value_and_grad(layer, range(6)))(*args)
    replay = jax.value_and_grad(layers.checkpoint_layer(layer), range(6))
    # forward, the forward again with the entering states, backward
    assert n_kernels(replay, *args) == 3 * A_PASS
    for g, w in zip(jax.tree.leaves(jax.jit(replay)(*args)),
                    jax.tree.leaves(walked)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_past_the_interpreters_size_another_platform_runs_the_plain_scan():
    """A shape the kernels take, too large to interpret: lowered for the
    CPU it is the plain scan and counted as a fallback, for a TPU the
    Mosaic kernels."""
    args, dy = make((1, 128, 1024, 16))
    assert not interpreted(args[0])
    f = jax.jit(lambda *a: value_and_grads(ss.selective_scan, a, dy))
    with tracing.timeline_span("train.fit", root=True):
        text = f.lower(*args).as_text()
        assert tracing.counter("sscan.kernels") == 0
        assert tracing.counter("sscan.fallbacks") == 1
    assert "tpu_custom_call" not in text
    exported = jax.export.export(f, platforms=["tpu"])(*args)
    assert exported.mlir_module().count("tpu_custom_call") >= 2


def test_ops_exports_it():
    import ray_tpu.ops as ops

    assert ops.selective_scan is ss.selective_scan

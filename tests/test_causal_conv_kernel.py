"""`ops/causal_conv.py`: the causal depthwise convolution of a state-space
mixer.  The Pallas kernels (interpreted here) against the loop over
positions and against the plain form they stand for, forward and every
gradient, over three row tiles so that both halos cross a tile's edge; the
operands read where they lie in a wider array and the result cut into
several; the plain form itself at shapes the kernels decline; and what the
job timeline is told."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_short_conv import conv_by_positions

from ray_tpu.models import layers
from ray_tpu.ops import causal_conv as cc
from ray_tpu.ops import interpreted
from ray_tpu.util import tracing

# (B, S, C): three row tiles of 16 and one 128-lane block, a batch of 2 so
# that the taps' sums run over batch and tiles
TAKEN = (2, 48, 128)
TILE = 16
# three row tiles of 128: the kernels' inner loops take several turns a tile
LOOPED = (1, 384, 128)
# largest |kernel - plain| over the largest |plain|: float32 differs by the
# order of its sums; bfloat16 by a last place of the rounded result
TOL = {jnp.float32: 2e-6, jnp.bfloat16: 2 ** -7}
# `pallas_call`s a traced pass holds: the one compiled for a TPU and the one
# interpreted elsewhere (`ops.by_platform`)
A_PASS = 2
SILU = {None: lambda x: x, "silu": lambda x: x / (1 + np.exp(-x))}


def make(shape=TAKEN, taps=4, dtype=jnp.float32, seed=0, channels=None):
    """v (B, S, W), {"kernel": (C, taps), "bias": (C,)} over ``channels``
    (all of W unless given) and a cotangent (B, S, C)."""
    C = channels or shape[-1]
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], shape, dtype),
            {"kernel": jax.random.uniform(ks[1], (C, taps), jnp.float32,
                                          -1.0, 1.0),
             "bias": jax.random.normal(ks[2], (C,))},
            jax.random.normal(ks[3], (*shape[:-1], C), dtype))


def value_and_grads(f, v, p, dy):
    """(out, dv, dw, db) of ``f(v, w, b)`` under the cotangent ``dy``."""
    out, vjp = jax.vjp(f, v, p["kernel"], p["bias"])
    return (out, *vjp(dy))


def close(got, want, tol):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


def n_kernels(f, *args):
    return str(jax.make_jaxpr(f)(*args)).count("pallas_call")


@pytest.mark.parametrize("activation", [None, "silu"])
@pytest.mark.parametrize("taps", [2, 3, 4])
def test_the_kernel_is_the_loop_over_positions(taps, activation):
    v, p, _ = make(taps=taps)
    f = lambda v: cc.causal_conv(v, p["kernel"], p["bias"], activation)
    assert cc._blocks(v.shape[1], 0, v.shape[2], cc._FORWARD) == (TILE, 128)
    assert n_kernels(f, v) == A_PASS
    got = f(v)
    assert got.shape == v.shape and got.dtype == v.dtype
    np.testing.assert_allclose(
        got, conv_by_positions(v, p, SILU[activation]), atol=2e-5)


@pytest.mark.parametrize("taps", [2, 4])
def test_a_position_moves_its_taps_positions_across_a_tiles_edge(taps):
    """The first K - 1 positions see zeros before them, and a position
    just before a row tile's edge moves the K outputs from it on, in the
    next tile too (the halo behind); its gradient gathers the K cotangents
    from it on (the halo ahead)."""
    v, p, dy = make(taps=taps)
    w, b = p["kernel"], p["bias"]
    f = lambda v: cc.causal_conv(v, w, b)
    whole = f(v)
    np.testing.assert_allclose(whole[:, 0], w[:, -1] * v[:, 0] + b,
                               atol=1e-5)
    np.testing.assert_allclose(
        whole[:, 1], w[:, -1] * v[:, 1] + w[:, -2] * v[:, 0] + b, atol=1e-5)
    for t in (0, TILE - 1, 2 * TILE - 2, 3 * TILE - 1):
        moved = np.abs(np.asarray(f(v.at[:, t].add(1.0)) - whole)).max(
            axis=(0, 2)) > 1e-6
        reach = min(t + taps, v.shape[1])
        assert moved[t:reach].all()
        assert not moved[:t].any() and not moved[reach:].any()
        dv = jax.vjp(f, v)[1](dy)[0]
        want = sum(w[:, taps - 1 - k] * dy[:, t + k]
                   for k in range(reach - t))
        np.testing.assert_allclose(dv[:, t], want, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("activation", [None, "silu"])
@pytest.mark.parametrize("shape,taps", [(TAKEN, 2), (TAKEN, 4), (LOOPED, 4)])
def test_the_kernels_are_the_plain_form(shape, taps, activation, dtype):
    """out, dv, dw and db, each in its primal's type, with one
    `pallas_call` forward and one backward: against `jax.vjp` of the plain
    form, whose sums over the batch of 2 and the three tiles the
    accumulators make."""
    v, p, dy = make(shape, taps=taps, dtype=dtype)
    f = lambda v, w, b: cc.causal_conv(v, w, b, activation)
    plain = lambda v, w, b: cc._reference(
        v, w, b, start=0, activation=activation)
    got = jax.jit(lambda *a: value_and_grads(f, *a))(v, p, dy)
    want = value_and_grads(plain, v, p, dy)
    for g, w_, primal in zip(got, want, (v, v, p["kernel"], p["bias"])):
        assert g.dtype == primal.dtype
        close(g, w_, TOL[dtype])
    assert n_kernels(lambda *a: value_and_grads(f, *a), v, p, dy) \
        == 2 * A_PASS


@pytest.mark.parametrize("start,wide", [(128, 384), (256, 384), (0, 256)])
def test_the_channels_are_read_where_they_lie_in_a_wider_array(start, wide):
    """v handed over as columns of [more | v | more], as a Mamba-2 mixer
    has xBC: the same result and gradients to the last bit as the sliced
    call's, the wider array's gradient 0 around them."""
    v, p, dy = make((2, 48, wide), channels=128)
    f = lambda start: lambda v, w, b: cc.causal_conv(v, w, b, "silu", start)
    sliced = v[..., start:start + 128]
    out, dv, dw, db = value_and_grads(f(start), v, p, dy)
    assert n_kernels(f(start), v, p["kernel"], p["bias"]) == A_PASS
    assert dv.shape == v.shape
    around = np.ones(wide, bool)
    around[start:start + 128] = False
    assert not np.asarray(dv)[..., around].any()
    for g, w_ in zip((out, dv[..., start:start + 128], dw, db),
                     value_and_grads(f(0), sliced, p, dy)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))


@pytest.mark.parametrize("widths", [(256, 128), (128, 128, 128)])
def test_several_results_are_the_split_of_one(widths):
    """x, B and C each a result of a call of its own: the columns of the
    one result, and the same gradients."""
    v, p, dy = make((2, 48, 512), channels=384)
    cuts = np.cumsum(widths)[:-1]
    one = lambda v, w, b: cc.causal_conv(v, w, b, "silu", 128)
    cut = lambda v, w, b: cc.causal_conv(v, w, b, "silu", 128, widths)
    assert n_kernels(cut, v, p["kernel"], p["bias"]) == len(widths) * A_PASS
    outs, vjp = jax.vjp(cut, v, p["kernel"], p["bias"])
    assert isinstance(outs, tuple) and [o.shape[-1] for o in outs] \
        == list(widths)
    got = (jnp.concatenate(outs, axis=-1),
           *vjp(tuple(jnp.split(dy, cuts, axis=-1))))
    for g, w_ in zip(got, value_and_grads(one, v, p, dy)):
        close(g, w_, 1e-6)
    with pytest.raises(ValueError, match="do not sum"):
        cc.causal_conv(v, p["kernel"], p["bias"], "silu", 128, (256, 256))


# what the kernels decline, one size at a time from a shape they take; the
# (rows of a tile, channels of a block) of each result, forward and backward
@pytest.mark.parametrize("S,taps,start,widths,forward,backward", [
    (48, 4, 0, (128,), [(16, 128)], [(16, 128)]),
    (8192, 4, 4096, (4096, 1024, 1024), [(512, 1024)] * 3,
     [(2048, 256)] * 3),                    # Nemotron-H's xBC in W_in's
    (8192, 4, 5120, (5120, 128, 128), [(512, 1024), (512, 128), (512, 128)],
     [(2048, 256), (2048, 128), (2048, 128)]),  # Mamba-2 2.7B's one group
    (8192, 3, 0, (2048,), [(512, 1024)], [(2048, 256)]),    # LFM2's taps
    (40, 4, 0, (128,), None, None),         # rows that are no tile
    (48, 4, 0, (96,), None, None),          # 96 lanes
    (48, 4, 64, (128,), None, None),        # a start inside a lane block
    (48, 18, 0, (128,), None, None),        # taps past the halo's rows
])
def test_what_the_kernels_take(S, taps, start, widths, forward, backward):
    v = jax.ShapeDtypeStruct((2, S, start + sum(widths)), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((sum(widths), taps), jnp.float32)
    assert cc._taken(v, w, start, widths) is (forward is not None)
    at = start
    for width, fwd, bwd in zip(widths, forward or (), backward or ()):
        assert cc._blocks(S, at, width, cc._FORWARD) == fwd
        assert cc._blocks(S, at, width, cc._BACKWARD) == bwd
        at += width


@pytest.mark.parametrize("shape,taken", [
    (TAKEN, 1),
    ((2, 40, 128), 0),                      # 40 rows
    ((2, 48, 96), 0),                       # 96 lanes
])
def test_a_layer_counts_itself_and_whether_the_kernels_took_it(shape, taken):
    """`layers.causal_conv` over a declined shape: no `pallas_call` in the
    traced call, the plain form's result and gradients to the last bit, and
    `conv.kernel_layers` 0 of `conv.layers` 1."""
    v, p, dy = make(shape)
    f = lambda v, w, b: layers.causal_conv(
        v, {"kernel": w, "bias": b}, jax.nn.silu)
    names = ("conv.layers", "conv.kernel_layers")
    jax.eval_shape(f, v, p["kernel"], p["bias"])
    assert [tracing.counter(n) for n in names] == [0, 0]    # no job
    with tracing.timeline_span("train.fit", root=True):
        kernels = n_kernels(lambda *a: value_and_grads(f, *a), v, p, dy)
        assert [tracing.counter(n) for n in names] == [1, taken]
    assert kernels == taken * 2 * A_PASS
    plain = lambda v, w, b: cc._reference(
        v, w, b, start=0, activation="silu")
    for g, w_ in zip(value_and_grads(f, v, p, dy),
                     value_and_grads(plain, v, p, dy)):
        if taken:
            close(g, w_, TOL[jnp.float32])
        else:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))


def test_an_activation_the_kernels_do_not_know_is_refused():
    v, p, _ = make()
    with pytest.raises(ValueError, match="activation"):
        cc.causal_conv(v, p["kernel"], p["bias"], "gelu")
    with pytest.raises(KeyError):
        layers.causal_conv(v, p, jax.nn.gelu)


def test_a_replayed_layer_gives_the_same_gradients():
    """Under `checkpoint_layer` the backward pass makes the layer's forward
    again (the kernel's residuals are its inputs and carry no kept name):
    the gradients are those of the layer walked once."""
    v, p, dy = make()

    def layer(v, w, b):
        out = cc.causal_conv(jnp.tanh(v), w, b, "silu")
        return jnp.sum(jnp.square(out) * dy)

    args = (v, p["kernel"], p["bias"])
    walked = jax.jit(jax.value_and_grad(layer, (0, 1, 2)))(*args)
    replay = jax.value_and_grad(layers.checkpoint_layer(layer), (0, 1, 2))
    # forward, the forward again, backward
    assert n_kernels(replay, *args) == 3 * A_PASS
    for g, w_ in zip(jax.tree.leaves(jax.jit(replay)(*args)),
                     jax.tree.leaves(walked)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))


def test_past_the_interpreters_size_another_platform_runs_the_plain_form():
    """A shape the kernels take, too large to interpret: lowered for the
    CPU it is the plain form (no layer counted as the kernels'), for a TPU
    the Mosaic kernels."""
    v, p, dy = make((2, 512, 128))
    assert not interpreted(v)
    f = jax.jit(lambda *a: value_and_grads(
        lambda v, w, b: layers.causal_conv(v, {"kernel": w, "bias": b}),
        *a))
    with tracing.timeline_span("train.fit", root=True):
        text = f.lower(v, p, dy).as_text()
        assert tracing.counter("conv.layers") == 1
        assert tracing.counter("conv.kernel_layers") == 0
    assert "tpu_custom_call" not in text
    exported = jax.export.export(f, platforms=["tpu"])(v, p, dy)
    assert exported.mlir_module().count("tpu_custom_call") >= 2

"""`ops/gated_norm.py`: Mamba-2's gate and group norm (`mamba2`) and a KDA
mixer's norm a head with the gate behind it (`head`).  Each rule's Pallas
kernels (interpreted here) against the plain form they stand for, forward and
every gradient, at shapes their gate takes; the plain form itself at shapes
it declines; and what the job timeline is told.  The second rule besides
without its gate, with a constant for a gain, and as the L2 norm a mixer
writes through it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import bailing_hybrid, layers
from ray_tpu.ops import gated_norm as gn
from ray_tpu.ops import interpreted
from ray_tpu.util import tracing

EPS = 1e-5
# (B, S, C): 64 rows of 1,024 channels, the interpreter's 65,536 elements;
# 8 groups are one 128-lane tile each, 1 group is eight
TAKEN = (2, 32, 1024)
# largest |kernel - plain| over the largest |plain|: float32 differs by the
# order of its sums; bfloat16 by a last place of the rounded result
TOL = {jnp.float32: 2e-6, jnp.bfloat16: 2 ** -7}
# `pallas_call`s a traced pass holds: the one compiled for a TPU and the one
# interpreted elsewhere (`ops.by_platform`)
A_PASS = 2


# rule -> (the public function, its plain form, the counter of its rows),
# both functions as f(y, z, gain, groups, eps)
RULES = {
    "mamba2": (gn.gated_rms_norm, gn._reference, "ssm.gate_norm_rows_fused"),
    "head": (lambda y, z, gain, heads, eps: gn.head_rms_norm(
        y, gain, heads, eps, z),
             lambda y, z, gain, heads, eps: gn._head_reference(
        y, z, gain, heads, eps), "kda.head_norm_rows_fused"),
}
rules = pytest.mark.parametrize("rule", sorted(RULES))


def make(shape=TAKEN, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    y, z, dout = (jax.random.normal(k, shape, dtype) for k in ks[:3])
    return y, z, 1 + 0.3 * jax.random.normal(ks[3], shape[-1:]), dout


def value_and_grads(f, groups, y, z, gain, dout):
    """(out, dy, dz, d gain) of ``f(y, z, gain, groups, EPS)`` under the
    cotangent ``dout``."""
    out, vjp = jax.vjp(lambda y, z, gain: f(y, z, gain, groups, EPS),
                       y, z, gain)
    return (out, *vjp(dout))


def close(got, want, tol):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


@rules
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("groups", [8, 1])
def test_the_kernels_are_the_plain_form(groups, dtype, rule):
    """out and the gradients in y, z and the gain, each in its primal's
    type, with a `pallas_call` forward and one backward."""
    kernels, plain, _ = RULES[rule]
    args = make(dtype=dtype)
    got = jax.jit(lambda *a: value_and_grads(kernels, groups, *a))(*args)
    want = value_and_grads(plain, groups, *args)
    for g, w, primal in zip(got, want, (args[0], *args[:3])):
        assert g.shape == primal.shape and g.dtype == primal.dtype
        close(g, w, TOL[dtype])
    jaxpr = str(jax.make_jaxpr(lambda *a: value_and_grads(
        kernels, groups, *a))(*args))
    assert jaxpr.count("pallas_call") == 2 * A_PASS


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("gated", [True, False])
@pytest.mark.parametrize("constant", [None, 0.37])
def test_the_head_rule_without_a_gate_or_with_a_constant_gain(
        constant, gated, dtype):
    """The second rule's other forms: no z, so no sigmoid and no dz; a
    number for a gain, which has no gradient and no block in a kernel."""
    x, z, gain, dout = make(dtype=dtype)
    operands = (x,) + ((z,) if gated else ()) \
        + ((gain,) if constant is None else ())

    def over(f):
        def call(x, *rest):
            rest = list(rest)
            zz = rest.pop(0) if gated else None
            return f(x, zz, rest.pop(0) if constant is None else constant)
        return jax.vjp(call, *operands)

    run = lambda f: (lambda out, vjp: (out, *vjp(dout)))(*over(f))
    got = jax.jit(lambda: run(lambda x, z, g: gn.head_rms_norm(
        x, g, 8, EPS, z)))()
    want = run(lambda x, z, g: gn._head_reference(x, z, g, 8, EPS))
    assert len(got) == 1 + len(operands)
    for g, w, primal in zip(got, want, (x, *operands)):
        assert g.shape == primal.shape and g.dtype == primal.dtype
        close(g, w, TOL[dtype])
    # without a gate the result is another function
    if not gated:
        assert np.abs(np.asarray(got[0], np.float32) - np.asarray(
            gn._head_reference(x, z, gain if constant is None else constant,
                               8, EPS), np.float32)).max() > 0.05


@rules
@pytest.mark.parametrize("shape", [TAKEN, (2, 20, 768)])
def test_z_is_read_where_it_lies_in_a_wider_array(shape, rule):
    """z handed over as the first C columns of [z | more], as a Mamba-2
    mixer has it: the same result and gradients to the last bit, the wider
    array's gradient 0 past z, by the kernels and by the plain form."""
    y, z, gain, dout = make(shape)
    C = shape[-1]
    wide = jnp.concatenate([z, 7.0 + z[..., :192]], axis=-1)
    f = lambda *a: value_and_grads(RULES[rule][0], 8, *a)
    (out, dy, dwide, dgain), want = f(y, wide, gain, dout), f(y, z, gain, dout)
    assert dwide.shape == wide.shape and not np.asarray(dwide[..., C:]).any()
    for g, w in zip((out, dy, dwide[..., :C], dgain), want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    taken = gn._row_tile(y.size // C, C, 8) is not None
    assert ("pallas_call" in str(jax.make_jaxpr(f)(y, wide, gain, dout))) \
        is taken


@pytest.mark.parametrize("groups", [8, 2])
def test_the_kernels_are_the_rule_in_float64(groups):
    """The gate BEFORE the norm, a norm a group, the gain, eps: written
    out in numpy."""
    y, z, gain, _ = make()
    f64 = lambda a: np.asarray(a, np.float64)
    g = f64(y) * f64(z) / (1 + np.exp(-f64(z)))
    parts = g.reshape(*g.shape[:-1], groups, -1)
    parts = parts / np.sqrt((parts ** 2).mean(-1, keepdims=True) + EPS)
    want = parts.reshape(g.shape) * f64(gain)
    got = gn.gated_rms_norm(y, z, gain, groups, EPS)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # one norm for all the groups is another function
    other = gn.gated_rms_norm(y, z, gain, 1, EPS)
    assert np.abs(np.asarray(other) - want).max() > 0.05


@pytest.mark.parametrize("heads", [8, 2])
def test_the_head_rules_kernels_are_the_rule_in_float64(heads):
    """A norm a head FIRST, the gain, then the sigmoid of z: written out in
    numpy; Mamba-2's order of the same parts is another function."""
    x, z, gain, _ = make()
    f64 = lambda a: np.asarray(a, np.float64)
    parts = f64(x).reshape(*x.shape[:-1], heads, -1)
    parts = parts / np.sqrt((parts ** 2).mean(-1, keepdims=True) + EPS)
    want = parts.reshape(x.shape) * f64(gain) / (1 + np.exp(-f64(z)))
    got = gn.head_rms_norm(x, gain, heads, EPS, z)
    np.testing.assert_allclose(got, want, atol=1e-5)
    other = gn.gated_rms_norm(x, z, gain, heads, EPS)
    assert np.abs(np.asarray(other) - want).max() > 0.05


@pytest.mark.parametrize("scale", [1.0, 128 ** -0.5])
def test_an_l2_norm_written_through_the_head_rule_is_the_mixers(scale):
    """x rsqrt(sum x^2 + eps) scale = the rule at eps / D with the constant
    gain scale D^-1/2, as `models/bailing_hybrid.py:_unit` writes q's and
    k's norms: `_l2` on the (B, S, H, D) view to float32's rounding, the
    result and dx."""
    x, _, _, dout = make()
    H, D, eps = 8, 128, 1e-6
    viewed = lambda a: a.reshape(*a.shape[:-1], H, D)

    def through(x):
        return gn.head_rms_norm(x, scale * D ** -0.5, H, eps / D)

    def plain(x):
        return (bailing_hybrid._l2(viewed(x), eps) * scale).reshape(x.shape)

    assert "pallas_call" in str(jax.make_jaxpr(through)(x))
    for g, w in zip(jax.vjp(through, x)[1](dout) + (through(x),),
                    jax.vjp(plain, x)[1](dout) + (plain(x),)):
        close(g, w, TOL[jnp.float32])


# what `_row_tile` declines, one size at a time from a shape it takes
@pytest.mark.parametrize("rows,C,groups,tile", [
    (64, 1024, 8, 64),
    (16384, 4096, 8, gn._ROW_TILE),         # Nemotron-H's
    (16384, 5120, 1, gn._ROW_TILE),         # Mamba-2 2.7B's one group
    (64, 768, 8, None),                     # 96 lanes a group
    (64, 1024, 3, None),                    # groups that do not divide C
    (40, 1024, 8, None),                    # rows that are no tile
    (gn._ROW_TILE + 16, 1024, 8, None),     # a tile and a part of one
])
def test_what_the_kernels_take(rows, C, groups, tile):
    assert gn._row_tile(rows, C, groups) == tile


@pytest.mark.parametrize("shape,groups", [
    ((2, 32, 768), 8),                      # 96 lanes a group
    ((2, 20, 1024), 8),                     # 40 rows
])
@rules
def test_a_declined_shape_takes_the_plain_form_and_counts_no_row(
        shape, groups, rule):
    """No `pallas_call` in the traced call, the plain form's result to the
    last bit, and the counter present at 0."""
    kernels, plain, counter = RULES[rule]
    args = make(shape)
    f = lambda *a: value_and_grads(kernels, groups, *a)
    with tracing.timeline_span("train.fit", root=True):
        jaxpr = jax.make_jaxpr(f)(*args)
        assert tracing.counter(counter) == 0
    assert "pallas_call" not in str(jaxpr)
    for g, w in zip(f(*args), value_and_grads(plain, groups, *args)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@rules
def test_it_counts_the_rows_it_fuses_once_a_traced_call(rule):
    kernels, _, counter = RULES[rule]
    y, z, gain, _ = make()
    rows = y.shape[0] * y.shape[1]

    def traced():
        jax.eval_shape(lambda y, z: kernels(y, z, gain, 8, EPS), y, z)
        return tracing.counter(counter)

    assert traced() == 0                    # no job, no count
    with tracing.timeline_span("train.fit", root=True):
        assert traced() == rows
        assert traced() == 2 * rows


@rules
def test_a_replayed_layer_gives_the_same_gradients(rule):
    """Under `checkpoint_layer` the backward pass makes the layer's
    forward again where something behind the norm reads its result (W_out's
    gradient in the model, the square here; the kernel's residuals are its
    inputs and carry no kept name): the gradients are those of the layer
    walked once."""
    y, z, gain, dout = make()

    def layer(y, z, gain):
        out = RULES[rule][0](jnp.tanh(y), z, gain, 8, EPS)
        return jnp.sum(jnp.square(out) * dout)

    walked = jax.jit(jax.value_and_grad(layer, (0, 1, 2)))(y, z, gain)
    replay = jax.value_and_grad(layers.checkpoint_layer(layer), (0, 1, 2))
    # forward, the forward again, backward
    assert str(jax.make_jaxpr(replay)(y, z, gain)).count(
        "pallas_call") == 3 * A_PASS
    for g, w in zip(jax.tree.leaves(jax.jit(replay)(y, z, gain)),
                    jax.tree.leaves(walked)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@rules
def test_past_the_interpreters_size_another_platform_runs_the_plain_form(
        rule):
    """A shape the kernels take, too large to interpret: lowered for the
    CPU it is the plain form (no row counted), for a TPU the Mosaic
    kernels."""
    args = make((2, 64, 1024))
    assert not interpreted(args[0])
    kernels, _, counter = RULES[rule]
    f = jax.jit(lambda *a: value_and_grads(kernels, 8, *a))
    with tracing.timeline_span("train.fit", root=True):
        text = f.lower(*args).as_text()
        assert tracing.counter(counter) == 0
    assert "tpu_custom_call" not in text
    exported = jax.export.export(f, platforms=["tpu"])(*args)
    assert exported.mlir_module().count("tpu_custom_call") >= 2

"""`models/layers.py:short_conv`, the gated short convolution of an
LFM2-shaped layer: against an explicit loop over positions, causal at the
first positions, its gradient by finite differences, and what it counts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import layers
from ray_tpu.ops.gated_norm import gated_rms_norm
from ray_tpu.util import tracing

B, S, E, L = 2, 12, 8, 3


pytestmark = pytest.mark.usefixtures("highest_precision")


def make(seed=0, taps=L, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    u = jax.random.normal(ks[0], (B, S, E), dtype)
    p = {"in_proj": {"kernel": jax.random.normal(ks[1], (E, 3 * E)) * 0.5},
         "conv": {"kernel": jax.random.uniform(ks[2], (E, taps), jnp.float32,
                                               -1.0, 1.0)},
         "out_proj": {"kernel": jax.random.normal(ks[3], (E, E)) * 0.5}}
    return u, p


def by_positions(u, p):
    """The equations, one position and one tap at a time, in numpy."""
    u = np.asarray(u, np.float64)
    w_in, w, w_out = (np.asarray(p[k]["kernel"], np.float64)
                      for k in ("in_proj", "conv", "out_proj"))
    taps = w.shape[1]
    out = np.zeros_like(u)
    for n in range(u.shape[0]):
        bcz = u[n] @ w_in
        b, c, z = bcz[:, :E], bcz[:, E:2 * E], bcz[:, 2 * E:]
        g = b * z
        for t in range(u.shape[1]):
            v = np.zeros(E)
            for j in range(taps):
                at = t - (taps - 1) + j
                if at >= 0:                  # zero before the sequence
                    v += w[:, j] * g[at]
            out[n, t] = (c[t] * v) @ w_out
    return out


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_short_conv_is_the_loop_over_positions(taps):
    u, p = make(taps=taps)
    got = layers.short_conv(u, p)
    assert got.shape == u.shape and got.dtype == u.dtype
    np.testing.assert_allclose(got, by_positions(u, p), atol=2e-5)


def test_it_is_causal_and_the_first_positions_see_only_what_exists():
    u, p = make()
    whole = layers.short_conv(u, p)
    # a change at position t moves nothing before t and nothing after t+2
    for t in (0, 1, 5):
        moved = layers.short_conv(u.at[:, t].add(1.0), p)
        changed = np.abs(np.asarray(moved - whole)).max(axis=(0, 2)) > 1e-6
        assert not changed[:t].any() and not changed[t + L:].any()
        assert changed[t]
    # position 0 sees itself alone (the last tap), position 1 the last two
    bcz = u @ p["in_proj"]["kernel"]
    b, c, z = (bcz[..., i * E:(i + 1) * E] for i in range(3))
    g, w = b * z, p["conv"]["kernel"]
    first = (c[:, 0] * w[:, 2] * g[:, 0]) @ p["out_proj"]["kernel"]
    second = (c[:, 1] * (w[:, 2] * g[:, 1] + w[:, 1] * g[:, 0])) \
        @ p["out_proj"]["kernel"]
    np.testing.assert_allclose(whole[:, 0], first, atol=1e-5)
    np.testing.assert_allclose(whole[:, 1], second, atol=1e-5)


def test_its_gradient_by_finite_differences():
    u, p = make(3)
    seed = jax.random.normal(jax.random.PRNGKey(9), (B, S, E))

    def loss(u, p):
        return jnp.sum(layers.short_conv(u, p) * seed)

    du, dp = jax.grad(loss, (0, 1))(u, p)
    rng = np.random.default_rng(0)
    eps = 1e-2

    def directional(direction_u, direction_p):
        plus = loss(u + eps * direction_u, jax.tree.map(
            lambda a, d: a + eps * d, p, direction_p))
        minus = loss(u - eps * direction_u, jax.tree.map(
            lambda a, d: a - eps * d, p, direction_p))
        return float(plus - minus) / (2 * eps)

    zeros = jax.tree.map(jnp.zeros_like, p)
    for which in ("u", "in_proj", "conv", "out_proj"):
        d_u = jnp.asarray(rng.normal(size=u.shape), jnp.float32) \
            if which == "u" else jnp.zeros_like(u)
        d_p = zeros if which == "u" else {**zeros, which: {
            "kernel": jnp.asarray(rng.normal(size=p[which]["kernel"].shape),
                                  jnp.float32)}}
        analytic = float(jnp.sum(du * d_u)) + sum(
            float(jnp.sum(a * b)) for a, b in zip(
                jax.tree.leaves(dp), jax.tree.leaves(d_p)))
        assert directional(d_u, d_p) == pytest.approx(analytic, rel=2e-3,
                                                      abs=2e-3), which


def test_bfloat16_keeps_its_type_and_stays_close():
    u, p = make(dtype=jnp.bfloat16)
    got = layers.short_conv(u, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), p))
    assert got.dtype == jnp.bfloat16
    want = by_positions(u.astype(jnp.float32), jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), p))
    assert float(np.max(np.abs(np.asarray(got, np.float32) - want))) \
        < 0.05 * float(np.max(np.abs(want)))


def test_it_counts_its_layers_and_taps_as_it_is_traced():
    names = ("shortconv.layers", "shortconv.taps")
    u, p = make()

    def traced():
        before = [tracing.counter(name) for name in names]
        # a new function each time: `eval_shape` keeps a function's trace
        jax.eval_shape(lambda u, p: layers.short_conv(u, p), u, p)
        return [tracing.counter(name) - b for name, b in zip(names, before)]

    assert traced() == [0, 0]                    # no job, no count
    with tracing.timeline_span("train.fit", root=True):
        assert traced() == [1, 3]


# -- `causal_conv` and `gated_rms_norm`, a state-space mixer's glue ----------

def make_conv(seed=0, taps=4, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, S, E), dtype),
            {"kernel": jax.random.uniform(ks[1], (E, taps), jnp.float32,
                                          -1.0, 1.0),
             "bias": jax.random.normal(ks[2], (E,))})


def conv_by_positions(v, p, activation):
    v, w, b = (np.asarray(a, np.float64)
               for a in (v, p["kernel"], p["bias"]))
    taps = w.shape[1]
    out = np.zeros_like(v)
    for n in range(v.shape[0]):
        for t in range(v.shape[1]):
            total = b.copy()
            for j in range(taps):
                at = t - (taps - 1) + j
                if at >= 0:                  # zero before the sequence
                    total += w[:, j] * v[n, at]
            out[n, t] = activation(total)
    return out


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_causal_conv_is_the_loop_over_positions(taps):
    v, p = make_conv(taps=taps)
    silu = lambda x: x / (1 + np.exp(-x))
    got = layers.causal_conv(v, p, jax.nn.silu)
    assert got.shape == v.shape and got.dtype == v.dtype
    np.testing.assert_allclose(got, conv_by_positions(v, p, silu), atol=2e-5)
    np.testing.assert_allclose(layers.causal_conv(v, p),
                               conv_by_positions(v, p, lambda x: x),
                               atol=2e-5)


def test_causal_conv_is_causal_at_the_first_three_positions():
    v, p = make_conv()
    whole = layers.causal_conv(v, p)
    w, b = p["kernel"], p["bias"]
    # position 0 sees itself alone (the last tap), 1 the last two, 2 three
    np.testing.assert_allclose(whole[:, 0], w[:, 3] * v[:, 0] + b, atol=1e-5)
    np.testing.assert_allclose(
        whole[:, 1], w[:, 3] * v[:, 1] + w[:, 2] * v[:, 0] + b, atol=1e-5)
    np.testing.assert_allclose(
        whole[:, 2], w[:, 3] * v[:, 2] + w[:, 2] * v[:, 1]
        + w[:, 1] * v[:, 0] + b, atol=1e-5)
    for t in (0, 1, 2, 6):
        moved = layers.causal_conv(v.at[:, t].add(1.0), p)
        changed = np.abs(np.asarray(moved - whole)).max(axis=(0, 2)) > 1e-6
        assert not changed[:t].any() and not changed[t + 4:].any()
        assert changed[t:t + 4].all()
    # bfloat16 keeps its type
    half = layers.causal_conv(v.astype(jnp.bfloat16), p, jax.nn.silu)
    assert half.dtype == jnp.bfloat16


@pytest.mark.parametrize("groups", [1, 4])
def test_gated_rms_norm_is_the_gate_then_a_norm_a_group(groups):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    y, z = (jax.random.normal(k, (B, S, 16)) for k in ks[:2])
    gain = 1 + 0.3 * jax.random.normal(ks[2], (16,))
    g = np.asarray(y, np.float64) * (np.asarray(z, np.float64)
                                     / (1 + np.exp(-np.asarray(z, np.float64))))
    parts = g.reshape(B, S, groups, 16 // groups)
    parts = parts / np.sqrt((parts ** 2).mean(-1, keepdims=True) + 1e-5)
    want = parts.reshape(B, S, 16) * np.asarray(gain)
    got = gated_rms_norm(y, z, gain, groups, 1e-5)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # the norm before the gate is another function
    other = np.asarray(gated_rms_norm(
        y, jnp.ones_like(z) * 30.0, gain, groups, 1e-5)) \
        * np.asarray(jax.nn.silu(z)) / 30.0
    assert np.abs(other - want).max() > 0.1


def test_short_conv_is_bit_for_bit_what_it_was():
    """`causal_conv` shares `_back` and `_taps` with `short_conv`, whose
    gates and taps and their written-out backward are the parent's: the
    formulas of PR 34, written out here, give the same bits."""
    u, p = make(seed=7, dtype=jnp.bfloat16)
    p = jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)
    f32 = lambda x: x.astype(jnp.float32)
    back = lambda x, k: x if k == 0 else jnp.pad(
        x, ((0, 0), (k, 0), (0, 0)))[:, :x.shape[1]]

    def parent(u, p):
        bcz = u @ p["in_proj"]["kernel"]
        b, c, z = (bcz[..., i * E:(i + 1) * E] for i in range(3))
        w = p["conv"]["kernel"]
        v = sum(f32(w[:, j]) * (f32(back(b, L - 1 - j))
                                * f32(back(z, L - 1 - j))) for j in range(L))
        return (f32(c) * v).astype(u.dtype) @ p["out_proj"]["kernel"]

    got, want = jax.jit(layers.short_conv)(u, p), jax.jit(parent)(u, p)
    assert got.dtype == want.dtype == jnp.bfloat16
    assert (np.asarray(got, np.float32) == np.asarray(want, np.float32)).all()

"""`models/layers.py:short_conv`, the gated short convolution of an
LFM2-shaped layer: against an explicit loop over positions, causal at the
first positions, its gradient by finite differences, and what it counts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import layers
from ray_tpu.util import tracing

B, S, E, L = 2, 12, 8, 3


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


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

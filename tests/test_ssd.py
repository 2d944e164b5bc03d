"""`ops/ssd.py`: the chunked scan against the recurrence it stands for, run
position by position, forward and every gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssd
from ray_tpu.util import tracing

B, S, H, P, N = 2, 12, 4, 3, 5


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def make(groups, seed=0, dtype=jnp.float32, seq=S):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(ks[0], (B, seq, H, P), dtype),
            jax.nn.softplus(jax.random.normal(ks[1], (B, seq, H))),
            -jnp.exp(jax.random.normal(ks[2], (H,))),
            jax.random.normal(ks[3], (B, seq, groups, N), dtype),
            jax.random.normal(ks[4], (B, seq, groups, N), dtype),
            jax.random.normal(ks[5], (H,)))


def by_positions(x, dt, A, Bm, Cm, D):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t; y_t = h_t C_t + D x_t,
    head h on group h // (H / G), in float32."""
    x, Bm, Cm = (v.astype(jnp.float32) for v in (x, Bm, Cm))
    rep = x.shape[2] // Bm.shape[2]
    Bm, Cm = (jnp.repeat(v, rep, axis=2) for v in (Bm, Cm))

    def step(h, t):
        xt, dtt, bt, ct = t                     # (B,H,P) (B,H) (B,H,N) x 2
        h = jnp.exp(dtt * A)[..., None, None] * h \
            + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, ct) + D[:, None] * xt

    h0 = jnp.zeros((x.shape[0], x.shape[2], x.shape[3], Bm.shape[-1]))
    _, y = jax.lax.scan(step, h0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1)


def close(a, b, tol):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b))) <= tol * max(
        1.0, float(np.max(np.abs(b))))


@pytest.mark.parametrize("groups", [1, 4])
@pytest.mark.parametrize("chunk", [1, 4, S, 5, 64])
def test_the_chunked_scan_is_the_recurrence(chunk, groups):
    args = make(groups)
    assert close(ssd.ssd_scan(*args, chunk), by_positions(*args), 1e-5)


@pytest.mark.parametrize("groups", [1, 4])
@pytest.mark.parametrize("chunk", [1, 4, S, 5])
def test_every_gradient_is_the_recurrences(chunk, groups):
    args = make(groups, seed=1)
    weigh = jax.random.normal(jax.random.PRNGKey(9), (B, S, H, P))
    grads = lambda f: jax.grad(
        lambda *a: jnp.sum(f(*a) * weigh), argnums=tuple(range(6)))(*args)
    got = grads(lambda *a: ssd.ssd_scan(*a, chunk))
    for name, g, w in zip("x dt A B C D".split(), got, grads(by_positions)):
        assert g.shape == w.shape
        assert np.isfinite(np.asarray(g)).all(), name
        assert close(g, w, 1e-5), name


def test_the_carry_is_the_chunk_by_chunk_recurrence():
    """`_carry` against h_c = exp(total_c) h_{c-1} + S_c, chunk by chunk."""
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    states = jax.random.normal(ks[0], (B, 5, H, P, N))
    total = -jax.nn.softplus(jax.random.normal(ks[1], (B, 5, H)))
    h, want = jnp.zeros_like(states[:, 0]), []
    for c in range(5):
        want.append(h)
        h = jnp.exp(total[:, c])[..., None, None] * h + states[:, c]
    assert close(ssd._carry(states, total), jnp.stack(want, axis=1), 1e-5)


def test_a_state_crosses_three_chunks():
    """An impulse at position 0 alone, read at the last position of the
    third chunk: only the carried state can bring it there."""
    x, dt, A, Bm, Cm, D = make(1, seed=4)
    x = x.at[:, 1:].set(0)
    D = jnp.zeros_like(D)
    y = ssd.ssd_scan(x, dt, A, Bm, Cm, D, 4)
    want = by_positions(x, dt, A, Bm, Cm, D)
    assert float(jnp.max(jnp.abs(want[:, -1]))) > 1e-4
    assert close(y[:, -1], want[:, -1], 1e-5)
    # and with the carry cut, nothing arrives
    cut = ssd.ssd_scan(x[:, 8:], dt[:, 8:], A, Bm[:, 8:], Cm[:, 8:], D, 4)
    assert float(jnp.max(jnp.abs(cut))) == 0.0


@pytest.mark.parametrize("groups", [1, 4])
def test_bfloat16_keeps_its_type_and_stays_close(groups):
    args = make(groups, seed=5, dtype=jnp.bfloat16)
    got = ssd.ssd_scan(*args, 4)
    assert got.dtype == jnp.bfloat16
    want = by_positions(*args)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) \
        < 0.05 * float(jnp.max(jnp.abs(want)))


def test_it_counts_its_layers_and_states_its_sizes():
    names = ("ssm.layers", "ssm.heads", "ssm.state", "ssm.chunk")
    args = make(2)

    def traced():
        jax.eval_shape(lambda *a: ssd.ssd_scan(*a, 4), *args)
        return [tracing.counter(name) for name in names]

    assert traced() == [0, 0, 0, 0]              # no job, no count
    with tracing.timeline_span("train.fit", root=True):
        assert traced() == [1, H, N, 4]
        assert traced() == [2, H, N, 4]          # sizes stated, not summed

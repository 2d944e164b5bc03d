"""`ops/ssd.py`: the chunked scan against the recurrence it stands for, run
position by position, forward and every gradient: in both its forms, the
Pallas kernels (interpreted here) at sizes their gate takes and the batched
`einsum`s at sizes it declines."""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssd
from ray_tpu.util import tracing

B = 2


@dataclasses.dataclass(frozen=True)
class Form:
    """The sizes one form of the scan is tried at: S positions, P channels
    a head, a state of N, heads by the count of groups, and the chunks (the
    second a divisor of S, the last two the padded tail and one past S)."""
    kernels: bool
    S: int
    P: int
    N: int
    heads: dict
    chunks: tuple
    tol: float

    def H(self, groups):
        return self.heads[groups]


# the kernels want whole 128-lane tiles: a pair of heads of 64 in one group
# (32 KB of x in float32), or two groups of eight heads
FORMS = {
    "einsum": Form(False, 12, 3, 5, {1: 4, 4: 4}, (1, 4, 12, 5, 64), 1e-5),
    "kernels": Form(True, 24, 64, 128, {1: 2, 2: 16}, (8, 24, 16, 40), 2e-5),
}
CASES = [(name, groups, chunk) for name, form in FORMS.items()
         for groups in form.heads for chunk in form.chunks]


pytestmark = pytest.mark.usefixtures("highest_precision")


def make(form, groups, seed=0, dtype=jnp.float32):
    H, S, P, N = form.H(groups), form.S, form.P, form.N
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(ks[0], (B, S, H, P), dtype),
            jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))),
            -jnp.exp(jax.random.normal(ks[2], (H,))),
            jax.random.normal(ks[3], (B, S, groups, N), dtype),
            jax.random.normal(ks[4], (B, S, groups, N), dtype),
            jax.random.normal(ks[5], (H,)))


def scan(form, *args):
    """`ssd_scan`, held to the form: the kernels' sizes must not fall to
    the `einsum`s, and the others must say that they do."""
    with warnings.catch_warnings(record=True) as said:
        warnings.simplefilter("always")
        y = ssd.ssd_scan(*args)
    fell = [w for w in said if w.category is ssd.SsdFallbackWarning]
    assert bool(fell) != form.kernels, [str(w.message) for w in said]
    return y


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


@pytest.mark.parametrize("name,groups,chunk", CASES)
def test_the_chunked_scan_is_the_recurrence(name, groups, chunk):
    form = FORMS[name]
    args = make(form, groups)
    assert close(scan(form, *args, chunk), by_positions(*args), form.tol)


@pytest.mark.parametrize("name,groups,chunk", [
    c for c in CASES if c[2] <= FORMS[c[0]].S])
def test_every_gradient_is_the_recurrences(name, groups, chunk):
    form = FORMS[name]
    args = make(form, groups, seed=1)
    weigh = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    grads = lambda f: jax.grad(
        lambda *a: jnp.sum(f(*a) * weigh), argnums=tuple(range(6)))(*args)
    got = grads(lambda *a: scan(form, *a, chunk))
    for what, g, w in zip("x dt A B C D".split(), got, grads(by_positions)):
        assert g.shape == w.shape and g.dtype == w.dtype, what
        assert np.isfinite(np.asarray(g)).all(), what
        assert close(g, w, form.tol), what


def test_the_carry_is_the_chunk_by_chunk_recurrence():
    """`_carry` against h_c = exp(total_c) h_{c-1} + S_c, chunk by chunk."""
    H, P, N = 4, 3, 5
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    states = jax.random.normal(ks[0], (B, 5, H, P, N))
    total = -jax.nn.softplus(jax.random.normal(ks[1], (B, 5, H)))
    h, want = jnp.zeros_like(states[:, 0]), []
    for c in range(5):
        want.append(h)
        h = jnp.exp(total[:, c])[..., None, None] * h + states[:, c]
    assert close(ssd._carry(states, total), jnp.stack(want, axis=1), 1e-5)


@pytest.mark.parametrize("name", list(FORMS))
def test_a_state_crosses_three_chunks(name):
    """An impulse at position 0 alone, read at the last position of the
    third chunk: only the carried state can bring it there."""
    form = FORMS[name]
    third = form.S // 3
    x, dt, A, Bm, Cm, D = make(form, 1, seed=4)
    x = x.at[:, 1:].set(0)
    D = jnp.zeros_like(D)
    y = scan(form, x, dt, A, Bm, Cm, D, third)
    want = by_positions(x, dt, A, Bm, Cm, D)
    assert float(jnp.max(jnp.abs(want[:, -1]))) > 1e-4
    assert close(y[:, -1], want[:, -1], form.tol)
    # and with the carry cut, nothing arrives
    last = slice(2 * third, None)
    cut = scan(form, x[:, last], dt[:, last], A, Bm[:, last], Cm[:, last], D,
               third)
    assert float(jnp.max(jnp.abs(cut))) == 0.0


@pytest.mark.parametrize("name,groups", [
    (name, groups) for name, form in FORMS.items() for groups in form.heads])
def test_bfloat16_keeps_its_type_and_stays_close(name, groups):
    form = FORMS[name]
    args = make(form, groups, seed=5, dtype=jnp.bfloat16)
    got = scan(form, *args, form.chunks[0] if form.kernels else 4)
    assert got.dtype == jnp.bfloat16
    want = by_positions(*args)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) \
        < 0.05 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("name", list(FORMS))
def test_bfloat16_gradients_keep_their_types_and_stay_close(name):
    """Every gradient in its operand's type, dt's, A's and D's float32, and
    within bfloat16's reach of the float32 recurrence's."""
    form = FORMS[name]
    args = make(form, 1, seed=6, dtype=jnp.bfloat16)
    grads = lambda f: jax.grad(
        lambda *a: jnp.sum(f(*a).astype(jnp.float32)),
        argnums=tuple(range(6)))(*args)
    got = grads(lambda *a: scan(form, *a, form.chunks[0] if form.kernels
                                else 4))
    for what, g, w, a in zip("x dt A B C D".split(), got,
                             grads(by_positions), args):
        assert g.dtype == a.dtype and g.shape == a.shape, what
        assert float(jnp.max(jnp.abs(g.astype(jnp.float32) - w))) \
            < 0.05 * float(jnp.max(jnp.abs(w.astype(jnp.float32)))), what


NAMES = ("ssm.layers", "ssm.kernel_layers", "ssm.heads", "ssm.state",
         "ssm.chunk")


@pytest.mark.parametrize("name", list(FORMS))
def test_it_counts_its_layers_and_states_its_sizes(name):
    """`ssm.kernel_layers` equals `ssm.layers` where the kernels run, and
    stays 0 where they decline."""
    form = FORMS[name]
    args = make(form, 1)
    H, N, took = form.H(1), form.N, int(form.kernels)

    def traced():
        jax.eval_shape(lambda *a: scan(form, *a, 8), *args)
        return [tracing.counter(name) for name in NAMES]

    assert traced() == [0, 0, 0, 0, 0]           # no job, no count
    with tracing.timeline_span("train.fit", root=True):
        assert traced() == [1, took, H, N, 8]
        assert traced() == [2, 2 * took, H, N, 8]    # sizes stated, not summed


# what `_kernel_problem` declines, one size at a time from a shape it takes
TAKEN = dict(H=16, P=64, G=2, N=128, Q=128)


@pytest.mark.parametrize("change,why", [
    ({}, None),
    (dict(H=64, G=8), None),                    # Nemotron-H's
    (dict(H=80, G=1, Q=256), "wider than"),     # Mamba-2 2.7B's one group
    (dict(H=8, P=128, G=1), None),
    (dict(P=48), "128-lane tiles"),             # 384 lanes, heads astride
    (dict(P=16), None),                         # eight heads a tile
    (dict(N=64), "the state"),
    (dict(Q=12), "the chunk"),
    (dict(H=8), "multiple of 8 rows"),          # 4 heads a group of 2
])
def test_what_the_kernels_take(change, why):
    problem = ssd._kernel_problem(**{**TAKEN, **change})
    assert (problem is None) if why is None else (why in problem), problem


def test_a_declined_shape_takes_the_einsum_form_and_says_so():
    """No `pallas_call` in the traced call, the warning names the reason,
    and the result is the `einsum` form's to the last bit."""
    form = FORMS["einsum"]
    args = make(form, 4)
    with pytest.warns(ssd.SsdFallbackWarning, match="128-lane tiles"):
        jaxpr = jax.make_jaxpr(lambda *a: ssd.ssd_scan(*a, 4))(*args)
    assert "pallas_call" not in str(jaxpr)
    np.testing.assert_array_equal(
        np.asarray(scan(form, *args, 4)),
        np.asarray(ssd._ssd_einsum(*args, 4)))
    taken = jax.make_jaxpr(
        lambda *a: ssd.ssd_scan(*a, 8))(*make(FORMS["kernels"], 1))
    assert "pallas_call" in str(taken)


def test_past_the_interpreters_size_another_platform_runs_the_einsums():
    """A shape the kernels take, too large to interpret: lowered for the
    CPU it is the `einsum` form (no kernel counted), for a TPU the Mosaic
    kernel."""
    form = dataclasses.replace(FORMS["kernels"], S=512)
    args = make(form, 2)
    assert not ssd.interpreted(args[0])
    f = jax.jit(lambda *a: scan(form, *a, 128))
    with tracing.timeline_span("train.fit", root=True):
        text = f.lower(*args).as_text()
        assert tracing.counter("ssm.kernel_layers") == 0
    assert "tpu_custom_call" not in text and "dot_general" in text
    exported = jax.export.export(f, platforms=["tpu"])(*args)
    assert "tpu_custom_call" in exported.mlir_module()

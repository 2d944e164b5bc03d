"""`ops/kda.py`: Kimi Delta Attention's rule.  The plain chunked form and the
Pallas kernels (interpreted here) against the recurrence run position by
position in float64 numpy and under `jax.grad` of the same recurrence in jax:
o and all five gradients, at one chunk and at many, at 1, 2, 3 and 16 heads
(so many a grid step, or a divisor of them) and chunks of 16 and 64, with g
drawn AT the gate's bound; the state carried from chunk to chunk, and handed to the
backward as it entered each chunk; what the kernels take; a declined shape
counted; a recomputed layer's replay; and what a TPU is given."""

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu.ops
from ray_tpu.models import layers
from ray_tpu.ops import interpreted
from ray_tpu.ops import kda as K
from ray_tpu.util import tracing

NAMES = ("q", "k", "v", "g", "beta")
# (B, S, H, K, V): a head is a lane tile, as the kernels ask
ONE_CHUNK = (1, 64, 2, 128, 128)
MANY = (1, 192, 1, 128, 128)
BATCHED = (2, 128, 1, 128, 128)
# a shape the kernels decline: heads of 32 and 48
DECLINED = (2, 128, 2, 32, 48)
# (shape, chunk): the heads a grid step takes (`_step_heads`) are all of 1, 2
# and 3 and a divisor of 16; 16 heads of 64 positions are past the
# interpreter's size, which `interpret_all` lifts
HEADS = {
    "three_heads": ((1, 128, 3, 128, 128), 64),
    "sixteen_heads": ((1, 128, 16, 128, 128), 64),
    "one_head_chunks_of_16": ((1, 48, 1, 128, 128), 16),
    "two_heads_chunks_of_16": ((2, 32, 2, 128, 128), 16),
    "three_heads_chunks_of_16": ((1, 64, 3, 128, 128), 16),
    "sixteen_heads_chunks_of_16": ((1, 32, 16, 128, 128), 16),
}
SHAPES = {"one_chunk": (ONE_CHUNK, 64), "three_chunks": (MANY, 64),
          "batched": (BATCHED, 64), **HEADS}


@pytest.fixture
def interpret_all(monkeypatch):
    """Every size of these tests runs the kernels interpreted."""
    monkeypatch.setattr(ray_tpu.ops, "INTERPRET_MAX_ELEMS", 1 << 20)


def make(shape, seed=0, at_bound=False, dtype=jnp.float32):
    """(q, k, v, g, beta) as a mixer hands them over, and a cotangent."""
    B, S, H, Kd, V = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, S, H, Kd))) * Kd ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, H, Kd)))
    v = jax.random.normal(ks[2], (B, S, H, V))
    g = -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (B, S, H, Kd)))
    if at_bound:
        g = jnp.full_like(g, -5.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    return ((q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta),
            jax.random.normal(ks[5], (B, S, H, V)))


def by_positions(q, k, v, g, beta, every=None):
    """The rule as its equation reads, numpy float64, one position after
    another: S_t = (I - beta k k') Diag(alpha) S_{t-1} + beta k v'.  -> o;
    with ``every`` the state before each ``every``-th position instead,
    transposed as the kernels hold it: (B, H, S / every up, V, K)."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    B, S, H, Kd = q.shape
    o = np.zeros(v.shape)
    entered = np.zeros((B, H, -(-S // (every or S)), v.shape[-1], Kd))
    for b in range(B):
        for h in range(H):
            state = np.zeros((Kd, v.shape[-1]))
            for t in range(S):
                if every and t % every == 0:
                    entered[b, h, t // every] = state.T
                kt, bt = k[b, t, h], beta[b, t, h]
                state = np.exp(g[b, t, h])[:, None] * state
                state = state - bt * np.outer(kt, kt @ state) \
                    + bt * np.outer(kt, v[b, t, h])
                o[b, t, h] = state.T @ q[b, t, h]
    return entered if every else o


def recurrence(q, k, v, g, beta):
    """The same in jax float32, for `jax.grad`."""
    B, S, H, Kd = q.shape

    def step(state, x):
        qt, kt, vt, gt, bt = x
        state = jnp.exp(gt)[..., None] * state
        seen = jnp.einsum("bhkv,bhk->bhv", state, kt)
        state = state + (bt[..., None] * kt)[..., None] \
            * (vt - seen)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    xs = tuple(jnp.moveaxis(x.astype(jnp.float32), 1, 0)
               for x in (q, k, v, g, beta))
    with jax.default_matmul_precision("highest"):
        _, o = jax.lax.scan(
            step, jnp.zeros((B, H, Kd, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1)


def value_and_grads(rule, args, do):
    out, back = jax.vjp(rule, *args)
    return (out, *back(do.astype(out.dtype)))


def close(got, want, tol):
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    assert np.max(np.abs(got - want)) <= tol * max(np.max(np.abs(want)),
                                                   1e-30)


A_PASS = 2      # `pallas_call`s of a traced pass: a TPU's and the interpreter's


def n_kernels(f, *args):
    return str(jax.make_jaxpr(f)(*args)).count("pallas_call")


def plain(q, k, v, g, beta, chunk=64):
    C = K._chunk_size(q.shape[1], chunk)
    return K._plain(q, k, K._scaled(k, beta), K._scaled(v, beta), g, C)[0]


@pytest.mark.parametrize("case", SHAPES)
@pytest.mark.parametrize("rule", [plain, K.kda], ids=["plain", "kernels"])
def test_the_rule_is_the_recurrence_position_by_position(
        case, rule, interpret_all):
    shape, chunk = SHAPES[case]
    args, _ = make(shape)
    close(rule(*args, chunk=chunk), by_positions(*args), 1e-5)


@pytest.mark.parametrize("case", ["one_chunk", "three_chunks", *HEADS])
@pytest.mark.parametrize("rule", [plain, K.kda], ids=["plain", "kernels"])
def test_all_five_gradients_are_the_recurrences(case, rule, interpret_all):
    """The plain form under `jax.grad`; the kernels' own backward."""
    shape, chunk = SHAPES[case]
    args, do = make(shape)
    by_chunks = functools.partial(rule, chunk=chunk)
    for name, g, w in zip(("o", *NAMES), value_and_grads(by_chunks, args, do),
                          value_and_grads(recurrence, args, do)):
        close(g, w, 2e-5), name


@pytest.mark.parametrize("heads", [1, 4, 8])
@pytest.mark.parametrize("case", [c for c in HEADS if "sixteen" in c])
def test_the_kernels_are_the_plain_form_a_divisor_of_the_heads_a_step(
        case, heads, interpret_all, monkeypatch):
    """16 heads, a grid step 1, 4 or 8 of them (the grid's second axis walks
    the rest): o and the five gradients of the kernels beside the plain
    form's, which holds all heads at once."""
    shape, chunk = SHAPES[case]
    args, do = make(shape)
    monkeypatch.setattr(K, "_STEP_HEADS", heads)
    jax.clear_caches()
    assert K._step_heads(16, K._chunk_size(shape[1], chunk),
                         jnp.float32) == heads
    for g, w in zip(
            value_and_grads(functools.partial(K.kda, chunk=chunk), args, do),
            value_and_grads(functools.partial(plain, chunk=chunk), args, do)):
        close(g, w, 2e-6)
    jax.clear_caches()


@pytest.mark.parametrize("H, most, limit, heads", [
    (1, 16, 64, 1), (2, 16, 64, 2), (3, 16, 64, 3), (16, 16, 64, 16),
    (16, 8, 64, 8), (12, 8, 64, 6), (7, 4, 64, 1), (16, 1, 64, 1),
    # by VMEM: 16 heads of a chunk of 64 in bfloat16 count 21.75 MiB, 8
    # heads half of it, and one head is taken whatever it counts
    (16, 16, 16, 8), (16, 16, 2, 1), (16, 16, 0, 1),
])
def test_the_heads_a_grid_step_takes_divide_the_heads_and_fit_vmem(
        H, most, limit, heads, monkeypatch):
    monkeypatch.setattr(K, "_STEP_HEADS", most)
    monkeypatch.setattr(K, "_COMPILER_PARAMS", K.pltpu.CompilerParams(
        vmem_limit_bytes=limit << 20))
    assert K._step_heads(H, 64, jnp.bfloat16) == heads
    assert K._step_bytes(16, 64, 2) == 21.75 * 2 ** 20


@pytest.mark.parametrize("shape", [ONE_CHUNK, (1, 64, 16, 128, 128)],
                         ids=["two_heads", "sixteen_heads"])
@pytest.mark.parametrize("rule", [plain, K.kda], ids=["plain", "kernels"])
def test_at_the_gates_bound_for_a_whole_chunk_it_is_finite_and_the_recurrence(
        rule, shape, interpret_all):
    """g = -5 at every one of 64 positions: exp(-G) over the chunk would be
    exp(320); against origins 16 back nothing overflows and nothing is
    lost; several heads a grid step alike."""
    args, do = make(shape, at_bound=True)
    got = value_and_grads(rule, args, do)
    assert all(np.isfinite(np.asarray(x)).all() for x in got)
    close(got[0], by_positions(*args), 1e-5)
    # dg is what is left of sums that all but cancel: 4e-4 at its largest
    for g, w in zip(got, value_and_grads(recurrence, args, do)):
        close(g, w, 2e-4)


@pytest.mark.parametrize("rule", [plain, K.kda], ids=["plain", "kernels"])
def test_the_state_is_carried_from_chunk_to_chunk(rule, monkeypatch):
    """The second chunk's o depends on the first chunk's keys and values;
    with the carry zeroed (a fault) it is another result."""
    args, _ = make(MANY)
    sound = np.asarray(rule(*args))
    moved = list(args)
    moved[2] = args[2].at[:, :64].multiply(2.0)         # v of chunk 0
    assert np.max(np.abs(np.asarray(rule(*moved))[:, 64:]
                         - sound[:, 64:])) > 1e-3
    forward = K._chunk_forward

    def no_carry(q, k, kb, vb, g, state):
        return forward(q, k, kb, vb, g, jnp.zeros_like(state))

    monkeypatch.setattr(K, "_chunk_forward", no_carry)
    jax.clear_caches()
    faulty = np.asarray(rule(*args))
    jax.clear_caches()
    want = by_positions(*args)
    assert np.max(np.abs(faulty[:, :64] - want[:, :64])) < 1e-5
    assert np.max(np.abs(faulty[:, 64:] - want[:, 64:])) \
        > 0.05 * np.max(np.abs(want))


RAGGED = (1, 100, 1, 128, 128)


@pytest.mark.parametrize("shape", [ONE_CHUNK, MANY, BATCHED, RAGGED],
                         ids=["one_chunk", "three_chunks", "batched",
                              "ragged"])
def test_the_forward_hands_over_the_state_that_entered_each_chunk(shape):
    """What the rule's forward keeps for its backward under differentiation,
    the kernel's second result and the plain form's stacked carries: the
    recurrence's state at every chunk's start (zeros at the first), the
    two forms alike; behind it each chunk's T in the inputs' type, which
    solves the chunk's triangle; and the kernel's o is what it writes
    alone."""
    (q, k, v, g, beta), _ = make(shape)
    C = K._chunk_size(shape[1], 64)
    pad = lambda x: jnp.pad(
        x, ((0, 0), (0, -shape[1] % C)) + ((0, 0),) * (x.ndim - 2))
    rows = tuple(pad(x) for x in (q, k, K._scaled(k, beta),
                                  K._scaled(v, beta), g))
    want = by_positions(q, k, v, g, beta, every=C)
    assert want.shape[2] == -(-shape[1] // C) and not want[:, :, 0].any()
    o, states, T = K._forward(*rows, C=C, states=True, interpret=True)
    assert states.dtype == jnp.float32 and states.shape == want.shape
    close(states, want, 2e-5)
    o_plain, carries, T_plain = K._plain(*rows, C)
    close(carries, want, 2e-5)
    close(states, carries, 1e-6)
    close(o, o_plain, 1e-6)
    assert T.dtype == q.dtype and T.shape == (*want.shape[:3], C, C)
    close(T, T_plain, 1e-6)
    # (I + A)(I + T) = I over the first chunk of the first head
    chunk = K._Chunk(*(rows[i][0, :C, :1].swapaxes(0, 1)
                       for i in (0, 1, 2, 4)))
    A = np.tril(np.asarray(chunk.pairs(chunk.kb_row)[0], np.float64), -1)
    eye = np.eye(C)
    close((eye + A) @ (eye + np.asarray(T[0, 0, 0], np.float64)), eye, 1e-5)
    alone, = K._forward(*rows, C=C, interpret=True)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(alone))


def test_bfloat16_operands_stay_close_to_the_recurrence():
    args, do = make(MANY, dtype=jnp.bfloat16)
    exact = value_and_grads(recurrence, args, do)
    for rule in (plain, K.kda):
        for g, w in zip(value_and_grads(rule, args, do), exact):
            close(g, w, 0.03)


def test_a_ragged_length_is_padded_with_positions_that_move_nothing():
    args, do = make(RAGGED)
    for g, w in zip(value_and_grads(K.kda, args, do),
                    value_and_grads(recurrence, args, do)):
        close(g, w, 2e-5)


@pytest.mark.parametrize("flat", [False, True], ids=["viewed", "rows"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("D", [128, 48])
def test_beta_is_spread_and_summed_by_a_product_with_the_lanes_matrix(
        D, dtype, flat):
    """`_scaled` against beta broadcast over the (B, S, H, D) view, which
    it stands for without the view: beta x in x's shape and type, dx, and
    d beta, the sum over a head's D; x handed over as (B, S, H, D) or as
    the (B, S, H D) rows the rule holds."""
    B, S, H = 2, 24, 3
    ks = jax.random.split(jax.random.PRNGKey(D), 3)
    x = jax.random.normal(ks[0], (B, S, H, D)).astype(dtype)
    beta = jax.nn.sigmoid(jax.random.normal(ks[1], (B, S, H)))
    dout = jax.random.normal(ks[2], x.shape).astype(dtype)
    handed = (lambda a: a.reshape(B, S, H * D)) if flat else (lambda a: a)

    def viewed(x, beta):
        return (x.astype(jnp.float32) * beta[..., None]).astype(x.dtype)

    got, vjp = jax.vjp(K._scaled, handed(x), beta)
    want, want_vjp = jax.vjp(viewed, x, beta)
    assert got.shape == handed(x).shape and got.dtype == dtype
    tol = 1e-6 if dtype == jnp.float32 else 2 ** -7
    close(got.reshape(x.shape), want, tol)
    (dx, dbeta), (want_dx, want_dbeta) = vjp(handed(dout)), want_vjp(dout)
    close(dx.reshape(x.shape), want_dx, tol)
    close(dbeta, want_dbeta, 1e-6)
    assert "reduce_sum" not in str(jax.make_jaxpr(
        lambda x, beta: jax.vjp(K._scaled, x, beta)[1](handed(dout)))(
            handed(x), beta))


@pytest.mark.parametrize("sizes, problem", [
    ((128, 128, 64), None), ((128, 128, 32), None), ((128, 128, 16), None),
    ((64, 128, 64), "tile"), ((128, 256, 64), "tile"),
    ((128, 128, 128), "chunk"), ((128, 128, 48), "chunk"),
])
def test_what_the_kernels_take(sizes, problem):
    said = K._kernel_problem(*sizes)
    assert (said is None) if problem is None else (problem in said)


@pytest.mark.parametrize("shape, taken", [(ONE_CHUNK, 1), (DECLINED, 0)],
                         ids=["taken", "declined"])
def test_a_call_counts_itself_and_a_declined_shape_is_the_plain_form(
        shape, taken):
    """A declined shape warns, holds no `pallas_call`, gives the plain
    form's result and gradients to the last bit and counts
    `kda.rule_plain`; a taken one two kernels, the forward (which hands the
    entering states over) and the backward, `kda.bwd_kernel`,
    `kda.kernel_passes` and, both heads a grid step in either,
    `kda.heads_per_step`."""
    args, do = make(shape)
    names = ("kda.layers", "kda.rule_kernel", "kda.rule_plain",
             "kda.bwd_kernel", "kda.kernel_passes", "kda.heads_per_step")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", K.KdaFallbackWarning)
        jax.eval_shape(K.kda, *args)
        assert [tracing.counter(n) for n in names] == [0] * 6    # no job
        with tracing.timeline_span("train.fit", root=True):
            kernels = n_kernels(
                lambda *a: value_and_grads(K.kda, a, do), *args)
            assert [tracing.counter(n) for n in names] == [
                1, taken, 1 - taken, taken, 2 * taken, 4 * taken]
        assert kernels == 2 * taken * A_PASS
        if not taken:
            with pytest.warns(K.KdaFallbackWarning, match="plain chunked"):
                got = value_and_grads(K.kda, args, do)
            for g, w in zip(got, value_and_grads(
                    lambda *a: plain(*a), args, do)):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
            for g, w in zip(got, value_and_grads(recurrence, args, do)):
                close(g, w, 5e-5)


def test_a_replayed_layer_gives_the_same_gradients():
    """Under `checkpoint_layer` with no room the backward pass makes the
    rule's inputs again and runs the forward kernel and the backward kernel:
    three kernels a layer, the replayed forward handing the backward its
    states.  Keeping o by a policy of one's own drops none of them: the
    replay still runs the kernel for the states."""
    args, do = make(ONE_CHUNK)

    def layer(*a):
        return jnp.sum(jnp.square(
            jax.ad_checkpoint.checkpoint_name(K.kda(*a), "o")) * do)

    walked = jax.jit(jax.value_and_grad(layer, range(5)))(*args)
    replay = jax.value_and_grad(layers.checkpoint_layer(layer), range(5))
    for g, w in zip(jax.tree.leaves(jax.jit(replay)(*args)),
                    jax.tree.leaves(walked)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    kept = jax.value_and_grad(jax.checkpoint(
        layer, policy=jax.checkpoint_policies.save_only_these_names("o")),
        range(5))
    assert n_kernels(jax.value_and_grad(layer, range(5)), *args) \
        == 2 * A_PASS
    assert n_kernels(replay, *args) == 3 * A_PASS
    assert n_kernels(kept, *args) == 3 * A_PASS


def test_past_the_interpreters_size_another_platform_runs_the_plain_form():
    """A shape the kernels take, too large to interpret: lowered for the
    CPU it is the plain form and counted so, for a TPU the Mosaic
    kernels."""
    args, do = make((1, 128, 8, 128, 128))
    assert not interpreted(args[0])
    f = jax.jit(lambda *a: value_and_grads(K.kda, a, do))
    with tracing.timeline_span("train.fit", root=True):
        text = f.lower(*args).as_text()
        assert tracing.counter("kda.rule_kernel") == 0
        assert tracing.counter("kda.rule_plain") == 1
        assert tracing.counter("kda.bwd_kernel") == 0
    assert "tpu_custom_call" not in text
    exported = jax.export.export(f, platforms=["tpu"])(*args)
    assert exported.mlir_module().count("tpu_custom_call") == 2

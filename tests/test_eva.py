"""`ops/eva.py`: EVA attention.  The op (the flash kernels under
`BlockRule(aligned=w)`, the remote pair and the pooling pair, interpreted
here) and the plain masked form against the definition written out over all S
keys and all S / c summaries with boolean masks, in float32: o and all five
gradients, at a sequence of several windows, with window 0 alone and at a
shape the kernels decline; one softmax over both sources; what the kernels
take; the counters; a recomputed layer's replay; and what a TPU is given."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import layers
from ray_tpu.ops import eva as E
from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops import interpreted
from ray_tpu.ops.flash_attention import BlockRule
from ray_tpu.util import tracing

NAMES = ("q", "k", "v", "phi", "mu")
# (B, S, H, D, window, chunk): a head is a lane block, as the kernels ask
TWO_WINDOWS = (1, 256, 2, 128, 128, 8)      # the smallest the kernels take
FOUR_WINDOWS = (1, 512, 1, 128, 128, 8)
WINDOW_0_ALONE = (1, 128, 2, 128, 128, 8)
# shapes the kernels decline: heads of 64; four summaries a window
DECLINED = (2, 256, 2, 64, 64, 16)
FEW_SUMMARIES = (1, 128, 1, 128, 32, 8)
A_PASS = 2      # `pallas_call`s of a traced pass: a TPU's and the interpreter's


def make(shape, seed=0, dtype=jnp.float32):
    B, S, H, D, _, _ = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(key, (B, S, H, D)).astype(dtype)
               for key in ks[:3])
    phi, mu = (0.3 * jax.random.normal(key, (H, D)) for key in ks[3:5])
    return (q, k, v, phi, mu), jax.random.normal(ks[5], (B, S, H, D))


def definition(q, k, v, phi, mu, window, chunk, apart=False):
    """o as the equations read: every key and every summary scored, A_i and
    B_i as masks.  ``apart``: the FAULT of two softmaxes averaged."""
    B, S, H, D = q.shape
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    N = S // chunk
    kc, vc = (x.reshape(B, N, chunk, H, D) for x in (k, v))
    a = jax.nn.softmax(jnp.einsum("bnchd,hd->bnch", kc, phi), axis=2)[..., None]
    ks, vs = jnp.sum(a * kc, axis=2) + mu, jnp.sum(a * vc, axis=2)
    i, j, n = jnp.arange(S)[:, None], jnp.arange(S)[None], jnp.arange(N)[None]
    own = (j <= i) & (j // window == i // window)
    earlier = (n * chunk) // window < i // window
    s1 = jnp.where(own, jnp.einsum("bqhd,bkhd->bhqk", q, k) * D ** -0.5,
                   -jnp.inf)
    s2 = jnp.where(earlier, jnp.einsum("bqhd,bnhd->bhqn", q, ks) * D ** -0.5,
                   -jnp.inf)
    if apart:
        o2 = jnp.einsum("bhqn,bnhd->bqhd", jnp.where(
            earlier, jax.nn.softmax(s2, -1), 0.0), vs)
        return 0.5 * (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s1, -1),
                                 v) + o2)
    p = jax.nn.softmax(jnp.concatenate([s1, s2], -1), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p[..., :S], v) \
        + jnp.einsum("bhqn,bnhd->bqhd", p[..., S:], vs)


def value_and_grads(fn, args, do):
    """(o, the gradients of sum(o do) to every argument)."""
    o, back = jax.vjp(lambda *a: fn(*a).astype(jnp.float32), *args)
    return o, back(do)


def op(shape):
    return lambda *a: E.eva_attention(*a, window=shape[4], chunk=shape[5])


def close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.max(np.abs(got - want)) <= tol * (1 + np.max(np.abs(want)))


@pytest.mark.parametrize("shape", [TWO_WINDOWS, FOUR_WINDOWS, WINDOW_0_ALONE,
                                   DECLINED, FEW_SUMMARIES])
def test_the_op_is_the_definition(shape):
    """o and the gradients to q, k, v, phi and mu: the kernels (interpreted)
    where the shape is taken, the plain form under a warning where not."""
    args, do = make(shape)
    taken = E._kernel_problem(args[0], *shape[4:]) is None
    assert taken == (shape not in (DECLINED, FEW_SUMMARIES))
    assert interpreted(args[0])
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", E.EvaFallbackWarning)
        got = value_and_grads(op(shape), args, do)
    want = value_and_grads(
        lambda *a: definition(*a, *shape[4:]), args, do)
    close(got[0], want[0])
    for name, g, w in zip(NAMES, got[1], want[1]):
        close(g, w), name
    if shape == WINDOW_0_ALONE:     # no summary is read: nothing reaches them
        assert not np.asarray(got[1][3]).any()
        assert not np.asarray(got[1][4]).any()
        assert np.isfinite(np.asarray(got[1][0])).all()


def test_one_softmax_spans_both_sources():
    """The halves normalised apart and averaged are another function."""
    args, _ = make(FOUR_WINDOWS)
    got = op(FOUR_WINDOWS)(*args)
    one = definition(*args, *FOUR_WINDOWS[4:])
    apart = definition(*args, *FOUR_WINDOWS[4:], apart=True)
    close(got, one)
    assert float(jnp.max(jnp.abs(got - apart))) > 0.05


def test_in_bfloat16():
    """bfloat16 operands, float32 inside: o to bfloat16's rounding."""
    args, _ = make(TWO_WINDOWS, dtype=jnp.bfloat16)
    got = op(TWO_WINDOWS)(*args)
    assert got.dtype == jnp.bfloat16
    close(got, definition(*args, *TWO_WINDOWS[4:]), tol=2e-2)


@pytest.mark.parametrize("shape", [TWO_WINDOWS, DECLINED])
def test_the_plain_forms_behind_the_kernels(shape):
    """`_plain` whole, and each kernel's own plain form (what a platform that
    is no TPU runs past the interpreter's sizes), are the definition too."""
    args, do = make(shape, seed=1)
    q, k, v, phi, mu = args
    window, chunk = shape[4:]
    want = definition(*args, window, chunk)
    close(E._plain(*args, window, chunk), want)
    if shape == DECLINED:
        return
    H = shape[2]
    ks, vs = E._pool_plain(E._flat(k), E._flat(v), phi, mu, H=H, chunk=chunk)
    kernel = E._pool_forward(E._flat(k), E._flat(v), phi, mu, H=H,
                             chunk=chunk, rows=window, interpret=True)
    close(kernel[0], ks), close(kernel[1], vs)
    o2, l2 = E._remote_plain(E._flat(q), ks, vs, H=H, window=window,
                             chunk=chunk)
    k2, kl2 = E._remote_forward(E._flat(q), ks, vs, H=H, window=window,
                                chunk=chunk, interpret=True)
    close(k2, o2)
    # window 0's rows attend nothing: weight 0 either way
    assert float(jnp.max(l2[:, :window])) < -1e29
    close(kl2[:, window:], l2[:, window:])
    delta = jax.random.normal(jax.random.PRNGKey(3), l2.shape)
    lse = l2.at[:, :window].set(0.0) + 1.0
    plain = E._remote_plain_bwd(E._flat(q), E._flat(do), lse, delta, ks, vs,
                                H=H, window=window, chunk=chunk)
    kernels = E._remote_backward(E._flat(q), E._flat(do), lse, delta, ks, vs,
                                 H=H, window=window, chunk=chunk,
                                 interpret=True)
    for g, w in zip(kernels, plain):
        close(g, w)


@pytest.mark.parametrize("shape,problem", [
    (TWO_WINDOWS, None),
    ((1, 16384, 16, 128, 2048, 16), None),          # the cell's
    (DECLINED, "wide"),
    (FEW_SUMMARIES, "tile"),
    ((1, 256, 1, 128, 96, 8), "divide"),
    ((1, 16384, 1, 128, 8192, 16), "VMEM"),
    ((1, 32768, 16, 128, 2048, 16), "no room"),     # the flash backward's
])
def test_what_the_kernels_take(shape, problem):
    q = jax.ShapeDtypeStruct(shape[:4], jnp.bfloat16)
    found = E._kernel_problem(q, *shape[4:])
    assert (found is None) if problem is None else (problem in found), found


def test_the_counters():
    """Under a job: a layer, its three forward kernels, the summaries and the
    pairs attended and visited; a declined shape counts a fallback."""
    names = ("eva.layers", "eva.kernels", "eva.fallbacks", "eva.summaries",
             "eva.pairs_attended", "eva.pairs_visited")
    args, _ = make(FOUR_WINDOWS)
    S, window, chunk = 512, 128, 8
    local = 4 * window * (window + 1) // 2
    remote = window * (window // chunk) * (0 + 1 + 2 + 3)
    assert E.attended_pairs(S, window, chunk) == (local, remote)
    jax.eval_shape(op(FOUR_WINDOWS), *args)
    assert [tracing.counter(n) for n in names] == [0] * 6        # no job
    with tracing.timeline_span("train.fit", root=True):
        jax.eval_shape(op(FOUR_WINDOWS), *args)
        # tiles of 128: a window's one tile, crossed by the diagonal
        assert [tracing.counter(n) for n in names] == [
            1, 3, 0, S // chunk, local + remote, 4 * window * window + remote]
    declined, _ = make(DECLINED)
    with tracing.timeline_span("train.fit", root=True):
        with pytest.warns(E.EvaFallbackWarning, match="plain masked form"):
            jax.eval_shape(op(DECLINED), *declined)
        assert [tracing.counter(n) for n in names[:3]] == [1, 0, 1]
    # the cell's shape: 1,024.5 + 448 pairs a query, 0.852 of those visited
    cell = E.attended_pairs(16384, 2048, 16)
    assert (cell[0] / 16384, cell[1] / 16384) == (1024.5, 448.0)
    visited = cell[1] + 512 * 512 * fa._tiles_visited(
        BlockRule(aligned=2048), 16384, 512, 512)
    assert round(100 * sum(cell) / visited, 1) == 85.2


def test_a_recomputed_layer_keeps_o_its_statistics_and_the_summaries():
    """`checkpoint_layer` keeps the merged o and lse under the flash kernels'
    names, and with `eva/summary` among the kept names the replay runs no
    kernel of the forward's three: the backward's three are all it calls."""
    args, do = make(TWO_WINDOWS)

    def kernels(names):
        layer = jax.checkpoint(
            lambda *a: op(TWO_WINDOWS)(*a),
            policy=jax.checkpoint_policies.save_only_these_names(*names))
        text = str(jax.make_jaxpr(lambda *a: value_and_grads(
            layer, a, do))(*args))
        return text.count("pallas_call")

    assert E.SUMMARY_NAME in layers.KEPT_NAMES
    # forward, replay, backward
    assert kernels(()) == A_PASS * (3 + 3 + 3)
    assert kernels(fa.KEPT_RESIDUALS) == A_PASS * (3 + 1 + 3)   # the pooling
    assert kernels(fa.KEPT_RESIDUALS + (E.SUMMARY_NAME,)) == A_PASS * (3 + 3)


def test_a_tpu_is_given_the_kernels_whatever_the_size():
    """Past the interpreter's sizes another platform lowers the plain forms;
    an export for a TPU carries the six kernels."""
    shape = (1, 512, 2, 128, 128, 8)
    args, do = make(shape)
    assert not interpreted(args[0])
    f = jax.jit(lambda *a: value_and_grads(op(shape), a, do))
    assert "tpu_custom_call" not in f.lower(*args).as_text()
    exported = jax.export.export(f, platforms=["tpu"])(*args)
    assert exported.mlir_module().count("tpu_custom_call") == 6

"""EVA attention (Zheng, Yuan, Wang, Kong: "Efficient Attention via Control
Variates", ICLR 2023, arXiv:2302.04542) in the parametrisation EvaByte's
release trains: softmax attention over TWO key sources of different length
under ONE softmax.

Positions come in chunks of ``chunk`` (c) and aligned windows of ``window``
(w, whole chunks).  A head h has two learned vectors phi_h and mu_h (D).

  the summaries, a chunk n (positions c n .. c n + c - 1):
    a_t = softmax over the chunk's c positions of (k_t . phi_h)
    ks_n = sum_t a_t k_t + mu_h          vs_n = sum_t a_t v_t
  query i, W(i) = i // w:
    A_i = {j : W(j) = W(i), j <= i}      its own window's keys up to itself
    B_i = {n : (c n) // w < W(i)}        every EARLIER window's summaries
    o_i = [sum_A e^(s q_i.k_j) v_j + sum_B e^(s q_i.ks_n) vs_n]
          / [sum_A e^(s q_i.k_j) + sum_B e^(s q_i.ks_n)],   s = D^-1/2

The paper's estimator with E the query's own window and one control variate
a chunk of the rest.  Window 0's queries have no summary: an empty source
weighs 0.

**The form taken: two forward calls merged by their row statistics, one
backward softmax.**  The local half is the flash kernels under a rule
(`ops/flash_attention.py:BlockRule(aligned=w)`: the diagonal INSIDE an
aligned window, the tiles of earlier windows never fetched), head-major as
every long call of them.  The remote half is a kernel pair of this file: S
queries on S / c summaries under a rule of WHOLE tiles (a q tile of window
W visits summary tiles 0 .. W - 1, w / c summaries each, none masked), over
(B, S, H D) rows as the projections wrote them, a head a 128-lane block.
Forward, each gives (o, lse) and the two are merged: L = logaddexp(l1, l2),
o = e^(l1 - L) o1 + e^(l2 - L) o2.  The backward differentiates the ONE
softmax: with L and delta = rowsum(do o) of the merged result, dS = P (dP -
delta) holds for both sources alike, so the flash backward kernel (given L
and delta) and the remote backward kernel each make their source's part and
dq is their sum: nothing is differentiated through the merge, and no
statistic has a cotangent.  The summaries are a kernel pair of their own
(`_pool`): one read of k and v where W_k and W_v wrote them, the gradients
to k, v, phi and mu; the plain `jax.numpy` form of each kernel stands behind
`ops.by_platform`.

Why two calls and not one kernel with two sources: the local half is 70 %
of the pairs (1,024.5 of 1,472.5 a query at S = 16,384) and is, tile for
tile, the causal call the flash kernels are tuned for; the remote half has
no mask at all.  One kernel would save the merge (three passes over o) and
one fetch of q a pass; it would be a third forward and a second backward to
keep beside the flash kernels' (PERF.md section 7, left by PR 69).

**What the shape decides** (`_kernel_problem`).  The kernels take heads of
128 lanes, chunks of whole float32 tiles (c % 8 = 0), w / c summaries a
window in whole bfloat16 tiles (% 16 = 0), windows up to 4,096 that the
flash kernels can tile (a tile divides the window).  Every other shape runs
`_plain` (the masked definition by windows, differentiated by jax) under an
`EvaFallbackWarning`, counted as `eva.fallbacks`.

Counted on the job timeline as the step is traced: `eva.layers`,
`eva.kernels` (the Mosaic kernels a layer's forward calls: 3),
`eva.fallbacks`, `eva.summaries` (a head and sequence), `eva.pairs_attended`
(sum over the queries of |A_i| + |B_i|) and `eva.pairs_visited` (what the
forward kernels multiply: the local tiles the rule visits, whole, and the
remote pairs, all of which are attended).

`jax.named_scope`s: `summary`, `local`, `remote`, `merge` (the caller stands
in `eva`).
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import by_platform, interpreted
from ray_tpu.ops import flash_attention as flash
from ray_tpu.ops.flash_attention import KEPT_RESIDUALS, BlockRule
from ray_tpu.util import tracing

_F32 = jnp.float32
_LANE, _NEG_INF, _LOG2E = flash._LANE, flash._NEG_INF, flash._LOG2E
# the summaries a recomputed layer may keep (`models/layers.py:KEPT_NAMES`)
SUMMARY_NAME = "eva/summary"
# rows of q a grid step of the remote forward takes
_REMOTE_TILE = 512
# scoped VMEM the kernels ask for: a window's q, do, dq and the two (w, 1)
# statistics (a lane a row) double-buffered, the summaries and their float32
# gradients, and a (w, w / c) tile's temporaries; 13 MB at w = 2,048
_VMEM = 48 << 20


class EvaFallbackWarning(UserWarning):
    """A shape the EVA kernels decline ran the plain masked form, on every
    platform, the TPU included."""


def _kernel_problem(q, window: int, chunk: int) -> Optional[str]:
    """Why the kernels cannot take the call, or None."""
    B, S, H, D = q.shape
    if S % window or window % chunk:
        return "the windows do not divide the sequence into whole chunks"
    if D != _LANE:
        return f"a head is {D} wide, not a block of {_LANE} lanes"
    if chunk % 8 or (window // chunk) % 16:
        return ("a chunk is no whole float32 tile of rows, or a window's "
                "summaries no whole bfloat16 tile")
    if window > 4096:
        return "a window's rows leave the remote backward no room in VMEM"
    rule = BlockRule(aligned=window)
    _, whole, fwd, bwd = flash._resolve(q, S, rule, None, None, None)
    held = 0 if whole else flash._bwd_held_bytes(S, D, D, q.dtype)
    return flash._tiling_problem(S, *fwd, 0, rule) \
        or flash._tiling_problem(S, *bwd, held, rule)


def _runs_kernels(x) -> bool:
    """Whether `by_platform` gives a call whose first operand is ``x`` the
    kernels where this process traces it: on a TPU, or interpreted."""
    return interpreted(x) or jax.default_backend() == "tpu"


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM)


def _flat(x):
    """(B, S, H, D) -> (B, S, H D): the rows as a projection wrote them."""
    return x.reshape(*x.shape[:2], -1)


def _head_block(rows, H):
    """A head's 128 lanes of ``rows`` rows of a (B, ., H D) array, grid
    (b h, i): rows block i."""
    return pl.BlockSpec((None, rows, _LANE), lambda g, i: (g // H, i, g % H))


def _stat_block(rows):
    """The same rows of a (B H, ., 1) statistic."""
    return pl.BlockSpec((None, rows, 1), lambda g, i: (g, i, 0))


# ---------------------------------------------------------------------------
# the summaries
# ---------------------------------------------------------------------------

def _pool_weights(k3, phi):
    """k3 (n, c, D) float32, phi (1, D) -> a (n, c, 1): the softmax of
    k . phi over each chunk's c positions."""
    score = jnp.sum(k3 * phi, axis=-1, keepdims=True)
    e = jnp.exp(score - jnp.max(score, axis=1, keepdims=True))
    return e / jnp.sum(e, axis=1, keepdims=True)


def _pool_kernel(k_ref, v_ref, phi_ref, mu_ref, ks_ref, vs_ref, *, chunk):
    rows, D = k_ref.shape
    k3 = k_ref[...].astype(_F32).reshape(rows // chunk, chunk, D)
    v3 = v_ref[...].astype(_F32).reshape(rows // chunk, chunk, D)
    a = _pool_weights(k3, phi_ref[...].astype(_F32))
    ks_ref[...] = (jnp.sum(a * k3, axis=1)
                   + mu_ref[...].astype(_F32)).astype(ks_ref.dtype)
    vs_ref[...] = jnp.sum(a * v3, axis=1).astype(vs_ref.dtype)


def _pool_bwd_kernel(k_ref, v_ref, phi_ref, dks_ref, dvs_ref, dk_ref, dv_ref,
                     dphi_ref, *, chunk):
    """With g_t = dks . k_t + dvs . v_t and ds_t = a_t (g_t - sum_u a_u g_u):
    dk_t = a_t dks + ds_t phi, dv_t = a_t dvs, dphi = sum_t ds_t k_t (this
    step's rows' part; the caller sums the steps')."""
    rows, D = k_ref.shape
    n = rows // chunk
    phi = phi_ref[...].astype(_F32)
    k3 = k_ref[...].astype(_F32).reshape(n, chunk, D)
    v3 = v_ref[...].astype(_F32).reshape(n, chunk, D)
    dks = dks_ref[...].astype(_F32).reshape(n, 1, D)
    dvs = dvs_ref[...].astype(_F32).reshape(n, 1, D)
    a = _pool_weights(k3, phi)
    g = jnp.sum(dks * k3 + dvs * v3, axis=-1, keepdims=True)
    ds = a * (g - jnp.sum(a * g, axis=1, keepdims=True))
    dk_ref[...] = (a * dks + ds * phi).reshape(rows, D).astype(dk_ref.dtype)
    dv_ref[...] = (a * dvs).reshape(rows, D).astype(dv_ref.dtype)
    dphi_ref[...] = jnp.sum((ds * k3).reshape(rows, D), axis=0,
                            keepdims=True)


@functools.partial(jax.jit, static_argnames=("H", "chunk", "rows",
                                             "interpret"))
def _pool_forward(k, v, phi, mu, *, H, chunk, rows, interpret=False):
    """k, v (B, S, H D), phi, mu (H, D) -> ks, vs (B, S / c, H D)."""
    B, S, _ = k.shape
    vector = pl.BlockSpec((None, 1, _LANE), lambda g, i: (g % H, 0, 0))
    out = jax.ShapeDtypeStruct((B, S // chunk, H * _LANE), k.dtype)
    return pl.pallas_call(
        functools.partial(_pool_kernel, chunk=chunk),
        grid=(B * H, S // rows),
        in_specs=[_head_block(rows, H), _head_block(rows, H), vector,
                  vector],
        out_specs=[_head_block(rows // chunk, H)] * 2,
        out_shape=[out, out], interpret=interpret,
        compiler_params=_params("parallel", "parallel"),
    )(k, v, phi[:, None], mu[:, None])


@functools.partial(jax.jit, static_argnames=("H", "chunk", "rows",
                                             "interpret"))
def _pool_backward(k, v, phi, dks, dvs, *, H, chunk, rows, interpret=False):
    """-> (dk, dv in k's shape and type, dphi in phi's, summed in float32
    over a step's rows and over the steps)."""
    B, S, _ = k.shape
    steps = S // rows
    vector = pl.BlockSpec((None, 1, _LANE), lambda g, i: (g % H, 0, 0))
    like = jax.ShapeDtypeStruct(k.shape, k.dtype)
    dk, dv, dphi = pl.pallas_call(
        functools.partial(_pool_bwd_kernel, chunk=chunk),
        grid=(B * H, steps),
        in_specs=[_head_block(rows, H), _head_block(rows, H), vector,
                  _head_block(rows // chunk, H),
                  _head_block(rows // chunk, H)],
        out_specs=[_head_block(rows, H), _head_block(rows, H),
                   pl.BlockSpec((None, None, 1, _LANE),
                                lambda g, i: (g, i, 0, 0))],
        out_shape=[like, like, jax.ShapeDtypeStruct(
            (B * H, steps, 1, _LANE), _F32)],
        interpret=interpret,
        compiler_params=_params("parallel", "parallel"),
    )(k, v, phi[:, None], dks, dvs)
    return dk, dv, jnp.sum(dphi.reshape(B, H, steps, _LANE),
                           axis=(0, 2)).astype(phi.dtype)


def _pool_plain(k, v, phi, mu, *, H, chunk):
    """`_pool_forward` in plain `jax.numpy`, float32 inside."""
    B, S, _ = k.shape
    k5, v5 = (x.astype(_F32).reshape(B, S // chunk, chunk, H, -1)
              for x in (k, v))
    a = jax.nn.softmax(jnp.einsum("bnchd,hd->bnch", k5, phi.astype(_F32)),
                       axis=2)[..., None]
    ks = jnp.sum(a * k5, axis=2) + mu.astype(_F32)
    vs = jnp.sum(a * v5, axis=2)
    return tuple(x.reshape(B, S // chunk, -1).astype(k.dtype)
                 for x in (ks, vs))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _pool(k, v, phi, mu, H, chunk, rows):
    """The chunk summaries of k and v (B, S, H D) -> ks, vs (B, S / c, H D)
    in their type."""
    return _pool_fwd(k, v, phi, mu, H, chunk, rows)[0]


def _pool_fwd(k, v, phi, mu, H, chunk, rows):
    summaries = by_platform(
        functools.partial(_pool_forward, H=H, chunk=chunk, rows=rows),
        functools.partial(_pool_plain, H=H, chunk=chunk), k, v, phi, mu)
    return summaries, (k, v, phi, mu)


def _pool_bwd(H, chunk, rows, residuals, cotangents):
    k, v, phi, mu = residuals
    dks, dvs = cotangents

    def plain(k, v, phi, dks, dvs):
        return jax.vjp(lambda k, v, phi: _pool_plain(
            k, v, phi, mu, H=H, chunk=chunk), k, v, phi)[1](
                (dks.astype(k.dtype), dvs.astype(v.dtype)))

    dk, dv, dphi = by_platform(
        functools.partial(_pool_backward, H=H, chunk=chunk, rows=rows),
        plain, k, v, phi, dks, dvs)
    dmu = jnp.sum(dks.astype(_F32).reshape(*dks.shape[:2], H, -1),
                  axis=(0, 1))
    return dk, dv, dphi, dmu.astype(mu.dtype)


_pool.defvjp(_pool_fwd, _pool_bwd)


# ---------------------------------------------------------------------------
# the remote half: S queries on the S / c summaries of earlier windows
# ---------------------------------------------------------------------------

def _remote_fwd_kernel(q_ref, ks_ref, vs_ref, o_ref, lse_ref, *, sm_scale,
                       window, per_window):
    """A q tile of window W: an online softmax over summary tiles 0 .. W - 1
    of ``per_window`` rows each, whole.  W = 0: nothing is visited, o = 0
    and lse = -1e30 / log2(e), which weighs 0 in the merge."""
    rows, D = q_ref.shape
    W = (pl.program_id(1) * rows) // window
    q = q_ref[...] * jnp.asarray(sm_scale * _LOG2E, q_ref.dtype)

    def tile(t, carry):
        acc, m_prev, l_prev = carry
        at = pl.ds(pl.multiple_of(t * per_window, per_window), per_window)
        k, v = ks_ref[at, :], vs_ref[at, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=_F32)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        p = jnp.exp2((s - m_new).astype(v.dtype))
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True,
                                         dtype=_F32)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=_F32)
        return acc, m_new, l_new

    acc, m, l = jax.lax.fori_loop(0, W, tile, (
        jnp.zeros((rows, D), _F32), jnp.full((rows, 1), _NEG_INF, _F32),
        jnp.zeros((rows, 1), _F32)))
    o_ref[...], lse_ref[...] = flash._finish_fwd(acc, m, l, o_ref.dtype)


def _remote_bwd_kernel(q_ref, do_ref, lse_ref, delta_ref, ks_ref, vs_ref,
                       dq_ref, dks_ref, dvs_ref, *, sm_scale, per_window):
    """Grid step (b h, W): window W's rows against summary tiles 0 .. W - 1:
    s and dp once a tile, dq summed over the tiles, the summaries' gradients
    summed over the windows in their float32 blocks, which stay in VMEM
    while a (b, h) slice's windows pass (that axis is `arbitrary`)."""
    W = pl.program_id(1)

    @pl.when(W == 0)
    def _():
        dks_ref[...] = jnp.zeros_like(dks_ref)
        dvs_ref[...] = jnp.zeros_like(dvs_ref)

    q = q_ref[...] * jnp.asarray(sm_scale * _LOG2E, q_ref.dtype)
    do = do_ref[...]
    lse = lse_ref[...] * _LOG2E
    delta = delta_ref[...]

    def tile(t, dq):
        at = pl.ds(pl.multiple_of(t * per_window, per_window), per_window)
        k, v = ks_ref[at, :], vs_ref[at, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=_F32)
        p = jnp.exp2((s - lse).astype(k.dtype))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=_F32)
        ds = p * (dp - delta).astype(k.dtype)
        dks_ref[at, :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=_F32) * (1.0 / _LOG2E)
        dvs_ref[at, :] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=_F32)
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=_F32)

    dq = jax.lax.fori_loop(0, W, tile, jnp.zeros(q_ref.shape, _F32))
    dq_ref[...] = (dq * sm_scale).astype(dq_ref.dtype)


_STATIC = ("H", "window", "chunk", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _remote_forward(q, ks, vs, *, H, window, chunk, interpret=False):
    """q (B, S, H D), ks, vs (B, N, H D) -> (o (B, S, H D), lse (B H, S, 1)
    float32, natural units)."""
    B, S, _ = q.shape
    N = ks.shape[1]
    rows = _REMOTE_TILE if window % _REMOTE_TILE == 0 else window
    held = pl.BlockSpec((None, N, _LANE), lambda g, i: (g // H, 0, g % H))
    return pl.pallas_call(
        functools.partial(_remote_fwd_kernel, sm_scale=_LANE ** -0.5,
                          window=window, per_window=window // chunk),
        grid=(B * H, S // rows),
        in_specs=[_head_block(rows, H), held, held],
        out_specs=[_head_block(rows, H), _stat_block(rows)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B * H, S, 1), _F32)],
        interpret=interpret,
        compiler_params=_params("parallel", "parallel"),
    )(q, ks, vs)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _remote_backward(q, do, lse, delta, ks, vs, *, H, window, chunk,
                     interpret=False):
    """lse and delta (B H, S, 1) float32, of the MERGED softmax -> (dq in
    q's shape and type, dks, dvs (B, N, H D) float32)."""
    B, S, _ = q.shape
    N = ks.shape[1]
    held = pl.BlockSpec((None, N, _LANE), lambda g, i: (g // H, 0, g % H))
    grads = jax.ShapeDtypeStruct(ks.shape, _F32)
    return pl.pallas_call(
        functools.partial(_remote_bwd_kernel, sm_scale=_LANE ** -0.5,
                          per_window=window // chunk),
        grid=(B * H, S // window),
        in_specs=[_head_block(window, H), _head_block(window, H),
                  _stat_block(window), _stat_block(window), held, held],
        out_specs=[_head_block(window, H), held, held],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), grads, grads],
        interpret=interpret,
        compiler_params=_params("parallel", "arbitrary"),
    )(q, do, lse, delta, ks, vs)


def _remote_scores(q, ks, H, window, chunk):
    """The plain form's scores, natural units, the pairs outside B_i at
    -1e30: (B, H, S, N) float32."""
    B, S, _ = q.shape
    N = ks.shape[1]
    s = jnp.einsum("bqhd,bnhd->bhqn", q.reshape(B, S, H, -1),
                   ks.reshape(B, N, H, -1),
                   preferred_element_type=_F32) * _LANE ** -0.5
    seen = (jnp.arange(N)[None] * chunk) // window \
        < jnp.arange(S)[:, None] // window
    return jnp.where(seen, s, _NEG_INF), seen


def _remote_plain(q, ks, vs, *, H, window, chunk):
    """`_remote_forward` in plain `jax.numpy`."""
    B, S, _ = q.shape
    s, seen = _remote_scores(q, ks, H, window, chunk)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(seen, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bhqn,bnhd->bqhd", p / l_safe,
                   vs.astype(_F32).reshape(B, -1, H, _LANE))
    lse = (m + jnp.log(l_safe)).reshape(B * H, S, 1)
    return o.reshape(q.shape).astype(q.dtype), lse


def _remote_plain_bwd(q, do, lse, delta, ks, vs, *, H, window, chunk):
    """`_remote_backward` in plain `jax.numpy`."""
    B, S, _ = q.shape
    heads = lambda x: x.astype(_F32).reshape(*x.shape[:2], H, -1)
    s, seen = _remote_scores(q, ks, H, window, chunk)
    p = jnp.where(seen, jnp.exp(s - lse.reshape(B, H, S, 1)), 0.0)
    dp = jnp.einsum("bqhd,bnhd->bhqn", heads(do), heads(vs))
    ds = p * (dp - delta.reshape(B, H, S, 1)) * _LANE ** -0.5
    dq = jnp.einsum("bhqn,bnhd->bqhd", ds, heads(ks))
    dks = jnp.einsum("bhqn,bqhd->bnhd", ds, heads(q))
    dvs = jnp.einsum("bhqn,bqhd->bnhd", p, heads(do))
    return (dq.reshape(q.shape).astype(q.dtype), dks.reshape(ks.shape),
            dvs.reshape(vs.shape))


# ---------------------------------------------------------------------------
# both halves under one softmax
# ---------------------------------------------------------------------------

def _tr(x):
    return x.transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _attend(q, k, v, ks, vs, window, chunk):
    """q, k, v (B, S, H, D), ks, vs (B, S / c, H D) -> o (B, S, H, D)."""
    return _attend_fwd(q, k, v, ks, vs, window, chunk)[0]


def _attend_fwd(q, k, v, ks, vs, window, chunk):
    B, S, H, D = q.shape
    with jax.named_scope("local"):
        o1, (*_, l1) = flash._flash_fwd(
            _tr(q), _tr(k), _tr(v), BlockRule(aligned=window), None, None,
            None)
    with jax.named_scope("remote"):
        o2, l2 = by_platform(
            functools.partial(_remote_forward, H=H, window=window,
                              chunk=chunk),
            functools.partial(_remote_plain, H=H, window=window, chunk=chunk),
            _flat(q), ks, vs)
    with jax.named_scope("merge"):
        l2 = l2.reshape(B, H, S)
        lse = jnp.logaddexp(l1, l2)
        w1, w2 = (_tr(jnp.exp(l - lse)[..., None]) for l in (l1, l2))
        o = (w1 * _tr(o1).astype(_F32)
             + w2 * o2.reshape(q.shape).astype(_F32)).astype(q.dtype)
    # what the backward reads besides its recomputed inputs, under the flash
    # kernels' names: a checkpointed layer that keeps them
    # (`models/layers.py:checkpoint_layer`) runs neither forward kernel again
    o = checkpoint_name(o, KEPT_RESIDUALS[0])
    lse = checkpoint_name(lse, KEPT_RESIDUALS[1])
    return o, (q, k, v, ks, vs, o, lse)


def _attend_bwd(window, chunk, residuals, do):
    q, k, v, ks, vs, o, lse = residuals
    B, S, H, D = q.shape
    with jax.named_scope("merge"):
        delta = _tr(jnp.sum(do.astype(_F32) * o.astype(_F32), axis=-1,
                            keepdims=True))[..., 0]            # (B, H, S)
    with jax.named_scope("local"):
        dq1, dk, dv = flash._flash_bwd(
            BlockRule(aligned=window), None, None, None,
            (_tr(q), _tr(k), _tr(v), None, lse), _tr(do), delta)
    with jax.named_scope("remote"):
        dq2, dks, dvs = by_platform(
            functools.partial(_remote_backward, H=H, window=window,
                              chunk=chunk),
            functools.partial(_remote_plain_bwd, H=H, window=window,
                              chunk=chunk),
            _flat(q), _flat(do), lse.reshape(B * H, S, 1),
            delta.reshape(B * H, S, 1), ks, vs)
    with jax.named_scope("merge"):
        dq = _tr(dq1) + dq2.reshape(q.shape)
    return dq, _tr(dk), _tr(dv), dks.astype(ks.dtype), dvs.astype(vs.dtype)


_attend.defvjp(_attend_fwd, _attend_bwd)


def attended_pairs(seq_len: int, window: int, chunk: int):
    """(local, remote) (query, key) pairs a sequence attends, a head: the
    triangle of each window, and each query's earlier windows' summaries."""
    windows = seq_len // window
    return (windows * window * (window + 1) // 2,
            window * (window // chunk) * windows * (windows - 1) // 2)


def _plain(q, k, v, phi, mu, window, chunk):
    """The definition, masked, a sequence's windows side by side: scores of a
    window's queries on the window's keys (w x w) and on all summaries
    (w x S / c), one softmax over both; float32 inside.  For the shapes the
    kernels decline: S need only be whole windows of whole chunks."""
    B, S, H, D = q.shape
    N, nw = S // chunk, S // window
    ks, vs = (x.astype(_F32).reshape(B, N, H, D) for x in _pool_plain(
        _flat(k).astype(_F32), _flat(v).astype(_F32), phi, mu, H=H,
        chunk=chunk))
    qw, kw, vw = (x.astype(_F32).reshape(B, nw, window, H, D)
                  for x in (q, k, v))
    scale = D ** -0.5
    local = jnp.einsum("bwqhd,bwkhd->bwhqk", qw, kw) * scale
    local = jnp.where(jnp.tril(jnp.ones((window, window), bool)), local,
                      _NEG_INF)
    remote = jnp.einsum("bwqhd,bnhd->bwhqn", qw, ks) * scale
    seen = (jnp.arange(N)[None] * chunk) // window < jnp.arange(nw)[:, None]
    remote = jnp.where(seen[None, :, None, None], remote, _NEG_INF)
    p = jax.nn.softmax(jnp.concatenate([local, remote], axis=-1), axis=-1)
    o = jnp.einsum("bwhqk,bwkhd->bwqhd", p[..., :window], vw) \
        + jnp.einsum("bwhqn,bnhd->bwqhd", p[..., window:], vs)
    return o.reshape(q.shape).astype(q.dtype)


def eva_attention(q, k, v, phi, mu, *, window: int, chunk: int):
    """q, k, v (B, S, H, D) with RoPE applied, phi, mu (H, D) -> o
    (B, S, H, D) in q's type: the attention above at D^-1/2, scores and
    softmax in float32."""
    B, S, H, D = q.shape
    tracing.count("eva.layers")
    problem = _kernel_problem(q, window, chunk)
    if problem:
        warnings.warn(
            f"EVA attention on shape {tuple(q.shape)}, windows of {window} "
            f"in chunks of {chunk}, runs the plain masked form: {problem}",
            EvaFallbackWarning, stacklevel=2)
        tracing.count("eva.fallbacks")
        return _plain(q, k, v, phi, mu, window, chunk)
    local, remote = attended_pairs(S, window, chunk)
    rule = BlockRule(aligned=window)
    _, _, (bq, bk), _ = flash._resolve(q, S, rule, None, None, None)
    tracing.count("eva.fallbacks", 0)       # the key present: a reading
    tracing.count("eva.kernels", 3 * int(_runs_kernels(_flat(k))))
    tracing.count("eva.summaries", S // chunk)
    tracing.count("eva.pairs_attended", local + remote)
    tracing.count("eva.pairs_visited", remote + bq * bk
                  * flash._tiles_visited(rule, S, bq, bk))
    with jax.named_scope("summary"):
        # a grid step a window's rows of a head: at most 4,096, whose k and
        # v in float32 are two megabytes each
        ks, vs = _pool(_flat(k), _flat(v), phi, mu, H, chunk, window)
        ks, vs = (checkpoint_name(x, SUMMARY_NAME) for x in (ks, vs))
    return _attend(q, k, v, ks, vs, window, chunk)

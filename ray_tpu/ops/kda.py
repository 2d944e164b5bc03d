"""Kimi Delta Attention's rule (Kimi Linear, arXiv:2510.26692): a gated
delta rule whose decay is a vector a head, one factor a KEY CHANNEL, chunked,
as plain `jax.numpy` and as a Pallas (Mosaic) kernel a pass, forward and
backward, under one `jax.custom_vjp`.

For one head, q_t and k_t of K channels, v_t of V, g_t of K (the log of the
decay, negative: alpha_t = exp(g_t)), beta_t a scalar, the state S (K, V)
float32, zero where the sequence starts:

    S_t = (I - beta_t k_t k_t') Diag(alpha_t) S_{t-1} + beta_t k_t v_t'
    o_t = S_t' q_t

q, k (B, S, H, K); v (B, S, H, V); g (B, S, H, K) float32; beta (B, S, H).
What a mixer does before (the projections, the convolution, the L2 norms,
the gate's map) and behind (the head's norm, the output gate) is no part of
this file.

**The chunked form.**  With u_t = beta_t (v_t - S_{t-1}' (alpha_t * k_t)) the
rule is S_t = Diag(alpha_t) S_{t-1} + k_t u_t'.  Over a chunk of C positions,
G_t the sum of g over the chunk's positions up to t, S_0 the state that
enters it and D(t, i) = exp(G_t - G_i) a vector of K:

    A_ti = beta_t sum(k_t * k_i * D(t, i)), i < t   (C, C), strictly lower
    P_ti = sum(q_t * k_i * D(t, i)), i <= t
    (I + A) U = beta * V - (beta * K * exp(G)) S_0
        so U = T (beta * V) - T (beta * K * exp(G)) S_0,  T = (I + A)^-1
    O = (Q * exp(G)) S_0 + P U
    S_C = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))' U

Never is exp(-G_i) formed over the chunk: at the gate's bound g = -5 a
position it passes float32 after 18 positions.  The pair decays are taken
against an origin inside the row's own sub-block of `_SUB` = 16 positions:
with R_a the sum of g up to the EIGHTH position of sub-block a, the row side
is exp(G_t - R_a) and the column side exp(R_a - G_i), both within exp(+-40)
for the pairs inside the sub-block and the column side at most 1 for the
columns of earlier ones (where it underflows the pair's decay is nothing in
float32 either).  An origin at the sub-block's start would do by the
exponents alone, exp(80) being float32's, but exp(-80) times a key's entry
of 1e-3 is no normal number and the chip flushes it: at the bound for a
whole chunk that cost o a part in a hundred (`tests/test_kda.py`).  The
cumulative sums are float32 products with triangles of ones, inside a
sub-block and up to it apart, so that neither rounds the other.

T: the triangle is inverted without a loop over its rows, by products
alone.  Its 16-wide diagonal blocks A_d are
nilpotent, so (I + A_d)^-1 = (I - A_d)(I + A_d^2)(I + A_d^4)(I + A_d^8); with
that D and N = D (A - A_d), which is nilpotent over the C / 16 blocks,
T = (I - N)(I + N^2) D.  Ten products of (C, C) a chunk, operands in the
inputs' type and sums in float32, the identity kept apart so that no
1 + small is ever rounded.

**The kernels.**  A chunk of one head is a chain: the sums, A, the ten
products of T one after the other, U against the carried state, o, the
state; each product fills a quarter of a 128 x 128 matrix unit and the next
waits for it.  The heads' chains are independent, so a grid step takes
several heads and runs their chains side by side: the chunk's algebra above
is written over (heads, ., .) arrays, every product batched over the heads
(`_dot`), and of two products that follow each other in the unrolled body
the second is another head's and waits for nothing.  The grid is (B,
H / heads, chunks), the chunks walked in order (`arbitrary`), a step one
chunk of its heads; `_step_heads` takes the most heads that divide H, up to
`_STEP_HEADS`, whose blocks and temporaries fit the kernels' VMEM
(`_step_bytes`), and one head a step is the same body with a batch of one.
q, k, beta k, beta v and g are read where they lie, blocks of (1, C,
heads x 128) of (B, S, H K), a head's 128 lanes cut out
of the block as one matrix of the batch (`_by_heads`); the states,
transposed (V, K) so that the decay of a key channel is a factor a lane,
live in a VMEM scratch of (heads, V, K).  beta never enters a kernel:
XLA makes beta k and beta v (`_scaled`) and takes their cotangents apart.
Between `kda`'s two ends everything is held as those (B, S, H K) rows, the
`custom_vjp`'s operands and cotangents included, and a head's beta reaches
its lanes by a product with a 0 / 1 matrix (`_whose`): a reduction or a
broadcast over a (B, S, H, K) view makes XLA re-tile the float32 rows, a
head's 128 lanes into a tile of their own, and re-tile them back.
The backward is a kernel of its own over the same grid from last to first.
Its residuals are the forward's inputs and what the forward kernel writes
beside o under differentiation: the state that ENTERED each chunk, (B, H,
chunks, V, K) float32, from the scratch it carries anyway, and each chunk's T
less the identity, (B, H, chunks, C, C) in the inputs' type, the only form a
pass reads it in (ten dependent products of the chain that the backward does
not make again: 3.8 of its 9.5 ms a layer at the ling cell's shape, PERF.md
section 6, PR 68).  It remakes the chunk's sums, P, U and W from them, carries
the state's cotangent in VMEM and writes dq, dk, d(beta k), d(beta v) and dg.
No product in it has exp(-G) over the chunk either: every cotangent of a pair
decay is taken with the same row and column factors as the forward.

**What the shape decides** (`_kernel_problem`): K = V = 128 (a head is a
lane tile) and a chunk of 16, 32 or 64.  Every other shape runs the chunked
form as plain `jax.numpy` (`_plain`: the same chunk's algebra over all H
heads at once, the batch the kernels cut into grid steps, `vmap`ped over
the batch rows, a `lax.scan` over the chunks' carry), which `jax.grad`
differentiates and which is also what any platform but a TPU runs beyond the
interpreter's sizes (`ops.by_platform`).

Counts itself on the job timeline as the step is traced: `kda.layers` (a
call), `kda.rule_kernel` / `kda.rule_plain` (a call that took the kernels /
that a shape or a platform declined), `kda.bwd_kernel` (a backward rule
traced with its kernel), `kda.kernel_passes` (passes traced with a kernel,
forward or backward: 2 a layer) and `kda.heads_per_step` (the heads a grid
step of each of those passes takes, summed: over `kda.kernel_passes`, the
heads a step).
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import by_platform, interpreted
from ray_tpu.util import tracing

_LANE = 128
# positions a pair decay's origin lies back at most: exp(5 x 16) is float32's
_SUB = 16
_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
# heads a grid step takes at most, their chains side by side: all 16 of the
# ling cell's (a layer's kernels at 1, 2, 4, 8 and 16 heads a step: forward
# 8.02, 4.43, 2.76, 2.03 and 1.83 ms, backward 6.22, 3.93, 2.77, 2.44 and
# 2.39; a second chunk a step, unrolled, which was worth 6 % at one head, is
# worth nothing from 8 heads on and went: PERF.md section 6, PR 70)
_STEP_HEADS = 16
# float32 (C, 128) arrays a chunk's body holds a head at once, as
# `_step_bytes` counts them: the least VMEM limit under which Mosaic compiles
# the backward, less its blocks, is 20 to 21 of them at 4, 8 and 16 heads
_LIVE = 24

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 << 20)


class KdaFallbackWarning(UserWarning):
    """A shape the rule's kernels do not take ran the plain chunked form,
    on every platform, the TPU included."""


# ---------------------------------------------------------------------------
# one chunk's algebra, several heads side by side: arrays of (heads, ., .),
# every product batched over the heads, so that a kernel's body and the plain
# form are the same lines
# ---------------------------------------------------------------------------

def _dot(a, b, dims):
    """a . b a head, in float32: a and b (heads, ., .), the axis dims[0] of
    a's matrices contracted with dims[1] of b's; a 2-D a is every head's.
    float32 operands are multiplied as float32."""
    if a.ndim == 2:
        a = jnp.broadcast_to(a, (b.shape[0], *a.shape))
    exact = a.dtype == _F32 and b.dtype == _F32
    return jax.lax.dot_general(
        a, b, (((dims[0] + 1,), (dims[1] + 1,)), ((0,), (0,))),
        preferred_element_type=_F32, precision=_HIGHEST if exact else None)


def _iota(C):
    rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    return rows, cols


def _doublings(n):
    """Squarings after which (I - X)(I + X^2)... holds every power of an X
    that is nilpotent at n."""
    return max(n - 1, 0).bit_length() - 1 if n > 1 else 0


def _inverse_less_identity(X, steps, mm):
    """(I + X)^-1 - I for X nilpotent at 2^(steps + 1):
    (I - X)(I + X^2)...(I + X^(2^steps)), the identity kept apart."""
    M = -X
    for _ in range(steps):
        X = mm(X, X)
        M = M + X + mm(M, X)
    return M


def _solve(A, dtype):
    """T - I for T = (I + A)^-1, A (heads, C, C) float32 strictly lower;
    products with operands in ``dtype``."""
    C = A.shape[-1]
    mm = lambda x, y: _dot(x.astype(dtype), y.astype(dtype), (1, 0))
    rows, cols = _iota(C)
    near = rows // _SUB == cols // _SUB
    A_d = jnp.where(near, A, 0.0)
    D = _inverse_less_identity(A_d, _doublings(min(_SUB, C)), mm)
    if C <= _SUB:
        return D
    A_o = A - A_d
    N = A_o + mm(D, A_o)
    M = _inverse_less_identity(N, _doublings(C // _SUB), mm)
    return D + M + mm(M, D)


class _Chunk:
    """What both passes make of a chunk's q, k, beta k and g (heads, C, K):
    the sums, the decays' factors, P and T less the identity in the inputs'
    type (``T``: the forward's, handed to the backward; None: made here from
    A)."""

    def __init__(self, q, k, kb, g, T=None):
        C = q.shape[1]
        self.C, self.dtype = C, q.dtype
        dtype = q.dtype
        rows, cols = _iota(C)
        self.rows, self.cols = rows, cols
        g = g.astype(_F32)
        near = rows // _SUB == cols // _SUB
        # the sums inside a sub-block and up to it, apart
        first = rows // _SUB * _SUB
        inside = _dot(((cols <= rows) & near).astype(_F32), g, (1, 0))
        middle = _dot(((cols <= first + _SUB // 2 - 1) & near).astype(_F32),
                      g, (1, 0))
        before = _dot((cols < first).astype(_F32), g, (1, 0))
        G = inside + before
        self.row = jnp.exp(inside - middle)         # exp(G_t - R_a(t))
        self.start = jnp.exp(G)                     # exp(G_t), from the chunk's
        self.total = G[:, C - 1:C]                  # (heads, 1, K)
        self.end = jnp.exp(self.total - G)          # exp(G_C - G_i)
        # the column side of sub-block a's rows: exp(R_a - G_i) up to the
        # sub-block's last column, 0 behind it
        row_of = jax.lax.broadcasted_iota(jnp.int32, G.shape, 1)
        self.col = []
        for a in range(C // _SUB):
            at = slice(a * _SUB, a * _SUB + 1)
            origin = before[:, at] + middle[:, at]  # (heads, 1, K): R_a
            self.col.append(jnp.exp(jnp.where(
                row_of < (a + 1) * _SUB, origin - G, -jnp.inf)))
        f32 = lambda x: x.astype(_F32)
        self.q_row = (f32(q) * self.row).astype(dtype)
        self.kb_row = (f32(kb) * self.row).astype(dtype)
        self.k_col = [(f32(k) * col).astype(dtype) for col in self.col]
        self.q_start = (f32(q) * self.start).astype(dtype)
        self.kb_start = (f32(kb) * self.start).astype(dtype)
        self.k_end = (f32(k) * self.end).astype(dtype)
        if T is None:       # A before P: the order the forward was timed in
            A = jnp.where(cols < rows, self.pairs(self.kb_row), 0.0)
        self.P = jnp.where(cols <= rows, self.pairs(self.q_row), 0.0)
        self.T = _solve(A, dtype).astype(dtype) if T is None else T

    def blocks(self):
        return [slice(a * _SUB, (a + 1) * _SUB) for a in range(self.C // _SUB)]

    def pairs(self, x_row):
        """(heads, C, C): sum(x_t * k_i * D(t, i)), sub-block of rows by
        sub-block; the entries above a sub-block's last column are 0, those
        above the diagonal inside it finite and for the caller to mask."""
        return jnp.concatenate(
            [_dot(x_row[:, rows], self.k_col[a], (1, 1))
             for a, rows in enumerate(self.blocks())], axis=1)

    def solved(self, x):
        """T x, x (heads, C, .) in the inputs' type."""
        return x.astype(_F32) + _dot(self.T, x, (1, 0))

    def solved_back(self, x):
        """T' x, x (heads, C, .) float32."""
        return x + _dot(self.T, x.astype(self.dtype), (0, 0))


def _chunk_forward(q, k, kb, vb, g, state, want_o=True):
    """One chunk of a few heads: q, k, kb = beta k (heads, C, K), vb = beta v
    (heads, C, V), g (heads, C, K) float32, ``state`` the state that enters,
    TRANSPOSED (heads, V, K) float32 -> (o (heads, C, V) float32, the state
    that leaves, the chunk's T as `_Chunk` holds it).  Nothing reads
    ``want_o``: `benchmark/tests/bailing_hybrid_faults.py` hands it on."""
    c = _Chunk(q, k, kb, g)
    dtype = c.dtype
    state_x = state.astype(dtype)
    W = c.solved(c.kb_start)                                    # (., C, K)
    U = c.solved(vb) - _dot(W.astype(dtype), state_x, (1, 1))   # (., C, V)
    U_x = U.astype(dtype)
    o = _dot(c.q_start, state_x, (1, 1)) + _dot(c.P.astype(dtype), U_x, (1, 0))
    return o, jnp.exp(c.total) * state + _dot(U_x, c.k_end, (0, 0)), c.T


def _chunk_backward(q, k, kb, vb, g, state, T, do, dstate):
    """The same chunk's cotangents: ``T`` the forward's, ``do`` (heads, C,
    V), ``dstate`` that of the state that LEFT, transposed (heads, V, K)
    float32 -> (dq, dk, dkb, dvb, dg (heads, C, .) float32, the cotangent of
    the state that entered)."""
    c = _Chunk(q, k, kb, g, T)
    dtype = c.dtype
    f32 = lambda x: x.astype(_F32)
    x = lambda v: v.astype(dtype)
    state_x, dstate_x, do_x = x(state), x(dstate), x(do)
    grown = jnp.exp(c.total)                                    # (., 1, K)
    # the forward's values again
    Ubar = c.solved(vb)
    W = c.solved(c.kb_start)
    U = Ubar - _dot(x(W), state_x, (1, 1))
    U_x = x(U)
    # back through O, the state that leaves, and U
    dU = _dot(x(c.P), do_x, (0, 0)) + _dot(c.k_end, dstate_x, (1, 1))
    dP = jnp.where(c.cols <= c.rows, _dot(do_x, U_x, (1, 1)), 0.0)
    dW = -_dot(x(dU), state_x, (1, 0))                          # (., C, K)
    dstate_in = grown * dstate + _dot(do_x, c.q_start, (0, 0)) \
        - _dot(x(dU), x(W), (0, 0))
    dvb = c.solved_back(dU)                                     # T' dU
    dkb_start = c.solved_back(dW)                               # T' dW
    dA = jnp.where(c.cols < c.rows,
                   -_dot(x(dvb), x(Ubar), (1, 1))
                   - _dot(x(dkb_start), x(W), (1, 1)), 0.0)
    # back through the pair decays: a sub-block of rows at a time, the row
    # side times exp(G_t - R_a), the column side times exp(R_a - G_i)
    dq_rows, dkb_rows = [], []
    dk_col = jnp.zeros(k.shape, _F32)
    for a, rows in enumerate(c.blocks()):
        d_pairs = x(jnp.concatenate([dP[:, rows], dA[:, rows]], axis=1))
        sides = jnp.concatenate([c.q_row[:, rows], c.kb_row[:, rows]], axis=1)
        back = _dot(d_pairs, c.k_col[a], (1, 0))                # (., 2 SUB, K)
        dq_rows.append(back[:, :_SUB])
        dkb_rows.append(back[:, _SUB:])
        dk_col = dk_col + c.col[a] * _dot(d_pairs, sides, (0, 0))
    dq = c.row * jnp.concatenate(dq_rows, axis=1) \
        + c.start * _dot(do_x, state_x, (1, 0))
    dkb = c.row * jnp.concatenate(dkb_rows, axis=1) + c.start * dkb_start
    dk_end = c.end * _dot(U_x, dstate_x, (1, 0))
    dk = dk_col + dk_end
    # d G_t, then dg_s = the sum of d G_t over t >= s
    last = jnp.sum(f32(k) * dk_end, axis=1, keepdims=True) \
        + grown * jnp.sum(dstate * state, axis=1, keepdims=True)
    dG = f32(q) * dq + f32(kb) * dkb - f32(k) * dk
    # `last` first: behind the product Mosaic takes a row spread over the
    # chunk for the product's accumulator, and cannot cut it by heads
    dg = last + _dot((c.rows <= c.cols).astype(_F32), dG, (1, 0))
    return dq, dk, dkb, dvb, dg, dstate_in


# ---------------------------------------------------------------------------
# the plain form
# ---------------------------------------------------------------------------

def _chunk_size(S, chunk):
    """The chunk a sequence of S takes: ``chunk``, or for a shorter sequence
    its length up to whole sub-blocks."""
    return min(chunk, -(-S // _SUB) * _SUB)


def _by_chunks(x, C):
    """(B, S, H, D) -> (chunks, B, H, C, D)."""
    B, S, H, D = x.shape
    return x.reshape(B, S // C, C, H, D).transpose(1, 0, 3, 2, 4)


def _plain(q, k, kb, vb, g, C):
    """The chunked form over (B, S, H, .) arrays, S a multiple of C: the
    chunk's algebra over all the heads, `vmap`ped over the batch, a
    `lax.scan` over the chunks' carry.  -> (o (B, S, H, V) in q's type, the state that entered
    each chunk, transposed: (B, H, chunks, V, K) float32, each chunk's T:
    (B, H, chunks, C, C) in q's type)."""
    B, S, H, K = q.shape
    V = vb.shape[-1]
    over = jax.vmap(_chunk_forward)

    def chunk(state, xs):
        o, after, T = over(*xs, state)
        return after, (o.astype(q.dtype), state, T)

    _, (o, *kept) = jax.lax.scan(chunk, jnp.zeros((B, H, V, K), _F32), tuple(
        _by_chunks(v, C) for v in (q, k, kb, vb, g)))
    return (o.transpose(1, 0, 3, 2, 4).reshape(B, S, H, V),
            *(x.transpose(1, 2, 0, 3, 4) for x in kept))


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _kernel_problem(K, V, C) -> Optional[str]:
    """Why the kernels do not take a rule of these sizes, or None."""
    if K != _LANE or V != _LANE:
        return "a head's keys and values are not one 128-lane tile each"
    if C not in (_SUB, 2 * _SUB, 4 * _SUB):
        return "the chunk is not 16, 32 or 64 positions"
    return None


def _step_bytes(heads, C, width):
    """The VMEM a grid step of ``heads`` heads holds, by the backward, the
    larger of the two: its sixteen blocks twice (the five inputs and do, the
    five cotangents, the two residuals; q's type is ``width`` bytes), the
    carried state and the float32 (C, 128) arrays the chunk's body keeps a
    head, `_LIVE` of them."""
    rows = heads * C * _LANE
    blocks = rows * (9 * width + 2 * 4) \
        + heads * (_LANE * _LANE * 4 + C * C * width)
    return 2 * blocks + rows * 4 * _LIVE + heads * _LANE * _LANE * 4


def _step_heads(H, C, dtype):
    """The heads a grid step takes: the most that divide H, up to
    `_STEP_HEADS`, whose step fits the kernels' VMEM."""
    width = jnp.dtype(dtype).itemsize
    return max(h for h in range(1, min(H, _STEP_HEADS) + 1)
               if H % h == 0 and (h == 1 or _step_bytes(h, C, width)
                                  <= _COMPILER_PARAMS.vmem_limit_bytes))


def _by_heads(ref):
    """A (1, C, heads x 128) block as (heads, C, 128): a head's lane tile a
    matrix of the batch."""
    return jnp.stack([ref[0, :, h * _LANE:(h + 1) * _LANE]
                      for h in range(ref.shape[2] // _LANE)])


def _to_heads(ref, x):
    """`_by_heads`' inverse: x (heads, C, 128) into the block."""
    for h in range(x.shape[0]):
        ref[0, :, h * _LANE:(h + 1) * _LANE] = x[h].astype(ref.dtype)


def _forward_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, o_ref, *rest):
    """A grid step: one chunk of a few heads, side by side; with the
    backward's residuals for results (``states``), the state that entered
    the chunk and the chunk's T into them."""
    *kept_refs, state_ref = rest

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    state = state_ref[...]
    o, after, T = _chunk_forward(
        *map(_by_heads, (q_ref, k_ref, kb_ref, vb_ref, g_ref)), state)
    for ref, kept in zip(kept_refs, (state, T)):
        ref[0, :, 0] = kept
    state_ref[...] = after
    _to_heads(o_ref, o)


def _backward_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, before_ref, t_ref,
                     do_ref, dq_ref, dk_ref, dkb_ref, dvb_ref, dg_ref,
                     dstate_ref):
    """A grid step: the chunks from last to first; `dstate_ref` carries the
    cotangent of the state that LEFT the chunk."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    *grads, dstate = _chunk_backward(
        *map(_by_heads, (q_ref, k_ref, kb_ref, vb_ref, g_ref)),
        before_ref[0, :, 0], t_ref[0, :, 0], _by_heads(do_ref),
        dstate_ref[...])
    for ref, grad in zip((dq_ref, dk_ref, dkb_ref, dvb_ref, dg_ref), grads):
        _to_heads(ref, grad)
    dstate_ref[...] = dstate


def _flat(x):
    """(B, S, H, D) -> (B, S, H D): a head's D lanes side by side, as the
    projections wrote them."""
    return x.reshape(*x.shape[:2], -1)


def _specs(B, S, H, C, dtype, chunk_of):
    """The grid (B, H / heads, chunks), the block of a (B, S, H x 128)
    operand, (1, C, heads x 128), the blocks of the backward's two residuals
    (the entering states, (B, H, chunks, 128, 128), and the chunks' T, (B, H,
    chunks, C, C): (1, heads, 1, ., .) of each) and the carried state's
    scratch, for `_step_heads` heads a step; ``chunk_of`` maps the grid's
    third index to the chunk's place in the sequence."""
    heads = _step_heads(H, C, dtype)
    kept = [pl.BlockSpec((1, heads, 1, *tile),
                         lambda b, h, c: (b, h, chunk_of(c), 0, 0))
            for tile in ((_LANE, _LANE), (C, C))]
    block = pl.BlockSpec(
        (1, C, heads * _LANE), lambda b, h, c: (b, chunk_of(c), h))
    return (B, H // heads, S // C), block, kept, [
        pltpu.VMEM((heads, _LANE, _LANE), _F32)]


@functools.partial(jax.jit, static_argnames=("C", "states", "interpret"))
def _forward(q, k, kb, vb, g, C, states=False, interpret=False):
    """-> (o (B, S, H, V) in q's type,) and with ``states``, behind it, the
    backward's residuals: the state that entered each chunk, transposed,
    (B, H, chunks, V, K) float32, and each chunk's T, (B, H, chunks, C, C)
    in q's type."""
    B, S, H, K = q.shape
    V = vb.shape[-1]
    grid, block, kept, scratch = _specs(B, S, H, C, q.dtype, lambda c: c)
    out_specs = [block]
    out_shape = [jax.ShapeDtypeStruct((B, S, H * V), q.dtype)]
    if states:
        out_specs += kept
        out_shape += [jax.ShapeDtypeStruct((B, H, S // C, V, K), _F32),
                      jax.ShapeDtypeStruct((B, H, S // C, C, C), q.dtype)]
    o, *kept = pl.pallas_call(
        _forward_kernel,
        grid=grid, in_specs=[block] * 5, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=_COMPILER_PARAMS, interpret=interpret,
    )(*(_flat(x) for x in (q, k, kb, vb, g)))
    return (o.reshape(B, S, H, V), *kept)


@functools.partial(jax.jit, static_argnames=("C", "interpret"))
def _backward(q, k, kb, vb, g, do, before, solved, C, interpret=False):
    """``before``, ``solved``: the states and the T `_forward` wrote ->
    (dq, dk, dkb, dvb, dg), each in its primal's shape and type."""
    B, S, H, K = q.shape
    last = S // C - 1
    grid, block, kept, scratch = _specs(
        B, S, H, C, q.dtype, lambda c: last - c)
    primals = (q, k, kb, vb, g)
    grads = pl.pallas_call(
        _backward_kernel,
        grid=grid, in_specs=[block] * 5 + [*kept, block],
        out_specs=[block] * 5,
        out_shape=[jax.ShapeDtypeStruct(_flat(x).shape, x.dtype)
                   for x in primals],
        scratch_shapes=scratch,
        compiler_params=_COMPILER_PARAMS, interpret=interpret,
    )(*(_flat(x) for x in primals), before, solved, _flat(do))
    return tuple(d.reshape(x.shape) for d, x in zip(grads, primals))


def _runs_kernels(q) -> bool:
    """Whether `by_platform` gives a call whose first operand is ``q`` the
    kernels where this process traces it: on a TPU, or interpreted."""
    return interpreted(q) or jax.default_backend() == "tpu"


def _count_pass(rows, H, C):
    """A pass over ``rows`` (B, S, H D) traced with its kernel:
    `kda.kernel_passes`, and under `kda.heads_per_step` the heads its grid
    step takes."""
    tracing.count("kda.kernel_passes")
    tracing.count("kda.heads_per_step", _step_heads(H, C, rows.dtype))


def _heads(arrays, H):
    """(B, S, H D) each -> (B, S, H, D) each: `_flat`'s inverse."""
    return tuple(x.reshape(*x.shape[:2], H, -1) for x in arrays)


# The rule over (B, S, H D) rows, H heads a row: its operands and their
# cotangents stay what the kernels read and write, so the sum of k's two
# cotangents (the rule's own and beta k's) is one of rows as they lie.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernels(q, k, kb, vb, g, H, C):
    return by_platform(
        lambda *a, interpret: _flat(
            _forward(*_heads(a, H), C=C, interpret=interpret)[0]),
        lambda *a: _flat(_plain(*_heads(a, H), C)[0]), q, k, kb, vb, g)


def _kernels_fwd(q, k, kb, vb, g, H, C):
    o, *kept = by_platform(
        lambda *a, interpret: _forward(
            *_heads(a, H), C=C, states=True, interpret=interpret),
        lambda *a: _plain(*_heads(a, H), C), q, k, kb, vb, g)
    return _flat(o), (q, k, kb, vb, g, *kept)


def _kernels_bwd(H, C, residuals, do):
    inputs, kept = residuals[:5], residuals[5:]
    reference = lambda *a: jax.vjp(
        lambda *v: _flat(_plain(*_heads(v, H), C)[0]), *a[:5])[1](a[5])
    if _runs_kernels(inputs[0]):
        tracing.count("kda.bwd_kernel")
        _count_pass(inputs[0], H, C)
    return by_platform(
        lambda *a, interpret: tuple(_flat(d) for d in _backward(
            *_heads(a[:6], H), *a[6:], C=C, interpret=interpret)),
        reference, *inputs, do, *kept)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def _whose(H, D):
    """(H, H D) float32 of 0 and 1: which of a row's lanes are which head's."""
    return jnp.repeat(jnp.eye(H, dtype=_F32), D, axis=1)


def _scaled(x, beta):
    """beta x, x (B, S, H, D) or the same as (B, S, H D) rows, beta
    (B, S, H) a scalar a head and position, in x's shape and type.  beta is
    spread over a head's D lanes, and d beta summed back from them, by a
    product with `_whose` over the rows as they lie: as a broadcast over the
    (B, S, H, D) view and a reduction over its last axis, XLA re-tiled the
    float32 arrays for them (a head's 128 lanes into a tile of their own,
    0.67 ms an array at the ling cell's shape: PERF.md section 6, PR 66)."""
    H = beta.shape[-1]
    spread = jnp.dot(beta.astype(_F32), _whose(H, x.size // beta.size),
                     precision=_HIGHEST)
    rows = x.reshape(spread.shape).astype(_F32)
    return (rows * spread).astype(x.dtype).reshape(x.shape)


def kda(q, k, v, g, beta, chunk=64):
    """-> o (B, S, H, V) in q's type: the rule above by chunks of ``chunk``
    positions.  g float32, at least -5 a position (`_SUB`'s reason: eight
    positions of it are exp(-40)).  S need not divide by the chunk: the tail is
    padded with positions of k = 0, beta = 0 and g = 0, which neither move
    the state nor are read."""
    B, S, H, K = q.shape
    V = v.shape[-1]
    tracing.count("kda.layers")
    C = _chunk_size(S, chunk)
    # from here on (B, S, H D) rows, as the kernels read them and as a mixer
    # has them: the reshapes at both ends undo the caller's
    q, k, v, g = _flat(q), _flat(k), _flat(v), _flat(g.astype(_F32))
    kb, vb = _scaled(k, beta), _scaled(v, beta)
    pad = -S % C
    if pad:
        q, k, kb, vb, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                           for x in (q, k, kb, vb, g))
    problem = _kernel_problem(K, V, C)
    if problem:
        warnings.warn(
            f"the delta rule of {H} heads, keys {K} and values {V} wide, "
            f"chunk {C} runs the plain chunked form: {problem}",
            KdaFallbackWarning, stacklevel=2)
        tracing.count("kda.rule_plain")
        o = _flat(_plain(*_heads((q, k, kb, vb, g), H), C)[0])
    else:
        taken = _runs_kernels(q)
        tracing.count("kda.rule_kernel" if taken else "kda.rule_plain")
        if taken:
            _count_pass(q, H, C)
        o = _kernels(q, k, kb, vb, g, H, C)
    return o.reshape(B, -1, H, V)[:, :S]

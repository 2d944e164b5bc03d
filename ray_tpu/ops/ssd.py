"""The selective state-space scan of Mamba-2, chunked (the state-space
dual of Dao and Gu, arXiv:2405.21060): a Pallas (Mosaic) kernel a pass,
forward and backward, under one `jax.custom_vjp`.

For head h of H, P channels wide, in group g = h // (H / G) of G, with a
state `h_t` of (P, N) and `h_{-1} = 0`:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,    y_t = h_t C_t + D x_t

x (b, S, H, P); dt (b, S, H), positive (after the softplus); A (H,),
negative; B and C (b, S, G, N): a group's heads share them; D (H,).

The recurrence is never run position by position.  With `a_t = dt_t A` and
a chunk of Q positions, four products a chunk and a carry over the S / Q
chunks give the same y:

  inside a chunk     Y = (L o C B') (dt x),  L_ts = exp(sum_{s<r<=t} a_r)
                     for s <= t and 0 above;
  a chunk's state    S_c = sum_s exp(sum_{s<r<=end} a_r) dt_s x_s (x) B_s;
  the carry          h_c = exp(sum_chunk a) h_{c-1} + S_c;
  earlier chunks     exp(sum_{start<=r<=t} a_r) h_{c-1} C_t.

The decays, their cumulative sums and the carried state are float32; the
products take their operands in x's type and accumulate in float32.

**The kernels.**  A grid of (b, G, steps), the steps walked in order
(`arbitrary`), a step a few chunks one after the other (`_step_chunks`: the
step's fixed cost is shared out, the Python loop unrolled), reads and writes
the arrays where they lie: a block of x and y as (b, S, H P) with the
group's R = H / G heads side by side on R P lanes, a block of B and C as
(b, S, G N); only dt (4 bytes a head and position) is re-laid, to rows
(b, chunks, H, Q) whose (R, Q) block is one lane-dense tile.  In VMEM and
nowhere else: the chunk's cumulative sum (a product with a triangle of
ones, exact to float32 in one pass: `_ones_dot`), C B', for each head L and
the scores, and dt, exp(cum) and exp(total - cum) laid over each head's P
lanes.  Those three are made as ROWS (a vreg each for eight heads),
transposed once with the sum, and spread over the lanes by one product with
a matrix of 0 and 1 (`_spread_matrix`; three bfloat16 parts a value, so
exact to float32): the v5e's vector unit, which has no bfloat16 and is what
bounds these kernels, would pay two lane broadcasts and a select a quantity
and tile for it, and the MXU is all but idle (forward 1.26 -> 1.02 ms a
layer: PERF.md §6, PR 39).  The group's states (N, R P) float32 live in a
scratch that is zeroed at step 0 and move on by
`exp(total) state + B' (dt x exp(total - cum))`, elementwise float32 but for
that one product.  Heads narrower than the 128 lanes are run by the tile
(a pair of 64-wide heads): each head's product is made over the whole tile
and its lanes selected, which costs the MXU what a half-wide product does
and shifts nothing.  float32 operands are multiplied as float32
(`Precision.HIGHEST`), bfloat16 ones as they are.

The backward is a kernel of its own over the same grid, the steps and a
step's chunks from last to first.  Its residuals are the forward's INPUTS
only (not y, not L, not the states): a first pass makes the state that
entered each chunk again (the forward kernel without the y products,
`_forward(..., states=True)`: (b, chunks, G, N, R P) float32, alive only
inside this backward), the second carries the state's cotangent in VMEM
and writes dx, dB, dC, d dt and, as rows XLA sums, what dA and dD are made
of.  The row sums that d cum needs are taken as in a flash backward:
sum_s dS_ts S_ts = dy_t . y_t, a product over a head's P lanes instead of Q
scores, each of the very values the forward multiplied, so that what
cancels between the sums over rows and over columns cancels.  So with the
scan's y kept by name (`models/layers.py:KEPT_NAMES`, "ssm/scan") a
recomputed layer's replay holds no scan kernel at all, and without it the
forward kernel and nothing else.

**What the shape decides.**  The kernels take the calls whose group fills
whole 128-lane tiles (R P a multiple of 128 up to 1,024, P a divisor or a
multiple of 128), whose state does (N a multiple of 128), whose chunk is a
multiple of 8 and whose R is a multiple of 8 or all the heads
(`_kernel_problem`): the published Mamba-2 and Nemotron-H shapes.  Every
other shape runs the batched `einsum`s of `_ssd_einsum`, the form of PR 38,
and says so (`SsdFallbackWarning`).  That form stays for them, as the
reference the kernels are timed and tested beside, and as what any platform
but a TPU runs beyond the interpreter's sizes (`ops.by_platform`): XLA passes
its (Q, Q) decays and scores through HBM, 2 GiB a layer and pass.

What a Mamba-2 mixer does with y behind the scan, the gate by z and the
groups' norm, is no part of these kernels: `ops/gated_norm.py` has a kernel
a pass of its own over the same (b S, H P) rows, where a scan block's R P
lanes are one norm group.

Counts itself on the job timeline as the step is traced: `ssm.layers` (one
a call), `ssm.kernel_layers` (one a call that took the kernels),
`ssm.heads`, `ssm.state`, `ssm.chunk` (the sizes, not summed).
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
# the widest group (R P lanes) a grid step holds: its (Q, R P) and (N, R P)
# float32 temporaries are a quarter of a megabyte each at 512
_GROUP_LANES_MAX = 1024
_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=48 << 20)


class SsdFallbackWarning(UserWarning):
    """A shape the scan kernels do not take ran the `einsum` form — on
    every platform, the TPU included."""


def _carry(states, total):
    """states (b, c, H, P, N) f32, each chunk's own; total (b, c, H): the
    sum of a over the chunk -> the state BEFORE each chunk, same shape, as
    one product: the sum over the chunks z < c of
    exp(sum of `total` over z < r < c) S_z."""
    c = states.shape[1]
    upto = jnp.cumsum(total, axis=1)                        # (b, c, H)
    # before chunk i: the decays of chunks z+1 .. i-1
    span = (upto - total)[:, :, None] - upto[:, None, :]    # (b, i, z, H)
    earlier = jnp.arange(c)[:, None] > jnp.arange(c)[None]
    decay = jnp.exp(jnp.where(earlier[None, :, :, None], span, -jnp.inf))
    return jnp.einsum("bizh,bzhpn->bihpn", decay, states,
                      precision=_HIGHEST)                   # float32 it stays


def _ssd_einsum(x, dt, A, B, C, D, Q):
    """The chunked form as batched `einsum`s over (chunks, Q), S a multiple
    of Q: what XLA makes of it passes every (Q, Q) array through HBM, and
    `jax.grad` differentiates it.  The carry is the chunks-by-chunks decay
    product (`_carry`)."""
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    R = H // G
    c = S // Q
    f32 = jnp.float32
    # (b, c, Q, ...): a group's heads side by side, (G, R)
    xs = x.reshape(b, c, Q, G, R, P)
    Bs, Cs = B.reshape(b, c, Q, G, N), C.reshape(b, c, Q, G, N)
    dts = dt.astype(f32).reshape(b, c, Q, H)
    cum = jnp.cumsum(dts * A.astype(f32), axis=2)           # (b, c, Q, H)
    total = cum[:, :, -1]                                   # (b, c, H)
    dtx = (xs.astype(f32) * dts.reshape(b, c, Q, G, R, 1)).astype(x.dtype)

    # inside a chunk
    cb = jnp.einsum("bcqgn,bcsgn->bcgqs", Cs, Bs,
                    preferred_element_type=f32)             # (b, c, G, Q, Q)
    at = jnp.moveaxis(cum, 2, 3)                            # (b, c, H, Q)
    seen = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None]
    L = jnp.exp(jnp.where(seen, at[..., :, None] - at[..., None, :],
                          -jnp.inf))                        # (b, c, H, Q, Q)
    scores = (cb[:, :, :, None] * L.reshape(b, c, G, R, Q, Q)).astype(x.dtype)
    y = jnp.einsum("bcgrqs,bcsgrp->bcqgrp", scores, dtx,
                   preferred_element_type=f32)

    # a chunk's own state, the carry, and what earlier chunks add
    to_end = jnp.exp(total[:, :, None] - cum).reshape(b, c, Q, G, R, 1)
    states = jnp.einsum("bcsgn,bcsgrp->bcgrpn", Bs,
                        (dtx.astype(f32) * to_end).astype(x.dtype),
                        preferred_element_type=f32)
    before = _carry(states.reshape(b, c, H, P, N), total)
    from_start = jnp.exp(cum).reshape(b, c, Q, G, R, 1)
    y = y + from_start * jnp.einsum(
        "bcqgn,bcgrpn->bcqgrp", Cs,
        before.reshape(b, c, G, R, P, N).astype(x.dtype),
        preferred_element_type=f32)

    y = y + D.astype(f32).reshape(G, R, 1) * xs.astype(f32)
    return y.astype(x.dtype).reshape(b, S, H, P)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _kernel_problem(H, P, G, N, Q) -> Optional[str]:
    """Why the kernels do not take a scan of these sizes, or None."""
    R = H // G
    if (R * P) % _LANE or (_LANE % P and P % _LANE):
        return "a group's heads do not fill whole 128-lane tiles"
    if R * P > _GROUP_LANES_MAX:
        return f"a group is wider than {_GROUP_LANES_MAX} lanes"
    if N % _LANE:
        return "the state is no multiple of 128 lanes"
    if Q % 8:
        return "the chunk is no multiple of 8"
    if R % 8 and G != 1:
        return "a group's heads are no multiple of 8 rows"
    return None


def _dot(a, b, dims):
    """a . b in float32, contracting a's axis dims[0] with b's dims[1];
    float32 operands are multiplied as float32 (the MXU's default would
    round them to bfloat16 first)."""
    exact = a.dtype == _F32 and b.dtype == _F32
    return jax.lax.dot_general(
        a, b, (((dims[0],), (dims[1],)), ((), ())),
        preferred_element_type=_F32, precision=_HIGHEST if exact else None)


def _triangle(Q, upto):
    """(Q, Q) float32 ones where row <= column (``upto``) or row >= column."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return (rows <= cols if upto else rows >= cols).astype(_F32)


def _parts(a):
    """a float32 -> its three bfloat16 parts, as float32: they sum to a."""
    part = lambda v: v.astype(jnp.bfloat16).astype(_F32)
    hi = part(a)
    mid = part(a - hi)
    return hi, mid, part(a - hi - mid)


def _ones_dot(a, ones):
    """a (R, Q) float32 . ones (Q, Q) of 0 and 1, exact to float32 in ONE
    pass of the MXU: a's three bfloat16 parts go in stacked on the rows
    (`Precision.HIGHEST` would split the ones as well: six passes, each
    paying for the (Q, Q) weights)."""
    R = a.shape[0]
    out = _dot(jnp.concatenate(_parts(a), axis=0).astype(jnp.bfloat16),
               ones.astype(jnp.bfloat16), (1, 0))
    return out[:R] + out[R:2 * R] + out[2 * R:]


def _spread_rows(R):
    """Rows of the array `_chunk_sums` transposes: three quantities in three
    parts a head, then the cumulative sum, up to whole lanes."""
    return -(-10 * R // _LANE) * _LANE


def _spread_matrix(R, P):
    """(rows, 3 R P) of 0 and 1, bfloat16: row (quantity, part, head) is 1
    on the P lanes of its head in its quantity's R P; the rows behind the
    parts are 0.  Multiplied from the left by the parts as columns it lays
    each head's dt, exp(cum) and exp(total - cum) over that head's lanes,
    exact to float32: the MXU does what 2 R lane broadcasts and selects a
    quantity would (PERF.md §6, PR 39)."""
    row = jnp.arange(_spread_rows(R))[:, None]
    lane = jnp.arange(3 * R * P)[None, :]
    return ((row < 9 * R) & (row // (3 * R) == lane // (R * P))
            & (row % R == lane // P % R)).astype(jnp.bfloat16)


def _chunk_sums(dt_ref, A_ref, spread_ref, j):
    """Of the step's j-th chunk -> the cumulative sum of a = dt A as rows
    (R, Q) and as columns (Q, R), and (Q, 3 R P) float32: dt, exp(cum) and
    exp(total - cum), each head's on its P lanes.  Everything a head and
    position is made as rows, a vreg each, and transposed once."""
    dt_row = dt_ref[0, j]                                   # (R, Q)
    R, Q = dt_row.shape
    cum_row = _ones_dot(dt_row * A_ref[...], _triangle(Q, True))
    start = jnp.exp(cum_row)
    end = jnp.exp(cum_row[:, Q - 1:Q] - cum_row)
    stack = [*_parts(dt_row), *_parts(start), *_parts(end), cum_row]
    unused = _spread_rows(R) - 10 * R
    if unused:
        stack.append(jnp.zeros((unused, Q), _F32))
    cols = jnp.concatenate(stack, axis=0).T                 # (Q, rows)
    wide = _dot(cols.astype(jnp.bfloat16), spread_ref[...], (1, 0))
    return cum_row, cols[:, 9 * R:10 * R], wide


class _Tile:
    """One 128-lane tile (or one head of a multiple of 128) of a group's
    (Q, R P) block: its lanes and the heads on them."""

    def __init__(self, j, P):
        self.width = max(P, _LANE)
        self.lanes = slice(j * self.width, (j + 1) * self.width)
        per = self.width // P
        self.heads = range(j * per, (j + 1) * per)
        self.P = P
        self._lane_head = {}

    def lane_head(self, rows):
        """(rows, width) int32: which of the tile's heads a lane is."""
        if rows not in self._lane_head:
            self._lane_head[rows] = jax.lax.broadcasted_iota(
                jnp.int32, (rows, self.width), 1) // self.P
        return self._lane_head[rows]

    def only(self, k, value):
        """``value`` (rows, width) with every head's lanes but the k-th of
        this tile zeroed."""
        if len(self.heads) == 1:
            return value
        return jnp.where(self.lane_head(value.shape[0]) == k, value,
                         jnp.zeros_like(value))


def _decays(seen, cum_col, cum_row, h):
    """L of head h, (Q, Q) float32: exp(cum_t - cum_s) where s <= t."""
    return jnp.exp(jnp.where(
        seen, cum_col[:, h:h + 1] - cum_row[h:h + 1, :], -jnp.inf))


def _chunk_rows(j, Q):
    """The rows of a step's j-th chunk in its (k Q, ...) blocks."""
    if isinstance(j, int):
        return slice(j * Q, (j + 1) * Q)
    return pl.ds(pl.multiple_of(j * Q, Q), Q)


def _over_chunks(chunks, chunk, carry):
    """``carry = chunk(j, carry)`` for j = 0 .. chunks - 1: one trace of
    the chunk's work, unrolled at lowering (`_STEP_CHUNKS`)."""
    if chunks == 1:
        return chunk(0, carry)
    return jax.lax.fori_loop(0, chunks, chunk, carry, unroll=True)


def _forward_kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, D_ref, spread_ref,
                    out_ref, state_ref, *, Q, R, P, states):
    """A grid step: one group of a few chunks, one after the other
    (`_over_chunks`).  ``states``: write the state that entered each chunk
    and no y (the backward's first pass)."""
    dtype = x_ref.dtype
    RP = R * P

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    seen = _triangle(Q, False) > 0

    def chunk(j, state):
        rows = _chunk_rows(j, Q)
        cum_row, cum_col, wide = _chunk_sums(dt_ref, A_ref, spread_ref, j)
        Bc, Cc = B_ref[0, rows], C_ref[0, rows]             # (Q, N)
        if states:
            out_ref[0, j, 0] = state
        else:
            cb = _dot(Cc, Bc, (1, 1))                       # (Q, Q)
            earlier = _dot(Cc, state.astype(dtype), (1, 0))  # (Q, R P)
        to_end = []
        for t in range(RP // max(P, _LANE)):
            tile = _Tile(t, P)
            at = lambda q: slice(q * RP + tile.lanes.start,
                                 q * RP + tile.lanes.stop)
            xt = x_ref[0, rows, tile.lanes].astype(_F32)
            dtx = (xt * wide[:, at(0)]).astype(dtype)
            if not states:
                y = None
                for k, h in enumerate(tile.heads):
                    scores = (cb * _decays(seen, cum_col, cum_row, h)
                              ).astype(dtype)
                    own = _dot(scores, dtx, (1, 0))         # (Q, width)
                    y = own if y is None else jnp.where(
                        tile.lane_head(Q) >= k, own, y)
                y = y + wide[:, at(1)] * earlier[:, tile.lanes] \
                    + D_ref[:, tile.lanes] * xt
                out_ref[0, rows, tile.lanes] = y.astype(dtype)
            to_end.append((dtx.astype(_F32) * wide[:, at(2)]).astype(dtype))
        # exp(total) is exp(cum) at the chunk's last position
        return wide[Q - 1:Q, RP:2 * RP] * state + _dot(
            Bc, jnp.concatenate(to_end, axis=1), (0, 0))    # (N, R P)

    state_ref[...] = _over_chunks(x_ref.shape[1] // Q, chunk,
                                  state_ref[...])           # (N, R P) f32


def _backward_kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, D_ref, spread_ref,
                     before_ref, dy_ref, dx_ref, dB_ref, dC_ref, ddt_ref,
                     da_ref, dD_ref, dstate_ref, *, Q, R, P):
    """A grid step: one group of a few chunks, the steps and the chunks of
    a step from last to first; `dstate_ref` carries the cotangent of the
    state that LEFT the chunk."""
    dtype = x_ref.dtype
    RP = R * P

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    seen = _triangle(Q, False) > 0
    last = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
    width = -(-2 * R // _LANE) * _LANE
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (Q, width), 1)
    chunks = x_ref.shape[1] // Q

    def chunk(i, dstate):
        j = chunks - 1 - i
        rows = _chunk_rows(j, Q)
        cum_row, cum_col, wide = _chunk_sums(dt_ref, A_ref, spread_ref, j)
        grown = wide[Q - 1:Q, RP:2 * RP]                    # exp(total)
        Bc, Cc = B_ref[0, rows], C_ref[0, rows]
        before = before_ref[0, j, 0]                        # (N, R P) f32
        before_x, dstate_x = before.astype(dtype), dstate.astype(dtype)
        cb = _dot(Cc, Bc, (1, 1))
        earlier = _dot(Cc, before_x, (1, 0))                # (Q, R P)
        dto_end_all = _dot(Bc, dstate_x, (1, 0))            # (Q, R P)
        # <dstate, before> down the state's N rows: a head's lanes of it,
        # times exp(total), is what the carried state gives d total
        held = jnp.sum(dstate * before, axis=0, keepdims=True)  # (1, R P)

        out_cols = jnp.zeros((Q, width), _F32)  # d cum's R lanes, d dt's R
        dcb = jnp.zeros((Q, Q), _F32)
        dearlier, to_end = [], []
        for t in range(RP // max(P, _LANE)):
            tile = _Tile(t, P)
            at = lambda q: slice(q * RP + tile.lanes.start,
                                 q * RP + tile.lanes.stop)
            xt = x_ref[0, rows, tile.lanes].astype(_F32)
            dyt = dy_ref[0, rows, tile.lanes].astype(_F32)
            dts, start, end = (wide[:, at(q)] for q in range(3))
            dtx = (xt * dts).astype(dtype)
            to_end.append((dtx.astype(_F32) * end).astype(dtype))
            dearlier.append((start * dyt).astype(dtype))
            dto_end = dto_end_all[:, tile.lanes]
            ddtx = end * dto_end                            # (Q, width)
            own = None                              # the chunk's own y
            for k, h in enumerate(tile.heads):
                L = _decays(seen, cum_col, cum_row, h)
                scores = (cb * L).astype(dtype)
                dy_own = tile.only(k, dy_ref[0, rows, tile.lanes])
                dcb = dcb + _dot(dy_own, dtx, (1, 1)) * L
                ddtx = ddtx + _dot(scores, dy_own, (0, 0))
                y = _dot(scores, dtx, (1, 0))
                own = y if own is None else jnp.where(
                    tile.lane_head(Q) >= k, y, own)
            core = start * earlier[:, tile.lanes] + own     # y but for D x
            dx_ref[0, rows, tile.lanes] = (
                dts * ddtx + D_ref[:, tile.lanes] * dyt).astype(dtype)
            dD_ref[0, j, :, tile.lanes] = jnp.sum(xt * dyt, axis=0,
                                                  keepdims=True)
            # a head's sums over its lanes: d cum_t = dy_t . core_t - dtx_t
            # . ddtx_t, and at the chunk's last position d total.  Each
            # product is of the very values the forward multiplied (dtx as
            # rounded to x's type), so that what cancels between the sums
            # cancels.
            by_cum = dyt * core - dtx.astype(_F32) * ddtx
            ended = jnp.sum(dtx.astype(_F32) * end * dto_end, axis=0,
                            keepdims=True) \
                + grown[:, tile.lanes] * held[:, tile.lanes]
            by_x = xt * ddtx
            for k, h in enumerate(tile.heads):
                lanes = lambda v: jnp.sum(tile.only(k, v), axis=1,
                                          keepdims=True)
                ddt_own = lanes(by_x)
                dcum = lanes(by_cum) + jnp.where(last, lanes(ended), 0.0)
                out_cols = jnp.where(out_lane == h, dcum, out_cols)
                out_cols = jnp.where(out_lane == R + h, ddt_own, out_cols)

        dearlier, to_end = (jnp.concatenate(v, axis=1)
                            for v in (dearlier, to_end))
        dcb = dcb.astype(dtype)
        dC_ref[0, rows] = (_dot(dcb, Bc, (1, 0))
                           + _dot(dearlier, before_x, (1, 1))
                           ).astype(dC_ref.dtype)
        dB_ref[0, rows] = (_dot(dcb, Cc, (0, 0))
                           + _dot(to_end, dstate_x, (1, 1))
                           ).astype(dB_ref.dtype)
        sums = out_cols.T                                   # (width, Q)
        da_row = _ones_dot(sums[:R], _triangle(Q, False))
        da_ref[0, j] = da_row
        ddt_ref[0, j] = A_ref[...] * da_row + sums[R:2 * R]
        return grown * dstate + _dot(Cc, dearlier, (0, 0))

    dstate_ref[...] = _over_chunks(chunks, chunk, dstate_ref[...])


def _rows(dt, Q):
    """dt (b, S, H) -> (b, chunks, H, Q) float32: a chunk's positions on
    the lanes, the one operand the kernels do not read where it lies."""
    b, S, H = dt.shape
    return jnp.swapaxes(dt.astype(_F32).reshape(b, S // Q, Q, H), 2, 3)


# chunks a grid step takes, at most.  The loop over them is unrolled where
# the kernel is lowered: what of a chunk does not wait for the carried state
# (its sums, decays, scores, the spreading product) then overlaps the chunk
# before it, forward 1.29 -> 1.09 ms a layer, and the kernel is still TRACED
# as one chunk's (the same four chunks unrolled in Python ran alike and cost
# a run 10 s of set-up; eight run 3 % faster and lower twice as long:
# PERF.md §6, PR 39)
_STEP_CHUNKS = 4


def _step_chunks(chunks, Q):
    """The chunks a grid step takes: the largest count up to
    `_STEP_CHUNKS` that divides the sequence's; one where a chunk is no
    whole tile of bfloat16 rows (the loop slices the blocks by chunks)."""
    if Q % 16:
        return 1
    return max(k for k in range(1, _STEP_CHUNKS + 1) if chunks % k == 0)


def _specs(b, S, H, P, G, N, Q, k, step_of):
    """The grid and the input blocks x, dt rows, A, B, C, D and the
    spreading matrix of both kernels, k chunks a step; ``step_of`` maps the
    grid's third index to the step's place in the sequence."""
    R = H // G
    at = lambda i, g, c: (i, step_of(c), g)
    return (b, G, S // (k * Q)), [
        pl.BlockSpec((1, k * Q, R * P), at),
        pl.BlockSpec((1, k, R, Q), lambda i, g, c: (i, step_of(c), g, 0)),
        pl.BlockSpec((R, 1), lambda i, g, c: (g, 0)),
        pl.BlockSpec((1, k * Q, N), at),
        pl.BlockSpec((1, k * Q, N), at),
        pl.BlockSpec((1, R * P), lambda i, g, c: (0, g)),
        pl.BlockSpec((_spread_rows(R), 3 * R * P), lambda i, g, c: (0, 0)),
    ]


def _operands(x, dt, A, B, C, D, Q):
    """What `_specs` cuts into blocks: x, dt's rows, A, B, C, D spread over
    its head's lanes, the spreading matrix."""
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    return (x.reshape(b, S, H * P), _rows(dt, Q),
            A.astype(_F32).reshape(H, 1), B.reshape(b, S, G * N),
            C.reshape(b, S, G * N),
            jnp.repeat(D.astype(_F32), P).reshape(1, H * P),
            _spread_matrix(H // G, P))


@functools.partial(jax.jit, static_argnames=("Q", "states", "interpret"))
def _forward(x, dt, A, B, C, D, Q, states=False, interpret=False):
    """-> y (b, S, H, P) in x's type; or, ``states``, the state that
    entered each chunk, (b, chunks, G, N, R P) float32.  Jitted, as
    `_backward` is: the primal, the forward rule and every layer of one
    shape then share one trace of the kernel, and a step's module one
    lowering of it for its sixteen calls (the nemotron step lowered in 2.6
    s for 5.2: PERF.md §6, PR 39)."""
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    R, k = H // G, _step_chunks(S // Q, Q)
    grid, in_specs = _specs(b, S, H, P, G, N, Q, k, lambda c: c)
    if states:
        shape = jax.ShapeDtypeStruct((b, S // Q, G, N, R * P), _F32)
        spec = pl.BlockSpec((1, k, 1, N, R * P),
                            lambda i, g, c: (i, c, g, 0, 0))
    else:
        shape = jax.ShapeDtypeStruct((b, S, H * P), x.dtype)
        spec = pl.BlockSpec((1, k * Q, R * P), lambda i, g, c: (i, c, g))
    out = pl.pallas_call(
        functools.partial(_forward_kernel, Q=Q, R=R, P=P, states=states),
        grid=grid, in_specs=in_specs, out_specs=spec, out_shape=shape,
        scratch_shapes=[pltpu.VMEM((N, R * P), _F32)],
        compiler_params=_COMPILER_PARAMS, interpret=interpret,
    )(*_operands(x, dt, A, B, C, D, Q))
    return out if states else out.reshape(b, S, H, P)


@functools.partial(jax.jit, static_argnames=("Q", "interpret"))
def _backward(x, dt, A, B, C, D, dy, Q, interpret=False):
    """-> (dx, d dt, dA, dB, dC, dD), each in its primal's shape and
    type."""
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    R, c = H // G, S // Q
    k = _step_chunks(c, Q)
    before = _forward(x, dt, A, B, C, D, Q=Q, states=True,
                      interpret=interpret)
    operands = _operands(x, dt, A, B, C, D, Q)
    back = lambda step: c // k - 1 - step
    grid, in_specs = _specs(b, S, H, P, G, N, Q, k, back)
    at = lambda i, g, step: (i, back(step), g)
    rows = pl.BlockSpec((1, k, R, Q), lambda i, g, step: (i, back(step), g, 0))
    dx, dB, dC, ddt, da, dD = pl.pallas_call(
        functools.partial(_backward_kernel, Q=Q, R=R, P=P),
        grid=grid,
        in_specs=in_specs + [
            pl.BlockSpec((1, k, 1, N, R * P),
                         lambda i, g, step: (i, back(step), g, 0, 0)),
            pl.BlockSpec((1, k * Q, R * P), at)],
        out_specs=[
            pl.BlockSpec((1, k * Q, R * P), at),
            pl.BlockSpec((1, k * Q, N), at),
            pl.BlockSpec((1, k * Q, N), at),
            rows, rows,
            pl.BlockSpec((1, k, 1, R * P),
                         lambda i, g, step: (i, back(step), 0, g))],
        out_shape=[
            jax.ShapeDtypeStruct((b, S, H * P), x.dtype),
            jax.ShapeDtypeStruct((b, S, G * N), B.dtype),
            jax.ShapeDtypeStruct((b, S, G * N), C.dtype),
            jax.ShapeDtypeStruct((b, c, H, Q), _F32),
            jax.ShapeDtypeStruct((b, c, H, Q), _F32),
            jax.ShapeDtypeStruct((b, c, 1, H * P), _F32)],
        scratch_shapes=[pltpu.VMEM((N, R * P), _F32)],
        compiler_params=_COMPILER_PARAMS, interpret=interpret,
    )(*operands, before, dy.reshape(b, S, H * P))
    # the rows XLA sums: dA = sum dt da, dD = sum x dy, a head's
    dA = jnp.sum(operands[1] * da, axis=(0, 1, 3))
    dD = jnp.sum(dD.reshape(b * c, H, P), axis=(0, 2))
    return (dx.reshape(x.shape),
            jnp.swapaxes(ddt, 2, 3).reshape(b, S, H).astype(dt.dtype),
            dA.astype(A.dtype), dB.reshape(B.shape), dC.reshape(C.shape),
            dD.astype(D.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd_kernels(x, dt, A, B, C, D, Q):
    return _ssd_kernels_fwd(x, dt, A, B, C, D, Q)[0]


def _ssd_kernels_fwd(x, dt, A, B, C, D, Q):
    y = by_platform(
        lambda *a, interpret: _forward(*a, Q=Q, interpret=interpret),
        lambda *a: _ssd_einsum(*a, Q), x, dt, A, B, C, D)
    return y, (x, dt, A, B, C, D)


def _ssd_kernels_bwd(Q, inputs, dy):
    def reference(*a):
        *inputs, dy = a
        return jax.vjp(lambda *v: _ssd_einsum(*v, Q), *inputs)[1](dy)

    return by_platform(
        lambda *a, interpret: _backward(*a, Q=Q, interpret=interpret),
        reference, *inputs, dy)


_ssd_kernels.defvjp(_ssd_kernels_fwd, _ssd_kernels_bwd)


def _set(name, value):
    tracing.count(name, value - tracing.counter(name))


def ssd_scan(x, dt, A, B, C, D, chunk):
    """-> y (b, S, H, P) in x's type: the recurrence above by chunks of
    ``chunk`` positions.  S need not divide by it: the tail is padded with
    positions whose dt is 0, which neither move the state nor are read."""
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    tracing.count("ssm.layers")
    _set("ssm.heads", H)
    _set("ssm.state", N)
    _set("ssm.chunk", chunk)
    Q = min(chunk, S)
    pad = -S % Q
    if pad:
        x, dt, B, C = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, B, C))
    problem = _kernel_problem(H, P, G, N, Q)
    if problem:
        warnings.warn(
            f"the state-space scan of {H} heads of {P} in {G} groups, state "
            f"{N}, chunk {Q} runs the einsum form: {problem}",
            SsdFallbackWarning, stacklevel=2)
        y = _ssd_einsum(x, dt, A, B, C, D, Q)
    else:
        if interpreted(x) or jax.default_backend() == "tpu":
            tracing.count("ssm.kernel_layers")
        y = _ssd_kernels(x, dt, A, B, C, D, Q)
    return y[:, :S]

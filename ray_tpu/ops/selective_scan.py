"""The selective state-space scan of Mamba-1 (Gu and Dao, arXiv:2312.00752):
a Pallas (Mosaic) kernel a pass, forward and backward, under one
`jax.custom_vjp`.

For channel c of C and state index n of N, with `s_{-1} = 0`:

    s_t[c, n] = exp(dt_t[c] A[c, n]) s_{t-1}[c, n] + dt_t[c] u_t[c] B_t[n]
    y_t[c]    = sum_n s_t[c, n] C_t[n] + D[c] u_t[c]

u (b, S, C); dt (b, S, C), positive (after the softplus); A (C, N),
negative; B and C (b, S, N): every channel shares them; D (C,).  The decay
differs for every channel AND state index, so nothing of it is a matrix
product (`ops/ssd.py`'s Mamba-2 has ONE decay a head, which is what lets it
run by chunks on the MXU): the recurrence is run position by position with
the whole state in VMEM, every operation the vector unit's, in float32.

**The layout.**  The C channels are laid over the 8 sublanes and L = C / 8
lanes of the register tile, channel c at sublane c // L and lane c % L: u,
dt and y go in and out as (b, S, 8, L), a free reshape of (b, S, C), and a
position's channels are a whole (8, lanes) tile that is read by its number.
The N state indices are a loop written out: a state index is a tile of the
scratch, its B_t[n] and C_t[n] two scalars read from SMEM (B and C go in as
(b, S N) float32 rows) and multiplied in as scalars.  So no value is
broadcast along sublanes or lanes and the sum over n is N multiply-adds of
whole tiles; with the state index on the sublanes instead, every position
pays two lane broadcasts (B_t, C_t) and a sublane reduction (y_t).  A the
kernels read as (N, 8, L), D as (8, L).

**Forward.**  A grid of (b, L / lanes, S / T), the T-position blocks of a
sequence in order (`arbitrary`); the (N, 8, lanes) float32 state lives in a
scratch that is zeroed at a sequence's first block.  A position: 2 N tile
multiplies and N exponentials for the decays, 2 N multiply-adds for the
state and N for y.  The forward rule also writes the state that ENTERS each
block, (b, S / T, N, 8, L) float32: the backward's only residual besides
the inputs (21 MB a layer a 256 positions' block at 5,120 channels; 84 MB at
the 64 the backward's scratch holds).

**Backward.**  A kernel over the same grid, the blocks from last to first.
A block first runs the recurrence forward again from the state that entered
it and keeps its T states in a scratch; then it walks the positions from the
block's end with the state's cotangent ds in a second scratch (zeroed at a
sequence's last block):

    ds_t  = C_t (x) dy_t + a_{t+1} ds_{t+1},        a_t = exp(dt_t A)
    dC_t[n] = sum_c dy_t[c] s_t[c, n],    dB_t[n] = sum_c ds_t[c, n] dt_t u_t
    g_t   = ds_t s_{t-1} a_t     (the cotangent of dt_t A)
    d dt_t = sum_n g_t A + (sum_n B_t ds_t) u_t,   d u_t = (..) dt_t + D dy_t
    dA = sum_t g_t dt_t,                   dD = sum_t dy_t u_t

dB and dC are sums over ALL channels a position and state index; the kernel
adds a block's lane tiles and its 8 sublanes and writes the 128 lanes'
partial sums, (b, L / lanes, S, N, 128) float32, which XLA adds up; dA and
dD ride in blocks that stay in VMEM while a sequence's blocks pass and are
summed over the batch by XLA.

**What the shape decides** (`_blocks`).  The kernels take a call whose
channels fill whole tiles (C a multiple of 1,024), whose sequence divides
into blocks of at least 8 positions whose T N scalars of B_t fill whole
128-word rows, and whose state is at most `_STATE_MAX` indices (the loop
over them is written out).  Any other runs `_reference`, a
plain `lax.scan` over the positions that jax differentiates, which is also
what the kernels are tested beside and what a platform that is no TPU runs
beyond the interpreter's sizes (`ops.by_platform`).

Counts itself on the job timeline as the step is traced: `sscan.kernels`
(the calls the kernels make), `sscan.fallbacks` (the calls `_reference`
makes where they run: a shape declined, or no TPU and no interpreter) and
`sscan.positions` (b x S a call).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import by_platform, interpreted
from ray_tpu.util import tracing

_LANE = 128
_SUBLANES = 8
_F32 = jnp.float32
# the state indices a kernel's loop writes out
_STATE_MAX = 32
# a grid step's lanes at most, and its positions: the largest of
# `_TIME_BLOCKS` that divides the sequence and whose states the backward's
# scratch holds within `_STATES_BYTES`
_LANES_MAX = 640
_TIME_BLOCKS = (64, 32, 16, 8)
_STATES_BYTES = 24 << 20
# positions of the kernels' loops written out a turn
_UNROLL = 2

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 << 20)


def _reference(u, dt, A, B, C, D):
    """The rule as a `lax.scan` over the positions in float32 -> y in u's
    type; jax differentiates it."""
    f = lambda x: jnp.moveaxis(x.astype(_F32), 1, 0)
    A, D = A.astype(_F32), D.astype(_F32)

    def position(s, x):
        u_t, dt_t, B_t, C_t = x                     # (b, C) x 2, (b, N) x 2
        a = jnp.exp(dt_t[..., None] * A)
        s = a * s + (dt_t * u_t)[..., None] * B_t[:, None, :]
        return s, jnp.sum(s * C_t[:, None, :], axis=-1) + D * u_t

    s0 = jnp.zeros((u.shape[0],) + A.shape, _F32)
    _, y = jax.lax.scan(position, s0, (f(u), f(dt), f(B), f(C)))
    return jnp.moveaxis(y, 0, 1).astype(u.dtype)


def _blocks(S: int, C: int, N: int) -> Optional[Tuple[int, int]]:
    """-> (positions of a block, lanes of a block), or None for a shape the
    kernels decline."""
    if C % (_SUBLANES * _LANE) or not 0 < N <= _STATE_MAX:
        return None
    L = C // _SUBLANES
    lanes = max(w for w in range(_LANE, min(L, _LANES_MAX) + 1, _LANE)
                if L % w == 0)
    held = lambda t: (t + 1) * N * _SUBLANES * lanes * 4
    # a block's T N scalars of B and of C are whole 128-word rows of SMEM
    T = next((t for t in _TIME_BLOCKS
              if S % t == 0 and (t * N % _LANE == 0 or t == S)
              and held(t) <= _STATES_BYTES), None)
    return None if T is None else (T, lanes)


def _over(T, position, carry):
    """`fori_loop` over a block's T positions, `_UNROLL` of them written out
    a turn."""
    def turn(i, carry):
        for j in range(_UNROLL):
            carry = position(i * _UNROLL + j, carry)
        return carry

    return jax.lax.fori_loop(0, T // _UNROLL, turn, carry)


def _forward_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref,
                    *rest, N, T, entering):
    if entering:
        enter_ref, s_ref = rest
    else:
        (s_ref,) = rest

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    if entering:
        enter_ref[0, 0] = s_ref[...]
    d = d_ref[...]

    def position(t, _):
        u = u_ref[0, t].astype(_F32)
        dt = dt_ref[0, t].astype(_F32)
        du = dt * u
        y = d * u
        for n in range(N):
            s = jnp.exp(dt * a_ref[n]) * s_ref[n] + b_ref[0, t * N + n] * du
            s_ref[n] = s
            y = y + c_ref[0, t * N + n] * s
        y_ref[0, t] = y.astype(y_ref.dtype)
        return 0

    _over(T, position, 0)


def _backward_kernel(u_ref, dt_ref, dy_ref, a_ref, b_ref, c_ref, d_ref,
                     enter_ref, du_ref, ddt_ref, db_ref, dc_ref, da_ref,
                     dd_ref, states_ref, ds_ref, *, N, T):
    # the grid's last axis counts a sequence's blocks from its end
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    d = d_ref[...]
    lanes = d.shape[-1]
    states_ref[0] = enter_ref[0, 0]

    def again(t, _):
        # the block's states once more: states_ref[t + 1] is s_t
        dt = dt_ref[0, t].astype(_F32)
        du = dt * u_ref[0, t].astype(_F32)
        for n in range(N):
            states_ref[t + 1, n] = jnp.exp(dt * a_ref[n]) \
                * states_ref[t, n] + b_ref[0, t * N + n] * du
        return 0

    _over(T, again, 0)

    def folded(x):
        """(8, lanes) -> (1, 128): the lane tiles added, then the sublanes."""
        x = sum(x[:, i:i + _LANE] for i in range(0, lanes, _LANE))
        return jnp.sum(x, axis=0, keepdims=True)

    def position(i, dd):
        t = T - 1 - i
        u = u_ref[0, t].astype(_F32)
        dt = dt_ref[0, t].astype(_F32)
        dy = dy_ref[0, t].astype(_F32)
        du = dt * u
        ddu = jnp.zeros_like(u)
        ddt = jnp.zeros_like(u)
        dbs, dcs = [], []
        for n in range(N):
            an = a_ref[n]
            a = jnp.exp(dt * an)
            ds = ds_ref[n] + c_ref[0, t * N + n] * dy
            dcs.append(folded(dy * states_ref[t + 1, n]))
            dbs.append(folded(ds * du))
            ddu = ddu + b_ref[0, t * N + n] * ds
            ads = a * ds
            g = ads * states_ref[t, n]
            ddt = ddt + g * an
            da_ref[0, n] += g * dt
            ds_ref[n] = ads
        db_ref[0, 0, t] = jnp.concatenate(dbs, axis=0)
        dc_ref[0, 0, t] = jnp.concatenate(dcs, axis=0)
        du_ref[0, t] = (ddu * dt + d * dy).astype(du_ref.dtype)
        ddt_ref[0, t] = (ddt + ddu * u).astype(ddt_ref.dtype)
        return dd + dy * u

    dd_ref[0] += _over(T, position, jnp.zeros_like(d))


def _tiled(x):
    """(b, S, C) -> (b, S, 8, C / 8): channel c at sublane c // L."""
    return x.reshape(*x.shape[:2], _SUBLANES, -1)


def _operands(A, B, C, D):
    """A (C, N) -> (N, 8, L), B and C (b, S, N) -> (b, S N) rows of scalars,
    D (C,) -> (8, L), all float32."""
    N = A.shape[1]
    return (A.astype(_F32).T.reshape(N, _SUBLANES, -1),
            B.astype(_F32).reshape(B.shape[0], -1),
            C.astype(_F32).reshape(C.shape[0], -1),
            D.astype(_F32).reshape(_SUBLANES, -1))


@functools.partial(jax.jit, static_argnames=("entering", "interpret"))
def _forward(u, dt, A, B, C, D, *, entering=False, interpret=False):
    """-> y (b, S, C) in u's type; with ``entering`` also the state that
    enters each block, (b, S / T, N, 8, L) float32."""
    b, S, Cn = u.shape
    N = A.shape[1]
    T, lanes = _blocks(S, Cn, N)
    L = Cn // _SUBLANES
    a, bm, cm, d = _operands(A, B, C, D)
    tile = pl.BlockSpec((1, T, _SUBLANES, lanes), lambda n, c, i: (n, i, 0, c))
    scalars = pl.BlockSpec((1, T * N), lambda n, c, i: (n, i),
                           memory_space=pltpu.SMEM)
    out_specs = [tile]
    out_shape = [jax.ShapeDtypeStruct((b, S, _SUBLANES, L), u.dtype)]
    if entering:
        out_specs.append(pl.BlockSpec((1, 1, N, _SUBLANES, lanes),
                                      lambda n, c, i: (n, i, 0, 0, c)))
        out_shape.append(jax.ShapeDtypeStruct(
            (b, S // T, N, _SUBLANES, L), _F32))
    outs = pl.pallas_call(
        functools.partial(_forward_kernel, N=N, T=T, entering=entering),
        grid=(b, L // lanes, S // T),
        in_specs=[
            tile, tile,
            pl.BlockSpec((N, _SUBLANES, lanes), lambda n, c, i: (0, 0, c)),
            scalars, scalars,
            pl.BlockSpec((_SUBLANES, lanes), lambda n, c, i: (0, c))],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((N, _SUBLANES, lanes), _F32)],
        compiler_params=_COMPILER_PARAMS, interpret=interpret,
    )(_tiled(u), _tiled(dt), a, bm, cm, d)
    y = outs[0].reshape(b, S, Cn)
    return (y, outs[1]) if entering else y


@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward(u, dt, A, B, C, D, enter, dy, *, interpret=False):
    """-> (du, d dt, dA, dB, dC, dD), each in its primal's shape and type."""
    b, S, Cn = u.shape
    N = A.shape[1]
    T, lanes = _blocks(S, Cn, N)
    L = Cn // _SUBLANES
    blocks, tiles = S // T, L // lanes
    a, bm, cm, d = _operands(A, B, C, D)
    at = lambda i: blocks - 1 - i
    tile = pl.BlockSpec((1, T, _SUBLANES, lanes),
                        lambda n, c, i: (n, at(i), 0, c))
    scalars = pl.BlockSpec((1, T * N), lambda n, c, i: (n, at(i)),
                           memory_space=pltpu.SMEM)
    sums = pl.BlockSpec((1, 1, T, N, _LANE),
                        lambda n, c, i: (n, c, at(i), 0, 0))
    du, ddt, db, dc, da, dd = pl.pallas_call(
        functools.partial(_backward_kernel, N=N, T=T),
        grid=(b, tiles, blocks),
        in_specs=[
            tile, tile, tile,
            pl.BlockSpec((N, _SUBLANES, lanes), lambda n, c, i: (0, 0, c)),
            scalars, scalars,
            pl.BlockSpec((_SUBLANES, lanes), lambda n, c, i: (0, c)),
            pl.BlockSpec((1, 1, N, _SUBLANES, lanes),
                         lambda n, c, i: (n, at(i), 0, 0, c))],
        out_specs=[
            tile, tile, sums, sums,
            pl.BlockSpec((1, N, _SUBLANES, lanes),
                         lambda n, c, i: (n, 0, 0, c)),
            pl.BlockSpec((1, _SUBLANES, lanes), lambda n, c, i: (n, 0, c))],
        out_shape=[
            jax.ShapeDtypeStruct((b, S, _SUBLANES, L), u.dtype),
            jax.ShapeDtypeStruct((b, S, _SUBLANES, L), dt.dtype),
            jax.ShapeDtypeStruct((b, tiles, S, N, _LANE), _F32),
            jax.ShapeDtypeStruct((b, tiles, S, N, _LANE), _F32),
            jax.ShapeDtypeStruct((b, N, _SUBLANES, L), _F32),
            jax.ShapeDtypeStruct((b, _SUBLANES, L), _F32)],
        scratch_shapes=[pltpu.VMEM((T + 1, N, _SUBLANES, lanes), _F32),
                        pltpu.VMEM((N, _SUBLANES, lanes), _F32)],
        compiler_params=_COMPILER_PARAMS, interpret=interpret,
    )(_tiled(u), _tiled(dt), _tiled(dy), a, bm, cm, d, enter)
    return (du.reshape(b, S, Cn), ddt.reshape(b, S, Cn),
            jnp.sum(da, axis=0).reshape(N, Cn).T.astype(A.dtype),
            jnp.sum(db, axis=(1, 4)).astype(B.dtype),
            jnp.sum(dc, axis=(1, 4)).astype(C.dtype),
            jnp.sum(dd, axis=0).reshape(Cn).astype(D.dtype))


@jax.custom_vjp
def _kernels(u, dt, A, B, C, D):
    return by_platform(_forward, _reference, u, dt, A, B, C, D)


def _kernels_fwd(u, dt, A, B, C, D):
    T, _ = _blocks(u.shape[1], *A.shape)

    def reference(*inputs):
        # what its backward does not read, in the kernels' shape
        return _reference(*inputs), jnp.zeros(
            (u.shape[0], u.shape[1] // T, A.shape[1], _SUBLANES,
             A.shape[0] // _SUBLANES), _F32)

    y, enter = by_platform(functools.partial(_forward, entering=True),
                           reference, u, dt, A, B, C, D)
    return y, (u, dt, A, B, C, D, enter)


def _kernels_bwd(residuals, dy):
    def reference(*a):
        *inputs, _, dy = a
        return jax.vjp(_reference, *inputs)[1](dy)

    return by_platform(_backward, reference, *residuals, dy)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def selective_scan(u, dt, A, B, C, D):
    """-> y (b, S, C) in u's type: the recurrence above, by the kernels
    where the shape lets them (`_blocks`) and by `_reference` elsewhere."""
    b, S, Cn = u.shape
    if dt.shape != u.shape or A.shape[0] != Cn or D.shape != (Cn,) \
            or B.shape != (b, S, A.shape[1]) or C.shape != B.shape:
        raise ValueError(
            f"selective_scan: u {u.shape}, dt {dt.shape}, A {A.shape}, "
            f"B {B.shape}, C {C.shape}, D {D.shape} do not fit")
    taken = _blocks(S, *A.shape) is not None
    # the kernels make the call where it runs: on a TPU, or at a size
    # another platform interprets
    made = taken and (interpreted(u) or jax.default_backend() == "tpu")
    tracing.count("sscan.kernels", int(made))
    tracing.count("sscan.fallbacks", int(not made))
    tracing.count("sscan.positions", b * S)
    return (_kernels if taken else _reference)(u, dt, A, B, C, D)

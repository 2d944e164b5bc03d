"""The selective state-space scan of Mamba-2, chunked (the state-space
dual of Dao and Gu, arXiv:2405.21060), forward; jax differentiates it.

For head h of H, P channels wide, in group g = h // (H / G) of G, with a
state `h_t` of (P, N) and `h_{-1} = 0`:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,    y_t = h_t C_t + D x_t

x (b, S, H, P); dt (b, S, H), positive (after the softplus); A (H,),
negative; B and C (b, S, G, N): a group's heads share them; D (H,).

The recurrence is never run position by position.  With `a_t = dt_t A` and
a chunk of Q positions, four batched products a chunk and a carry over the
S / Q chunks give the same y:

  inside a chunk     Y = (L o C B') (dt x),  L_ts = exp(sum_{s<r<=t} a_r)
                     for s <= t and 0 above;
  a chunk's state    S_c = sum_s exp(sum_{s<r<=end} a_r) dt_s x_s (x) B_s;
  the carry          h_c = exp(sum_chunk a) h_{c-1} + S_c;
  earlier chunks     exp(sum_{start<=r<=t} a_r) h_{c-1} C_t.

The decays, their cumulative sums and the carried state are float32; the
four products take their operands in x's type and accumulate in float32.
Everything stays in the (b, S, ...) layout, as batched `einsum`s over
(chunks, Q).  The carry is the chunks-by-chunks decay product (`_carry`),
which the chip ran faster than a `lax.scan` over the chunks (64 steps of
small operations: `tools/chip_kernels.py --cases ssd_8k` has both forms;
PERF.md §6, PR 38).

Counts itself on the job timeline as the step is traced: `ssm.layers` (one
a call), `ssm.heads`, `ssm.state`, `ssm.chunk` (the sizes, not summed).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.util import tracing


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
                      precision=jax.lax.Precision.HIGHEST)  # float32 it stays



def _set(name, value):
    tracing.count(name, value - tracing.counter(name))


def ssd_scan(x, dt, A, B, C, D, chunk):
    """-> y (b, S, H, P) in x's type: the recurrence above by chunks of
    ``chunk`` positions.  S need not divide by it: the tail is padded with
    positions whose dt is 0, which neither move the state nor are read."""
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    R = H // G
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
    c = (S + pad) // Q
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
    return y.astype(x.dtype).reshape(b, c * Q, H, P)[:, :S]

"""Ring attention: sequence/context parallelism over the ICI ring.

Greenfield capability vs the reference (verified absent there — SURVEY.md
§2.6: no ring-attention/Ulysses/sequence-parallel anywhere in `python/` or
`rllib/`).  Design:

  * ``ring_attention`` — inside-shard_map attention where each device holds
    a sequence chunk of Q/K/V; K/V chunks rotate around the ``sp`` mesh
    axis via ``lax.ppermute``.  The WHOLE fwd+bwd is a hand-written
    ``jax.custom_vjp`` ring (Liu et al.'s algorithm), with each per-step
    chunk-vs-chunk attention going through the SAME Pallas flash kernels
    as single-device attention (`ray_tpu/ops/flash_attention.py`):

      - per ring step the kernel returns (o_i, lse_i) partials; a running
        max-lse merge combines them, so the (Sq, S_total) score matrix
        never exists anywhere;
      - the K/V ppermute for step i+1 is issued BEFORE step i's kernel in
        program order, letting XLA's async collective scheduler overlap the
        ICI hop with the flash compute (double buffering);
      - causal steps that are fully masked (the visiting K/V chunk lies
        entirely in the future) skip the kernel via ``lax.cond`` — only
        the diagonal step pays the causal-mask path, earlier chunks run
        the cheaper non-causal body, later chunks cost nothing;
      - backward rotates (k, v, dk_acc, dv_acc) together: each device adds
        its dk/dv contribution (recomputed tile-by-tile from the GLOBAL
        logsumexp saved in fwd) while it hosts a chunk, and after a full
        cycle the accumulators arrive back at the chunk's owner.  dq
        accumulates locally.

  * ``ulysses_attention`` — all-to-all alternative: reshard seq→heads, run
    the local flash kernel on full sequences of a head subset, reshard
    back.

Load balancing note: with contiguous chunks, causal skipping saves energy
but not lockstep wall-clock (at ring step i the first i devices idle at the
next collective).  The zigzag chunk layout (device d holding chunks d and
2n-1-d) equalizes work; it changes the model-side sequence sharding, so it
is left to the model layer — the ring itself is layout-agnostic.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.flash_attention import (
    _flash_bwd,
    _flash_fwd,
    flash_attention,
)

_NEG_INF = -1e30


def _chunk_fwd(q, k, v, scale, causal_step):
    """One chunk-vs-chunk attention partial: (o normalized, lse natural-log).

    causal_step: True only on the diagonal ring step (q and k chunks hold
    the same absolute positions); earlier chunks attend fully unmasked.
    Routes through the flash kernel/reference gate of _flash_fwd."""
    o, (_, _, _, _, lse) = _flash_fwd(q, k, v, causal_step, scale, None, None)
    return o.astype(jnp.float32), lse


def _chunk_bwd(q, k, v, o, lse, do, scale, causal_step, delta):
    """dq/dk/dv of one chunk-vs-chunk step given the GLOBAL lse/o for the
    q chunk (globally-normalized probabilities, per the ring algorithm).
    delta = rowsum(do*o) is q-side-only and loop-invariant — computed once
    in _ring_bwd and threaded through all n chunk steps."""
    return _flash_bwd(causal_step, scale, None, None, (q, k, v, o, lse), do,
                      delta=delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ring_attention(q, k, v, axis_name: str, causal: bool = True,
                   sm_scale: Optional[float] = None):
    """Attention over sequence-sharded q/k/v — call INSIDE shard_map.

    Shapes per device: (batch, heads, seq_chunk, head_dim)."""
    o, _ = _ring_fwd(q, k, v, axis_name, causal, sm_scale)
    return o


def _ring_fwd(q, k, v, axis_name, causal, sm_scale):
    B, H, Sq, D = q.shape
    scale = sm_scale if sm_scale is not None else D ** -0.5
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(i, carry):
        num, m, den, kc, vc = carry
        src = (my - i) % n
        # issue the NEXT chunk's permute before this step's compute: the
        # kernel below doesn't depend on it, so XLA overlaps the ICI hop
        # with the flash kernel (double buffering).
        kn = lax.ppermute(kc, axis_name, perm)
        vn = lax.ppermute(vc, axis_name, perm)

        def compute(_):
            return _chunk_fwd(q, kc, vc, scale, causal_step=False)

        def compute_diag(_):
            return _chunk_fwd(q, kc, vc, scale, causal_step=True)

        def skip(_):
            return (jnp.zeros((B, H, Sq, D), jnp.float32),
                    jnp.full((B, H, Sq), _NEG_INF, jnp.float32))

        if causal:
            # src > my: chunk entirely in the future -> no contribution;
            # src == my: diagonal -> causal mask; src < my: full unmasked.
            o_i, lse_i = lax.cond(
                src > my, skip,
                lambda _: lax.cond(src == my, compute_diag, compute, _),
                operand=None)
        else:
            o_i, lse_i = compute(None)

        lse_col = lse_i[..., None]                    # (B, H, Sq, 1)
        m_new = jnp.maximum(m, lse_col)
        m_safe = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
        alpha = jnp.where(m <= _NEG_INF / 2, 0.0, jnp.exp(m - m_safe))
        w = jnp.where(lse_col <= _NEG_INF / 2, 0.0,
                      jnp.exp(lse_col - m_safe))
        num = num * alpha + o_i * w
        den = den * alpha + w
        return num, m_new, den, kn, vn

    init = (
        jnp.zeros((B, H, Sq, D), jnp.float32),
        jnp.full((B, H, Sq, 1), _NEG_INF, jnp.float32),
        jnp.zeros((B, H, Sq, 1), jnp.float32),
    )
    num, m, den, _, _ = lax.fori_loop(0, n, step, init + (k, v))
    den_safe = jnp.where(den == 0.0, 1.0, den)
    o = (num / den_safe).astype(q.dtype)
    # global lse for the bwd recompute: log(sum_i exp(lse_i)) = m + log(den)
    lse = (m + jnp.log(den_safe))[..., 0]
    return o, (q, k, v, o, lse)


def _ring_bwd(axis_name, causal, sm_scale, res, do):
    q, k, v, o, lse = res
    B, H, Sq, D = q.shape
    scale = sm_scale if sm_scale is not None else D ** -0.5
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    def step(i, carry):
        dq_acc, kc, vc, dk_acc, dv_acc = carry
        src = (my - i) % n
        # prefetch the next K/V chunk before the kernels (overlap, as in
        # fwd).  The dk/dv accumulators must receive THIS step's
        # contribution first, so their permute stays after the add — its
        # consumer is at the end of the NEXT iteration's body, which still
        # lets XLA overlap it with that iteration's kernels.
        kn = lax.ppermute(kc, axis_name, perm)
        vn = lax.ppermute(vc, axis_name, perm)

        def compute(causal_step):
            def run(_):
                return _chunk_bwd(q, kc, vc, o, lse, do, scale, causal_step,
                                  delta)
            return run

        def skip(_):
            return (jnp.zeros_like(q), jnp.zeros_like(kc),
                    jnp.zeros_like(vc))

        if causal:
            dq_i, dk_i, dv_i = lax.cond(
                src > my, skip,
                lambda _: lax.cond(src == my, compute(True), compute(False),
                                   _),
                operand=None)
        else:
            dq_i, dk_i, dv_i = compute(False)(None)

        dq_acc = dq_acc + dq_i.astype(dq_acc.dtype)
        # contributions join the accumulators that ARRIVED with (kc, vc),
        # then travel onward with them — after the full cycle each chunk's
        # accumulated dk/dv lands back on its owner.
        dk_acc = lax.ppermute(dk_acc + dk_i.astype(dk_acc.dtype),
                              axis_name, perm)
        dv_acc = lax.ppermute(dv_acc + dv_i.astype(dv_acc.dtype),
                              axis_name, perm)
        return dq_acc, kn, vn, dk_acc, dv_acc

    init = (jnp.zeros(q.shape, jnp.float32), k, v,
            jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32))
    dq_acc, _, _, dk_acc, dv_acc = lax.fori_loop(0, n, step, init)
    return (dq_acc.astype(q.dtype), dk_acc.astype(k.dtype),
            dv_acc.astype(v.dtype))


ring_attention.defvjp(_ring_fwd, _ring_bwd)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = True,
                      sm_scale: Optional[float] = None):
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism — call
    inside shard_map.  Per device in: (B, H, S/n, D); internally reshards to
    (B, H/n, S, D), runs dense flash attention, and reshards back."""
    B, H, Sq, D = q.shape
    n = lax.psum(1, axis_name)
    if H % n:
        raise ValueError(f"num heads {H} must divide by sp axis size {n}")

    def to_heads(x):
        # (B, H, S/n, D) -> (B, H/n, S, D)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def to_seq(x):
        # (B, H/n, S, D) -> (B, H, S/n, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    oh = flash_attention(qh, kh, vh, causal, sm_scale)
    return to_seq(oh)

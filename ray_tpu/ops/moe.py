"""Dropless routing of tokens to experts.

A routed mixture multiplies each token by the few experts its router
chose.  Here no token is ever dropped and no expert has a capacity: the
T*k (token, expert) rows are sorted by expert, so that each expert's rows
are one contiguous group of whatever size the router made it, the experts
run over the ragged groups (`jax.lax.ragged_dot`, which XLA:TPU lowers to
a grouped-matmul kernel), and the rows go back to their tokens and are
summed with their weights.  Shared by `models/olmoe.py` (SiLU-gated
experts) and `models/gpt2.py`'s mixture (GELU experts).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.util import tracing


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """x[perm] for a permutation whose inverse is known: the cotangent is
    a gather by the inverse, where autodiff of a gather would scatter-add
    (slow on the chip, and needless: no row is taken twice)."""
    return x[perm]


_permute_rows.defvjp(
    lambda x, perm, inverse: (x[perm], inverse),
    lambda inverse, g: (g[inverse], None, None))


def moe_dispatch(x, weights, experts, n_experts, run_experts, held=None):
    """x (T, E); weights, experts (T, k): each token's k experts, of ALL
    ``n_experts``, and what each one's output is multiplied by.
    `run_experts(rows, group_sizes)` gets the T*k rows in expert order
    (R, E) with the rows of each expert it runs and returns their outputs
    (R, E), row for row.  Whatever the imbalance, every row is computed.
    Returns (y (T, E), rows sent to each of all the experts (n_experts,)
    int32).  Differentiable in x, weights and whatever `run_experts`
    closes over.

    ``held`` = (first, count): only that contiguous range of the experts
    lives here (one chip's share of an expert-parallel layer).  Routing is
    still over all of them; the rows sent to a held expert come first in
    expert order and `run_experts` gets the group sizes of the held experts
    alone (count,); a row sent to an absent expert is computed by nobody
    and adds nothing to y (its part of the sum is another chip's), and no
    gradient comes back through it.  The buffer stays T*k rows, the most
    the held experts can be sent, so nothing is dropped under any
    imbalance: `moe.rows_buffered` against `moe.rows_routed` in the job
    timeline is what that costs.  None: all are held."""
    T, k = experts.shape
    first, count = held or (0, n_experts)
    tracing.count("moe.experts", n_experts)
    tracing.count("moe.experts_held", count)
    tracing.count("moe.rows_routed", T * k)
    tracing.count("moe.rows_buffered", T * k)
    with jax.named_scope("dispatch"):
        flat = experts.reshape(T * k)
        rows = jnp.arange(T * k, dtype=jnp.int32)
        keys = flat
        if held:
            # the absent experts' rows go last, behind every held group
            here = (flat >= first) & (flat < first + count)
            keys = jnp.where(here, flat, n_experts)
        # a stable sort keeps a token's rows in token order inside a group
        _, order = jax.lax.sort((keys, rows), num_keys=1)
        _, inverse = jax.lax.sort((order, rows), num_keys=1)
        group_sizes = jnp.sum(
            flat[:, None] == jnp.arange(n_experts, dtype=flat.dtype)[None],
            axis=0, dtype=jnp.int32)
        xs = _permute_rows(jnp.repeat(x, k, axis=0), order, inverse)
    with jax.named_scope("experts"):
        if held:
            sizes = group_sizes[first:first + count]
            # whatever a grouped matmul makes of the rows of no group, or
            # its transposes of their cotangents, stays inside
            grouped = (rows < jnp.sum(sizes))[:, None]
            ys = jnp.where(grouped, run_experts(
                jnp.where(grouped, xs, 0), sizes), 0)
        else:
            ys = run_experts(xs, group_sizes)
    with jax.named_scope("combine"):
        ys = _permute_rows(ys, inverse, order).reshape(T, k, -1)
        y = jnp.sum(ys.astype(jnp.float32) * weights[..., None], axis=1)
    return y.astype(x.dtype), group_sizes

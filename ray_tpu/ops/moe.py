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


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """x[perm] for a permutation whose inverse is known: the cotangent is
    a gather by the inverse, where autodiff of a gather would scatter-add
    (slow on the chip, and needless: no row is taken twice)."""
    return x[perm]


_permute_rows.defvjp(
    lambda x, perm, inverse: (x[perm], inverse),
    lambda inverse, g: (g[inverse], None, None))


def moe_dispatch(x, weights, experts, n_experts, run_experts):
    """x (T, E); weights, experts (T, k): each token's k experts and what
    each one's output is multiplied by.  `run_experts(rows, group_sizes)`
    gets the T*k rows in expert order (R, E) with the rows of each expert
    (n_experts,) and returns their outputs (R, E), row for row.  Whatever
    the imbalance, every row is computed.  Returns (y (T, E), rows per
    expert (n_experts,) int32).  Differentiable in x, weights and whatever
    `run_experts` closes over."""
    T, k = experts.shape
    with jax.named_scope("dispatch"):
        flat = experts.reshape(T * k)
        rows = jnp.arange(T * k, dtype=jnp.int32)
        # a stable sort keeps a token's rows in token order inside a group
        _, order = jax.lax.sort((flat, rows), num_keys=1)
        _, inverse = jax.lax.sort((order, rows), num_keys=1)
        group_sizes = jnp.sum(
            flat[:, None] == jnp.arange(n_experts, dtype=flat.dtype)[None],
            axis=0, dtype=jnp.int32)
        xs = _permute_rows(jnp.repeat(x, k, axis=0), order, inverse)
    with jax.named_scope("experts"):
        ys = run_experts(xs, group_sizes)
    with jax.named_scope("combine"):
        ys = _permute_rows(ys, inverse, order).reshape(T, k, -1)
        y = jnp.sum(ys.astype(jnp.float32) * weights[..., None], axis=1)
    return y.astype(x.dtype), group_sizes

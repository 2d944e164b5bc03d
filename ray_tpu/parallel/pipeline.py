"""Pipeline parallelism — staged execution over the mesh "pp" axis.

SURVEY.md §2.6 greenfield row "PP" (the reference has no native pipeline
parallelism; users reach for DeepSpeed).  TPU-native design: the WHOLE
pipeline — microbatch loop, per-stage layer stack, activation handoffs —
is ONE jit program:

  * the layer-stacked block params (leading dim = n_layer) shard across
    the ``pp`` axis, giving each stage ``n_layer / pp_size`` consecutive
    layers;
  * a ``lax.scan`` runs the fill/drain microbatch schedule: at tick t,
    stage 0 ingests microbatch t while stage s processes the activation it
    received from stage s-1, then every stage hands its output to the
    next stage via ``lax.ppermute`` (one ICI hop on a TPU torus);
  * per-microbatch AUXILIARY LOSSES (MoE load balancing) ride the same
    handoff as an extra scalar lane of the carry, so routed-FFN models
    train their router under pp (each microbatch's aux accumulates across
    stages exactly like its activation does);
  * outputs leave the schedule via ``lax.psum_scatter``: the final
    (microbatches, ...) buffer is nonzero only on the last stage, so a
    reduce-scatter over the microbatch dim hands each stage an equal slice
    at half an all-reduce's cost, and the result re-enters the outer SPMD
    program SHARDED over pp on the batch dim — the lm-head/loss downstream
    then runs batch-parallel across stages instead of replicated (the
    previous full-buffer ``psum`` gather paid 2x the bytes to compute the
    same thing everywhere);
  * only ``pp`` is manual (`shard_map` ``axis_names={'pp'}``): tensor/
    data/sequence sharding inside each stage stays with the XLA SPMD
    partitioner, so PP composes with tp/fsdp/dp from `ShardingConfig`.

Backward and the 1F1B question: backward is plain autodiff through the
scan — XLA re-runs the schedule in reverse with ppermute transposed, the
same communication pattern a hand-written 1F1B backward performs.  In a
single-program autodiff world the non-interleaved 1F1B schedule buys
nothing over this: its bubble fraction is identical ((S-1)/(M+S-1) ticks
each way — 1F1B's advantage over GPipe is PEAK MEMORY, bounding in-flight
microbatches at S instead of M), and here the memory bound comes from the
remat policy instead: ``jax.checkpoint`` around the stage body
(`models/layers.py:checkpoint_layer`) keeps the residual set to one
activation per tick and a flash kernel's output and row statistics, so
peak live activations per stage are O(M + S) microbatch-slices either way.
See
``schedule_info()`` for the tick/bubble accounting the tests assert.
"""

from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.models.layers import checkpoint_layer


def stack_layer_params(layer_params: list):
    """[per-layer pytree] -> single pytree with leading layer dim (the
    shardable "stage" axis)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *layer_params)


def schedule_info(num_microbatches: int, n_stages: int) -> Dict[str, Any]:
    """Tick/bubble accounting for the fill-drain schedule.

    Every device executes ``ticks`` stage-bodies per direction, of which
    ``num_microbatches`` process real data — utilization is the best any
    non-interleaved schedule (GPipe flush or 1F1B) achieves at this M, S."""
    ticks = num_microbatches + n_stages - 1
    return {
        "ticks": ticks,
        "useful_ticks": num_microbatches,
        "bubble_fraction": (n_stages - 1) / ticks,
        "utilization": num_microbatches / ticks,
    }


def pipeline_apply(
    block_fn: Callable[[Any, Any], Any],
    stacked_params: Any,
    x: jnp.ndarray,
    mesh: Mesh,
    num_microbatches: int,
    axis: str = "pp",
    remat: bool = True,
):
    """Run ``n_layer`` blocks (stacked leading dim, sharded on ``axis``)
    over ``x`` (batch-leading) with the fill-drain microbatch schedule.

    ``block_fn(params_one_layer, x) -> (x, aux)`` where ``aux`` is a
    scalar auxiliary loss (0.0 for plain blocks; MoE load balancing for
    routed FFNs).  Returns ``(out, aux_total)``: ``out`` matches applying
    the layers sequentially bit-for-bit (the schedule only reorders work)
    and comes back sharded over ``axis`` on the microbatch dim when
    ``num_microbatches % n_stages == 0`` (replicated otherwise);
    ``aux_total`` is the per-layer aux summed over layers, averaged over
    microbatches — ``sum_l mean_m aux[l, m]`` — a replicated scalar.
    """
    n_stages = mesh.shape[axis]
    batch = x.shape[0]
    M = num_microbatches
    if batch % M:
        raise ValueError(f"batch {batch} not divisible by "
                         f"num_microbatches {M}")
    mbs = x.reshape(M, batch // M, *x.shape[1:])
    scatter_out = (M % n_stages == 0)

    def stage_body(params_local, x_in):
        # params_local: (layers_per_stage, ...) — this stage's slice
        def layer_step(carry, p_layer):
            h, aux = carry
            h2, aux2 = block_fn(p_layer, h)
            return (h2, aux + aux2), None

        body = layer_step
        if remat:
            body = checkpoint_layer(layer_step)
        # the aux carry is pp-varying from the first layer (params differ
        # per stage) — mark the init accordingly
        aux0 = jax.lax.pcast(jnp.zeros((), jnp.float32), (axis,),
                             to="varying")
        (out, aux), _ = jax.lax.scan(body, (x_in, aux0), params_local)
        return out, aux

    def pipelined(params_local, mbs):
        idx = jax.lax.axis_index(axis)
        n_ticks = M + n_stages - 1
        ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        # carries are per-stage state: mark them pp-varying up front
        vary = lambda v: jax.lax.pcast(v, (axis,), to="varying")
        buf = vary(jnp.zeros_like(mbs[0]))
        buf_aux = vary(jnp.zeros((), jnp.float32))
        outs = vary(jnp.zeros_like(mbs))
        outs_aux = vary(jnp.zeros((M,), jnp.float32))

        def tick(carry, t):
            buf, buf_aux, outs, outs_aux = carry
            # stage 0 ingests microbatch t (clipped; masked after drain)
            feed = jax.lax.dynamic_index_in_dim(
                mbs, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            x_in = jnp.where(idx == 0, feed, buf)
            # aux restarts at 0 for each fresh microbatch and accumulates
            # across stages via the same handoff as the activation
            aux_in = jnp.where(idx == 0, 0.0, buf_aux)
            y, aux_add = stage_body(params_local, x_in)
            y_aux = aux_in + aux_add
            # last stage emits microbatch t-(n_stages-1)
            w = t - (n_stages - 1)
            emit = (idx == n_stages - 1) & (w >= 0)
            wc = jnp.clip(w, 0, M - 1)
            outs = jnp.where(
                emit,
                jax.lax.dynamic_update_index_in_dim(outs, y, wc, 0), outs)
            outs_aux = jnp.where(
                emit,
                jax.lax.dynamic_update_index_in_dim(outs_aux, y_aux, wc, 0),
                outs_aux)
            buf = jax.lax.ppermute(y, axis, ring)
            buf_aux = jax.lax.ppermute(y_aux, axis, ring)
            return (buf, buf_aux, outs, outs_aux), None

        (buf, buf_aux, outs, outs_aux), _ = jax.lax.scan(
            tick, (buf, buf_aux, outs, outs_aux), jnp.arange(n_ticks))
        # only the last stage holds real outputs
        outs = jnp.where(idx == n_stages - 1, outs, 0.0)
        outs_aux = jnp.where(idx == n_stages - 1, outs_aux, 0.0)
        aux_total = jax.lax.psum(jnp.sum(outs_aux), axis) / M
        if scatter_out:
            # reduce-scatter over the microbatch dim: each stage keeps its
            # M/n_stages slice (half an all-reduce's bytes; downstream ops
            # run batch-parallel over pp)
            outs = jax.lax.psum_scatter(outs, axis, scatter_dimension=0,
                                        tiled=True)
        else:
            outs = jax.lax.psum(outs, axis)
        return outs, aux_total

    spec_tree = jax.tree.map(lambda _: P(axis), stacked_params)
    out_spec = P(axis) if scatter_out else P()
    out, aux_total = jax.shard_map(
        pipelined, mesh=mesh,
        in_specs=(spec_tree, P()), out_specs=(out_spec, P()),
        axis_names={axis},
    )(stacked_params, mbs)
    return out.reshape(batch, *x.shape[1:]), aux_total

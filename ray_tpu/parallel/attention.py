"""Attention under a mesh: the one entry the models call.

`attention(q, k, v)` reads the mesh `use_mesh` bound (the only place that
does so for attention) and picks how the kernels of `ray_tpu/ops/` run on
it.  Which mesh axes cut the batch, the heads and the sequence is not said
here: `parallel/sharding.py`'s rules say it, for the activations' pins and
for the `shard_map`s below alike.
"""

from __future__ import annotations

import functools

import jax

from ray_tpu.ops.flash_attention import (
    BlockRule,
    flash_attention_bshd,
    flash_attention_bshd_lse,
    reference_attention,
)
from ray_tpu.parallel.context import get_mesh, require_mesh
from ray_tpu.parallel.ring_attention import ring_attention, ulysses_attention
from ray_tpu.parallel.sharding import dividing_spec


def _tr(x):
    return x.transpose(0, 2, 1, 3)


def attention(q, k, v, *, causal=True, variant: str = "flash",
              mask=None, with_lse: bool = False):
    """Multi-head attention over (batch, seq, heads, head_dim) arrays, at
    head_dim^-1/2.  k and v may have fewer heads than q, a divisor of its
    count (grouped queries: head h reads key/value head h // group); the
    kernels read them as they are and nothing repeats them.

    ``causal``: True, the diagonal; False, every pair; or a rule
    (`ops/flash_attention.py:BlockRule`) of blocks and kinds of row (block
    diffusion's clean and noised rows), of a window (a row's W latest
    keys: a model's sliding layers) or of aligned windows (the keys of a
    row's own window up to itself: `ops/eva.py` calls the kernels under it
    directly), whose empty tiles the kernels never visit; "flash" and
    "dense" only.

    ``mask``: (batch, seq, seq) int8, not 0 where a (query, key) pair is
    attended, the same for all heads of a sequence; with ``causal`` a pair
    must pass both.  A mask that is data: attention that selects its keys
    (`ops/sparse_index.py`), or a segment rule filling it (a window is a
    rule: ``causal``).
    ``with_lse``: -> (o, the kernels' row statistics (batch, heads, seq)
    float32: the log of each query's sum of exp(score) over the keys it
    attends), which nothing is differentiated through; "flash" only.

    ``"flash"``: the layout-native kernel (no (B,S,H,D) <-> (B,H,S,D)
    transposes); under a bound mesh of several devices each runs it on its
    batch/head slice.  ``"ring"`` / ``"ulysses"``: sequence parallelism
    over the bound mesh's sequence axis.  ``"dense"``: the O(S^2)
    reference, left to the partitioner."""
    if q.shape[2] % k.shape[2] or k.shape[:3] != v.shape[:3]:
        raise ValueError(
            f"attention: q has {q.shape[2]} heads and k, v "
            f"{k.shape[2]}, {v.shape[2]}: the key/value heads must be "
            f"alike and divide the query heads")
    if with_lse and variant != "flash":
        raise NotImplementedError(
            f"attention(variant={variant!r}, with_lse=True): the row "
            f"statistics are the flash kernels' (use \"flash\")")
    if variant in ("ring", "ulysses"):
        if isinstance(causal, BlockRule):
            raise NotImplementedError(
                f"attention(variant={variant!r}) takes no {causal}: a rule "
                f"of blocks or of a window is not written for the "
                f"sequence-parallel kernels, whose chunks know the "
                f"diagonal alone (use \"flash\")")
        if mask is not None:
            raise NotImplementedError(
                f"attention(variant={variant!r}) takes no mask: a mask "
                f"that is data is not written for the sequence-parallel "
                f"kernels, whose chunks rotate (use \"flash\")")
        if k.shape[2] != q.shape[2]:
            raise NotImplementedError(
                f"attention(variant={variant!r}) takes k and v with q's "
                f"{q.shape[2]} heads, not {k.shape[2]}: grouped queries "
                f"are not written for the sequence-parallel kernels "
                f"(repeat k and v, or use \"flash\")")
        return _sequence_parallel(q, k, v, require_mesh(), causal, variant)
    if variant == "dense":
        o, _ = reference_attention(_tr(q), _tr(k), _tr(v),
                                   q.shape[-1] ** -0.5, causal, mask)
        return _tr(o)
    mesh = get_mesh()
    if mesh is None or mesh.size == 1:
        if mask is None and not with_lse:
            return flash_attention_bshd(q, k, v, causal)
        kernel = flash_attention_bshd_lse if with_lse else flash_attention_bshd
        return kernel(q, k, v, causal, mask=mask)
    return _flash_sharded(q, k, v, mesh, causal, mask, with_lse)


def _flash_sharded(q, k, v, mesh, causal, mask=None, with_lse=False):
    """shard_map of the layout-native kernel: batch and heads cut as the
    rules say, and each device runs the kernel on its own slice.  A
    pallas_call is an opaque custom call to the SPMD partitioner: under a
    mesh of several devices jax refuses to lower one that is not inside a
    shard_map.  The heads' axes are those that divide the key/value heads
    (and so q's: with grouped queries each device holds whole groups, its
    query heads and the key/value heads they read).  A mask is cut with
    the batch and whole for every head."""
    spec = dividing_spec(mesh, ("batch", None, "heads", None), k.shape)
    if mask is not None or with_lse:
        kernel = flash_attention_bshd_lse if with_lse else flash_attention_bshd
        rows = type(spec)(spec[0], spec[2])         # lse: (batch, heads, seq)
        return jax.shard_map(
            lambda q, k, v, mask: kernel(q, k, v, causal, mask=mask),
            mesh=mesh, in_specs=(spec, spec, spec, type(spec)(spec[0])),
            out_specs=(spec, rows) if with_lse else spec, check_vma=False,
        )(q, k, v, mask)
    return jax.shard_map(
        lambda q, k, v: flash_attention_bshd(q, k, v, causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def _sequence_parallel(q, k, v, mesh, causal, variant):
    """shard_map of the ring (or Ulysses) over the mesh's sequence axis, in
    the head-major layout its chunks rotate in; with no such axis, as
    ``"flash"``."""
    B, S, H, D = q.shape
    spec = dividing_spec(mesh, ("batch", "heads", "seq", None), (B, H, S, D))
    if spec[2] is None:
        return attention(q, k, v, causal=causal)
    inner = ring_attention if variant == "ring" else ulysses_attention
    return _tr(jax.shard_map(
        functools.partial(inner, axis_name=spec[2], causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(_tr(q), _tr(k), _tr(v)))

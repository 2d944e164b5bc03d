"""ShardingConfig: declarative parallelism strategy → concrete shardings.

The TPU-native replacement for the reference's strategy knobs
(`prepare_model(parallel_strategy="ddp"|"fsdp")`,
`python/ray/train/torch/train_loop_utils.py:75-104`) — plus the strategies
the reference lacks natively (TP/PP/SP/EP; SURVEY.md §2.6): here they are
first-class axis sizes, and "wrapping a model" becomes assigning
`NamedSharding`s to a pytree of params by logical-dimension rules.

Parameters are placed by their names (`infer_param_logical_dims`).
Activations are placed where the model says what their dims are:
`constrain(x, "batch", "seq", "mlp")` inside the traced step pins `x` by the
same rules, under the mesh `use_mesh` bound.  `models/gpt2.py` states the
residual stream and both LayerNorm outputs ("batch", "seq", None), qkv
("batch", "seq", "heads"), the MLP's hidden ("batch", "seq", "mlp") and the
logits ("batch", "seq", "vocab").  Earlier dims win a mesh axis, so under
`fsdp=4` all of these are `P("fsdp", None, None)`: the batch stays cut, and
the partitioner has to gather each `embed → fsdp` weight at its use (FSDP).
A model that does not state them leaves the activations' layout to the
partitioner, which may as well keep the weights' cut on the contraction
dim and all-reduce full-batch partial products (GPT-2 XL before PR 29:
210 MB a layer, three times).  The `shard_map`s the attention kernels run
under (`parallel/attention.py`) take their specs from the same rules
(`dividing_spec`): no other module names a mesh axis for a logical dim.

Logical dims used by the bundled models (ray_tpu/models/*):
  "batch"   → (dp, fsdp)     activations' leading dim
  "seq"     → sp             sequence dim of activations
  "embed"   → fsdp           model width when it's the param *sharded* dim
  "mlp"     → tp             hidden/ffn dim
  "heads"   → tp             attention head dim
  "kv"      → None           per-head dim (never sharded)
  "vocab"   → tp             embedding vocab dim
  "expert"  → ep             MoE expert dim
  "stage"   → pp             pipeline-stacked leading dim
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel.context import get_mesh
from ray_tpu.parallel.mesh import create_mesh
from ray_tpu.util import tracing

DEFAULT_RULES: Dict[str, Any] = {
    "batch": ("dp", "fsdp"),
    "seq": "sp",
    "embed": "fsdp",
    "mlp": "tp",
    "heads": "tp",
    "kv": None,
    "vocab": "tp",
    "expert": "ep",
    "stage": "pp",
    None: None,
}


@dataclass
class ShardingConfig:
    """Axis sizes for the device mesh.  -1 = all remaining devices."""

    dp: int = 1
    fsdp: int = 1
    pp: int = 1
    sp: int = 1
    ep: int = 1
    tp: int = 1

    def axes(self) -> Dict[str, int]:
        sizes = {"dp": self.dp, "fsdp": self.fsdp, "pp": self.pp,
                 "sp": self.sp, "ep": self.ep, "tp": self.tp}
        return {k: v for k, v in sizes.items() if v != 1 or k == "dp"}

    def build_mesh(self, devices=None) -> Mesh:
        return create_mesh(self.axes(), devices=devices)

    # ------------------------------------------------------------------

    def spec(self, mesh: Mesh, *logical_dims: Optional[str]) -> P:
        return logical_spec(mesh, logical_dims)

    def named_sharding(self, mesh: Mesh, *logical_dims) -> NamedSharding:
        return NamedSharding(mesh, self.spec(mesh, *logical_dims))

    def shard_pytree(self, mesh: Mesh, logical_tree) -> Any:
        """Map a pytree of logical-dim tuples to NamedShardings."""
        return jax.tree.map(
            lambda dims: self.named_sharding(mesh, *dims),
            logical_tree,
            is_leaf=lambda x: isinstance(x, tuple),
        )


def _axes(mesh, logical_dims):
    """Per logical dim, the mesh axes (those larger than 1) `DEFAULT_RULES`
    cut it on.  A mesh axis may appear only once in a PartitionSpec;
    earlier dims win (so "batch" on (dp, fsdp) suppresses "embed" on fsdp
    for activations — params without a batch dim still shard on fsdp)."""
    used: set = set()
    out = []
    for d in logical_dims:
        axis = DEFAULT_RULES.get(d)
        axes = axis if isinstance(axis, (tuple, list)) else (axis,)
        axes = tuple(a for a in axes
                     if mesh.shape.get(a, 1) > 1 and a not in used)
        used.update(axes)
        out.append(axes)
    return out


def _entry(axes):
    return tuple(axes) if len(axes) > 1 else (axes[0] if axes else None)


def logical_spec(mesh, logical_dims) -> P:
    """PartitionSpec of logical dims on a mesh."""
    return P(*map(_entry, _axes(mesh, logical_dims)))


def dividing_spec(mesh, logical_dims, shape) -> P:
    """PartitionSpec of an array of ``shape`` stated as ``logical_dims``,
    for a `shard_map` (which refuses a dimension its axes do not divide):
    an axis that does not divide what is left of its dimension is dropped,
    and the dimension is gathered over it."""
    entries = []
    for axes, size in zip(_axes(mesh, logical_dims), shape):
        picked = []
        for a in axes:
            if size % mesh.shape[a] == 0:
                picked.append(a)
                size //= mesh.shape[a]
        entries.append(_entry(picked))
    return P(*entries)


def chip_bytes(shape, dtype, *logical_dims, mesh=None, tiled=False) -> int:
    """Bytes ONE chip holds of an array of ``shape`` stated as
    ``logical_dims`` (fewer than its dims: the rest are whole) on ``mesh``
    (None: the one `use_mesh` bound; none bound: one chip holds it all):
    each dim over the axes `dividing_spec` cuts it on.  ``tiled``: laid
    out in the order of its dims as the chip tiles an array, the last dim
    in whole lanes of 128 and the one before it in whole sublanes (8 rows
    of 32 bits, 16 of 16): what a Mosaic kernel's operand or result takes
    (a (rows, 1) float32 column 128 times its data: PERF.md §6, PR 35).
    An array the compiler makes itself it lays out in whatever order of
    dims pads least, and that counts as its data."""
    mesh = mesh or get_mesh()
    shape = list(shape)
    if mesh is not None and mesh.size > 1:
        dims = tuple(logical_dims) + (None,) * (len(shape) - len(logical_dims))
        for i, axes in enumerate(dividing_spec(mesh, dims, shape)):
            axes = axes if isinstance(axes, tuple) else (axes,)
            shape[i] //= math.prod(mesh.shape[a] for a in axes if a)
    width = jax.numpy.dtype(dtype).itemsize
    if tiled and shape:
        shape[-1] = -(-shape[-1] // 128) * 128
    if tiled and len(shape) > 1:
        sublanes = 8 * max(1, 4 // width)
        shape[-2] = -(-shape[-2] // sublanes) * sublanes
    return math.prod(shape) * width


def constrain(x, *logical_dims):
    """Pin an activation to where `DEFAULT_RULES` put its logical dims on
    the mesh `use_mesh` bound: `with_sharding_constraint` inside the traced
    step.  `x` comes back untouched, with nothing added to the jaxpr, when
    no mesh is bound or it has one device, when some dim's size does not
    divide by its axes, and inside a `shard_map` (the pipeline's stages: a
    full-mesh constraint cannot name the axis that is manual there, so the
    stage's inside stays with the partitioner).  What it does depends on
    the mesh alone; each pin counts once, at trace time, on the job's
    timeline (`parallel.constraints`)."""
    mesh = get_mesh()
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return x
    for size, axes in zip(x.shape, _axes(mesh, logical_dims)):
        if size % math.prod(mesh.shape[a] for a in axes):
            return x
    tracing.count("parallel.constraints")
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, logical_spec(mesh, logical_dims)))


def infer_param_logical_dims(path: Tuple[str, ...], shape: Tuple[int, ...]):
    """Heuristic logical dims for a transformer param by its name path.

    Mirrors how t5x/maxtext-style logical axis rules classify params; used
    when a model doesn't annotate its params explicitly.
    """
    name = "/".join(str(p) for p in path).lower()
    if path and str(path[0]) == "blocks":
        # pipeline-stacked block params: leading layer dim = "stage" (pp)
        inner = infer_param_logical_dims(path[1:], shape[1:])
        return ("stage",) + tuple(inner)
    nd = len(shape)
    if nd == 0:
        return ()
    if "router" in name:
        return ("embed", None)[:nd]
    if "exit_gate" in name:
        # a looped model's gate, (E, 1) and its one bias: nothing to cut
        # but the stream's width
        return ("embed", None) if nd == 2 else (None,)
    if "short_conv/conv" in name or "mamba/conv" in name:
        # a depthwise filter a channel, (E, taps), and a state-space
        # mixer's with its bias: the taps are never cut
        return ("embed", None)[:nd]
    if nd == 2 and ("mamba/x_proj" in name or "mamba/a_log" in name):
        # a Mamba-1 mixer's W_x (C, R + 2 N) and A_log (C, N): the channels
        # cut as the stream's width is, the rank and the state never (a
        # Mamba-2 mixer's A_log is a vector of its heads, whole everywhere)
        return ("embed", None)
    if "mamba/dt_proj" in name:
        # W_dt (R, C) and dt's bias (C,): the channels again
        return (None, "embed") if nd == 2 else ("embed",)
    if "kda/" in name:
        # a delta-rule mixer's leaves that the names below do not tell: the
        # taps (3 H K, taps), a filter a channel of q, k and v, cut by heads
        # and the taps never; A_log (H,) and dt_bias (H K,), a head's own;
        # W_f (E, H K) and W_b (E, H), the decay's and beta's maps (W_g is a
        # "g_proj" and the gain over a head a norm's scale, below)
        if "kda/conv" in name:
            return ("heads", None)[:nd]
        if "a_log" in name or "dt_bias" in name:
            return ("heads",)
        if "f_proj" in name or "b_proj" in name:
            return ("embed", "heads")
    if "eva/phi" in name or "eva/mu" in name:
        # an EVA mixer's two learned vectors a head, (H, D): the pooling's
        # direction and the summaries' offset, cut as the heads are
        return ("heads", None)
    if "lambda_" in name or "diff_norm" in name:
        # differential attention's four vectors of a head's width and the
        # gain over a pair of heads: whole on every chip
        return (None,) * nd
    if "moe" in name and "/wi" in name:
        return ("expert", "embed", "mlp")[:nd]
    if "moe" in name and "/wo" in name:
        return ("expert", "mlp", "embed")[:nd]
    if "embedding" in name or "wte" in name or "embed_tokens" in name:
        return ("vocab", "embed")[:nd] if nd >= 2 else ("embed",)
    if "wpe" in name or "pos_emb" in name:
        return (None, "embed")[:nd] if nd >= 2 else ("embed",)
    if any(k in name for k in ("ln", "layernorm", "layer_norm", "norm",
                               "scale", "bias", "rmsnorm")) and nd == 1:
        return (None,)
    # "g_proj": a gated attention's W_g (E, H), a scalar a head
    if any(k in name for k in ("q_proj", "k_proj", "v_proj", "g_proj", "qkv",
                               "c_attn", "wq", "wk", "wv", "query", "key",
                               "value")):
        return ("embed", "heads") if nd == 2 else ("embed", "heads", "kv")[:nd]
    if any(k in name for k in ("o_proj", "c_proj/attn", "attn/c_proj", "wo",
                               "out_proj")):
        return ("heads", "embed")[:nd]
    if any(k in name for k in ("up_proj", "gate_proj", "c_fc", "wi", "fc1",
                               "mlp_in")):
        return ("embed", "mlp")[:nd]
    if any(k in name for k in ("down_proj", "wo_mlp", "c_proj", "fc2", "wo2",
                               "mlp_out")):
        return ("mlp", "embed")[:nd]
    if "lm_head" in name:
        return ("embed", "vocab")[:nd]
    if nd == 2:
        return ("embed", "mlp")
    if nd == 1:
        return (None,)
    return tuple([None] * nd)


def param_logical_dims(params):
    """(the treedef of a param pytree, [(leaf, its logical dims by its
    path's names)])."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    return treedef, [
        (leaf, infer_param_logical_dims(
            tuple(getattr(k, "key", getattr(k, "idx", str(k))) for k in path),
            getattr(leaf, "shape", ())))
        for path, leaf in flat]


def param_shardings(params, config: ShardingConfig, mesh: Mesh):
    """NamedSharding pytree (for jit in_shardings/out_shardings)."""
    treedef, leaves = param_logical_dims(params)
    return jax.tree_util.tree_unflatten(treedef, [
        config.named_sharding(mesh, *dims) if dims
        else NamedSharding(mesh, P()) for _, dims in leaves])


def shard_params(params, config: ShardingConfig, mesh: Mesh):
    """Device-put a param pytree according to inferred logical dims."""
    return jax.tree.map(jax.device_put, params,
                        param_shardings(params, config, mesh))

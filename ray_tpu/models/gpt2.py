"""GPT-2 — pure-JAX transformer, TPU-first.

The flagship training model (BASELINE.json: "GPT-2 124M/1.5B data-parallel
pretraining").  Design choices for the MXU/HBM:

  * params stay f32 (optimizer quality), activations/matmuls run bf16
    (`compute_dtype`) — MXU native.
  * attention goes through `ray_tpu/parallel/attention.py`: the Pallas
    flash kernel (`ray_tpu/ops/flash_attention.py`), under a bound mesh
    inside a shard_map; sequence-parallel configs take ring attention.
  * param names follow the logical-dim heuristics in
    `ray_tpu/parallel/sharding.py` so `ShardingConfig` can place every leaf
    (wte → (vocab, embed), c_attn → (embed, heads), mlp c_proj →
    (mlp, embed), ...).
  * activations state their logical dims (`constrain`): the residual
    stream and LayerNorm outputs ("batch", "seq", None), qkv on "heads",
    the MLP's hidden on "mlp", the logits on "vocab".  Under a bound mesh
    the same rules pin them, so the batch stays cut and `fsdp` gathers
    each weight at its use; with no mesh, or one device, nothing is added.
  * static shapes everywhere; the whole train step jits to one XLA program.

`jax.named_scope`s (`models/layers.py:SCOPES`): embed, norm,
attention/{qkv,kernel,out}, ffn/dense, ffn/moe/{route,dispatch,experts,
combine}, head_and_loss, optimizer_update.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.layers import (
    checkpoint_layer,
    layer_norm,
    named,
    train_step,
)
from ray_tpu.ops import grouped_matmul
from ray_tpu.ops.moe import ROUTE_NAME, moe_dispatch
from ray_tpu.parallel.attention import attention
from ray_tpu.parallel.sharding import constrain


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50304  # padded to a multiple of 128 for the MXU
    block_size: int = 1024
    n_layer: int = 12
    n_head: int = 12
    n_embd: int = 768
    compute_dtype: Any = jnp.bfloat16
    attention: str = "flash"  # flash | ring | ulysses | dense
    # jax.checkpoint each block (trade FLOPs for HBM), keeping its attention
    # kernel's output and row statistics and, of `layers.KEPT_NAMES` (here
    # c_attn's, the attention c_proj's and c_fc's results), those the chip
    # has room for over all blocks (`layers.checkpoint_layer`)
    remat: bool = False
    # MoE (expert parallelism, SURVEY §2.6 row "EP"): >0 swaps every
    # block's dense FFN for a top-k routed mixture; expert weights carry a
    # leading "expert" dim that ShardingConfig places on the ep axis; the
    # dispatch is dropless (`ops/moe.py`): no capacity, no token dropped.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_aux_weight: float = 0.01

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head


GPT2_SMALL = GPT2Config()
GPT2_MEDIUM = GPT2Config(n_layer=24, n_head=16, n_embd=1024)
GPT2_LARGE = GPT2Config(n_layer=36, n_head=20, n_embd=1280)
GPT2_XL = GPT2Config(n_layer=48, n_head=25, n_embd=1600)
GPT2_TINY = GPT2Config(vocab_size=512, block_size=128, n_layer=2, n_head=2,
                       n_embd=64)


def init_params(rng, cfg: GPT2Config) -> Dict[str, Any]:
    std = 0.02
    proj_std = std / math.sqrt(2 * cfg.n_layer)
    keys = jax.random.split(rng, 4 + cfg.n_layer)

    def normal(key, shape, s=std):
        return (jax.random.normal(key, shape, jnp.float32) * s)

    params: Dict[str, Any] = {
        "wte": {"embedding": normal(keys[0], (cfg.vocab_size, cfg.n_embd))},
        "wpe": {"embedding": normal(keys[1], (cfg.block_size, cfg.n_embd), 0.01)},
        "ln_f": {"scale": jnp.ones((cfg.n_embd,)), "bias": jnp.zeros((cfg.n_embd,))},
    }
    for i in range(cfg.n_layer):
        k1, k2, k3, k4, k5 = jax.random.split(keys[4 + i], 5)
        block = {
            "ln_1": {"scale": jnp.ones((cfg.n_embd,)),
                     "bias": jnp.zeros((cfg.n_embd,))},
            "attn": {
                "c_attn": {"kernel": normal(k1, (cfg.n_embd, 3 * cfg.n_embd)),
                           "bias": jnp.zeros((3 * cfg.n_embd,))},
                "c_proj": {"kernel": normal(k2, (cfg.n_embd, cfg.n_embd),
                                            proj_std),
                           "bias": jnp.zeros((cfg.n_embd,))},
            },
            "ln_2": {"scale": jnp.ones((cfg.n_embd,)),
                     "bias": jnp.zeros((cfg.n_embd,))},
        }
        if cfg.moe_experts > 0:
            block["moe"] = {
                "router": {"kernel": normal(k5, (cfg.n_embd,
                                                 cfg.moe_experts))},
                "wi": normal(k3, (cfg.moe_experts, cfg.n_embd,
                                  4 * cfg.n_embd)),
                "wo": normal(k4, (cfg.moe_experts, 4 * cfg.n_embd,
                                  cfg.n_embd), proj_std),
            }
        else:
            block["mlp"] = {
                "c_fc": {"kernel": normal(k3, (cfg.n_embd, 4 * cfg.n_embd)),
                         "bias": jnp.zeros((4 * cfg.n_embd,))},
                "c_proj": {"kernel": normal(k4, (4 * cfg.n_embd, cfg.n_embd),
                                            proj_std),
                           "bias": jnp.zeros((cfg.n_embd,))},
            }
        params[f"h_{i}"] = block
    return params


def _attention(x, p, cfg: GPT2Config):
    B, S, E = x.shape
    H, D = cfg.n_head, cfg.head_dim
    with jax.named_scope("qkv"):
        qkv = (x @ p["c_attn"]["kernel"].astype(x.dtype)
               + p["c_attn"]["bias"].astype(x.dtype))
        qkv = named(constrain(qkv, "batch", "seq", "heads"), "attention/qkv")
        q, k, v = jnp.split(qkv, 3, axis=-1)
    with jax.named_scope("kernel"):
        o = attention(q.reshape(B, S, H, D), k.reshape(B, S, H, D),
                      v.reshape(B, S, H, D), variant=cfg.attention)
    with jax.named_scope("out"):
        o = o.reshape(B, S, E)
        return named(o @ p["c_proj"]["kernel"].astype(x.dtype)
                     + p["c_proj"]["bias"].astype(x.dtype), "attention/out")


def _mlp(x, p):
    h = x @ p["c_fc"]["kernel"].astype(x.dtype) + p["c_fc"]["bias"].astype(x.dtype)
    h = jax.nn.gelu(named(constrain(h, "batch", "seq", "mlp"), "ffn/hidden"))
    return h @ p["c_proj"]["kernel"].astype(x.dtype) + p["c_proj"]["bias"].astype(x.dtype)


def _moe_mlp(x, p, cfg: GPT2Config):
    """Top-k routed mixture-of-experts FFN (SURVEY §2.6 row "EP"), dropless
    (`ops/moe.py:moe_dispatch`): the (token, expert) rows sorted by expert,
    two grouped matmuls with a GELU between, the rows summed back with
    their renormalised gate values.  Expert weights carry a leading expert
    dim that `ShardingConfig` places on the ep axis.  Returns
    (y, aux_load_balancing_loss)."""
    B, S, E = x.shape
    xt = x.reshape(B * S, E)
    with jax.named_scope("route"):
        # the logits: a softmax's backward reads its own result
        router_logits = named(
            (xt @ p["router"]["kernel"].astype(x.dtype)
             ).astype(jnp.float32), ROUTE_NAME)                 # (T, n_exp)
        probs = jax.nn.softmax(router_logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, cfg.moe_top_k)  # (T, k)
        gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True)
                                 + 1e-9)
    wi, wo = p["wi"].astype(x.dtype), p["wo"].astype(x.dtype)

    def gelu_experts(xs, group_sizes):
        matmul = grouped_matmul.over(group_sizes, xs.shape[0])
        return matmul(jax.nn.gelu(matmul(xs, wi)), wo)

    y, _ = moe_dispatch(xt, gate_vals, gate_idx, cfg.moe_experts,
                        gelu_experts)
    with jax.named_scope("route"):
        # load-balancing aux (Switch eq. 4): fraction routed x router prob
        frac = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], cfg.moe_experts,
                                       dtype=jnp.float32), axis=0)
        importance = jnp.mean(probs, axis=0)
        aux = cfg.moe_experts * jnp.sum(frac * importance)
    return y.reshape(B, S, E), aux


def _residual(x):
    """The residual stream and what LayerNorm makes of it: cut by batch
    and sequence, whole in the width."""
    return constrain(x, "batch", "seq", None)


def _block(x, p, cfg: GPT2Config):
    """-> (x, the mixture's load-balancing loss; zero for a dense block)."""
    # stated at the block's entry (and not at its exit), so that a
    # `jax.checkpoint` of it recomputes the forward pass under the same pins
    x = _residual(x)
    h = _residual(layer_norm(x, p["ln_1"]))
    with jax.named_scope("attention"):
        x = _residual(x + _attention(h, p["attn"], cfg))
    h = _residual(layer_norm(x, p["ln_2"]))
    with jax.named_scope("ffn"):
        if "moe" in p:
            with jax.named_scope("moe"):
                y, aux = _moe_mlp(h, p["moe"], cfg)
        else:
            with jax.named_scope("dense"):
                y, aux = _mlp(h, p["mlp"]), jnp.zeros((), jnp.float32)
        return x + y, aux


def to_pipeline_params(params, cfg: GPT2Config):
    """Stack the per-layer blocks into one leading-layer-dim pytree (the
    "stage" axis `ShardingConfig` places on pp); non-block params pass
    through.  Use with ``forward``/``make_train_step`` on a mesh whose pp
    axis > 1."""
    from ray_tpu.parallel.pipeline import stack_layer_params

    out = {k: v for k, v in params.items() if not k.startswith("h_")}
    out["blocks"] = stack_layer_params(
        [params[f"h_{i}"] for i in range(cfg.n_layer)])
    return out


def _trunk(params, tokens, cfg: GPT2Config, pp_microbatches: int = 2):
    """Embedding + transformer blocks + final LN -> ((B, S, E) in
    compute_dtype (the LN itself runs f32 for stability), the blocks'
    auxiliary loss averaged over the layers).  With stacked ``blocks``
    params (see to_pipeline_params) the block stack runs as a pipeline over
    the mesh pp axis; MoE aux loss rides the stage handoff as a scalar
    carry lane (averaged over microbatches)."""
    S = tokens.shape[1]
    with jax.named_scope("embed"):
        x = (params["wte"]["embedding"][tokens]
             + params["wpe"]["embedding"][:S][None])
        x = _residual(x.astype(cfg.compute_dtype))

    def block(h, p):
        return _block(h, p, cfg)

    if "blocks" in params:
        from ray_tpu.parallel.context import require_mesh
        from ray_tpu.parallel.pipeline import pipeline_apply

        # the pipeline returns sum-over-layers of the per-microbatch-mean
        # aux, so dividing by n_layer matches the sequential path's mean
        x, aux = pipeline_apply(
            lambda p, h: block(h, p),
            params["blocks"], x, require_mesh(), pp_microbatches)
        aux = aux / cfg.n_layer
    else:
        blocks = [params[f"h_{i}"] for i in range(cfg.n_layer)]
        layer = checkpoint_layer(
            block, stack=[(x, p) for p in blocks],
            behind=jax.ShapeDtypeStruct((*tokens.shape, cfg.vocab_size),
                                        jnp.float32)) if cfg.remat else block
        auxes = []
        for p in blocks:
            x, aux = layer(x, p)
            auxes.append(aux)
        aux = sum(auxes) / len(auxes)
    x = layer_norm(_residual(x.astype(jnp.float32)), params["ln_f"])
    return _residual(x.astype(cfg.compute_dtype)), aux


def forward(params, tokens, cfg: GPT2Config, pp_microbatches: int = 2):
    """tokens (B, S) int32 -> logits (B, S, vocab) f32."""
    x, _ = _trunk(params, tokens, cfg, pp_microbatches)
    # Tied lm head: bf16 operands on the MXU (an f32 head would run the
    # model's largest matmul at the slow f32 MXU rate) with an f32
    # accumulate/output so the softmax sees full-precision logits.
    wte = params["wte"]["embedding"].astype(cfg.compute_dtype)
    return _logits(x, wte)


def _logits(x, wte):
    return constrain(jnp.matmul(x, wte.T, preferred_element_type=jnp.float32),
                     "batch", "seq", "vocab")


def loss_fn(params, batch, cfg: GPT2Config, pp_microbatches: int = 2):
    """batch: {"tokens": (B, S+1)} — next-token cross entropy (+ MoE
    load-balancing aux when the model is a mixture)."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x, aux = _trunk(params, inputs, cfg, pp_microbatches)
    with jax.named_scope("head_and_loss"):
        wte = params["wte"]["embedding"].astype(cfg.compute_dtype)
        logits = _logits(x, wte)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, targets[..., None],
                                  axis=-1)[..., 0]
        loss = jnp.mean(lse - tgt)
    if cfg.moe_experts > 0:
        loss = loss + cfg.moe_aux_weight * aux
    return loss


def make_train_step(cfg: GPT2Config, optimizer, pp_microbatches: int = 2):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics) — jit it with the appropriate shardings.  Works for dense,
    MoE, and pipeline-stacked params alike.  Mixed precision as
    `layers.train_step` says."""

    def objective(params, batch):
        loss = loss_fn(params, batch, cfg, pp_microbatches)
        return loss, {"loss": loss}

    return train_step(objective, optimizer, cfg.compute_dtype)


def count_flops_per_token(cfg: GPT2Config, seq_len: int) -> float:
    """Training (fwd+bwd) FLOPs per token: 6N + 12*L*E*S (PaLM appendix B).

    N counts matmul params only: 12*L*E^2 for the blocks (c_attn 3E^2 +
    attn c_proj E^2 + mlp 8E^2) plus V*E for the tied lm head (the
    embedding gather is not a matmul).  The 6 covers fwd (2) + bwd (4);
    callers must NOT multiply by 3 again.
    """
    n = 12 * cfg.n_layer * cfg.n_embd ** 2 + cfg.vocab_size * cfg.n_embd
    return 6 * n + 12 * cfg.n_layer * cfg.n_embd * seq_len

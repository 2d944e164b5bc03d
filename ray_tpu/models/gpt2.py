"""GPT-2 — pure-JAX transformer, TPU-first.

The flagship training model (BASELINE.json: "GPT-2 124M/1.5B data-parallel
pretraining").  Design choices for the MXU/HBM:

  * params stay f32 (optimizer quality), activations/matmuls run bf16
    (`compute_dtype`) — MXU native.
  * attention goes through the Pallas flash kernel
    (`ray_tpu/ops/flash_attention.py`); sequence-parallel configs swap in
    ring attention (`ray_tpu/parallel/ring_attention.py`) under shard_map.
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
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.ops.flash_attention import flash_attention_bshd
from ray_tpu.ops.moe import moe_dispatch
from ray_tpu.parallel.sharding import constrain


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50304  # padded to a multiple of 128 for the MXU
    block_size: int = 1024
    n_layer: int = 12
    n_head: int = 12
    n_embd: int = 768
    dropout: float = 0.0
    compute_dtype: Any = jnp.bfloat16
    attention: str = "flash"  # flash | ring | ulysses | dense
    remat: bool = False      # jax.checkpoint each block (trade FLOPs for HBM)
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


def _layer_norm(x, p, eps=1e-5):
    """Stats in f32 for stability; output CAST BACK to the input dtype —
    the f32 scale/bias would otherwise silently promote the residual
    stream (and every downstream matmul) to the MXU's slow f32 path."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def _attention(x, p, cfg: GPT2Config, mesh=None):
    B, S, E = x.shape
    H, D = cfg.n_head, cfg.head_dim
    qkv = x @ p["c_attn"]["kernel"].astype(x.dtype) + p["c_attn"]["bias"].astype(x.dtype)
    qkv = constrain(qkv, "batch", "seq", "heads")
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, S, H, D)
    k = k.reshape(B, S, H, D)
    v = v.reshape(B, S, H, D)
    if cfg.attention in ("ring", "ulysses"):
        # sequence parallelism: shard_map over the bound mesh's sp axis
        # (head-major layout — the ring rotates (B, H, Sq, D) chunks)
        from ray_tpu.parallel.context import require_mesh
        from ray_tpu.parallel.ring_attention import ring_attention_sharded

        o = ring_attention_sharded(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), require_mesh(), causal=True,
            variant=cfg.attention).transpose(0, 2, 1, 3)
    elif cfg.attention == "dense":
        from ray_tpu.ops.flash_attention import _reference_attention

        o, _ = _reference_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), D ** -0.5, True)
        o = o.astype(x.dtype).transpose(0, 2, 1, 3)
    else:
        # layout-native kernel: no (B,S,H,D) <-> (B,H,S,D) transposes;
        # under a bound mesh each device runs it on its batch/head slice
        from ray_tpu.parallel.context import get_mesh

        mesh = get_mesh()
        if mesh is None or mesh.size == 1:
            o = flash_attention_bshd(q, k, v, True)
        else:
            from ray_tpu.parallel.ring_attention import (
                flash_attention_sharded,
            )

            o = flash_attention_sharded(q, k, v, mesh, causal=True)
    o = o.reshape(B, S, E)
    return o @ p["c_proj"]["kernel"].astype(x.dtype) + p["c_proj"]["bias"].astype(x.dtype)


def _mlp(x, p):
    h = x @ p["c_fc"]["kernel"].astype(x.dtype) + p["c_fc"]["bias"].astype(x.dtype)
    h = jax.nn.gelu(constrain(h, "batch", "seq", "mlp"))
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
    router_logits = (xt @ p["router"]["kernel"].astype(x.dtype)
                     ).astype(jnp.float32)                      # (T, n_exp)
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, cfg.moe_top_k)   # (T, k)
    gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)
    wi, wo = p["wi"].astype(x.dtype), p["wo"].astype(x.dtype)

    def gelu_experts(xs, group_sizes):
        h = jax.nn.gelu(jax.lax.ragged_dot(xs, wi, group_sizes))
        return jax.lax.ragged_dot(h, wo, group_sizes)

    y, _ = moe_dispatch(xt, gate_vals, gate_idx, cfg.moe_experts,
                        gelu_experts)
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


def _block(x, p, cfg: GPT2Config, aux_acc=None):
    # stated at the block's entry (and not at its exit), so that a
    # `jax.checkpoint` of it recomputes the forward pass under the same pins
    x = _residual(x)
    x = _residual(x + _attention(_residual(_layer_norm(x, p["ln_1"])),
                                 p["attn"], cfg))
    h = _residual(_layer_norm(x, p["ln_2"]))
    if "moe" in p:
        y, aux = _moe_mlp(h, p["moe"], cfg)
        if aux_acc is not None:
            aux_acc.append(aux)
    else:
        y = _mlp(h, p["mlp"])
    return x + y


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


def _trunk(params, tokens, cfg: GPT2Config, aux_acc=None,
           pp_microbatches: int = 2):
    """Embedding + transformer blocks + final LN -> (B, S, E) in
    compute_dtype (the LN itself runs f32 for stability).  With stacked
    ``blocks`` params (see to_pipeline_params) the block stack runs as a
    pipeline over the mesh pp axis; MoE aux loss rides the stage handoff
    as a scalar carry lane (averaged over microbatches)."""
    S = tokens.shape[1]
    x = (params["wte"]["embedding"][tokens]
         + params["wpe"]["embedding"][:S][None])
    x = _residual(x.astype(cfg.compute_dtype))

    def block_with_aux(h, p):
        acc: list = []
        h2 = _block(h, p, cfg, acc)
        aux = acc[0] if acc else jnp.zeros((), jnp.float32)
        return h2, aux

    if "blocks" in params:
        from ray_tpu.parallel.context import require_mesh
        from ray_tpu.parallel.pipeline import pipeline_apply

        # MoE aux rides the stage handoff as a scalar carry lane; the
        # pipeline returns sum-over-layers of the per-microbatch-mean aux,
        # so dividing by n_layer matches the sequential path's
        # sum(aux_acc)/len(aux_acc).
        x, pp_aux = pipeline_apply(
            lambda p, h: block_with_aux(h, p),
            params["blocks"], x, require_mesh(), pp_microbatches)
        if aux_acc is not None and cfg.moe_experts > 0:
            aux_acc.append(pp_aux / cfg.n_layer)
    elif cfg.remat:
        rblock = jax.checkpoint(block_with_aux)
        for i in range(cfg.n_layer):
            x, aux = rblock(x, params[f"h_{i}"])
            if aux_acc is not None and cfg.moe_experts > 0:
                aux_acc.append(aux)
    else:
        for i in range(cfg.n_layer):
            x = _block(x, params[f"h_{i}"], cfg, aux_acc)
    x = _layer_norm(_residual(x.astype(jnp.float32)), params["ln_f"])
    return _residual(x.astype(cfg.compute_dtype))


def forward(params, tokens, cfg: GPT2Config, aux_acc=None,
            pp_microbatches: int = 2):
    """tokens (B, S) int32 -> logits (B, S, vocab) f32."""
    x = _trunk(params, tokens, cfg, aux_acc, pp_microbatches)
    # Tied lm head: bf16 operands on the MXU (an f32 head costs ~30% of
    # model FLOPs at the slow f32 MXU rate) with an f32 accumulate/output
    # so the softmax sees full-precision logits.
    wte = params["wte"]["embedding"].astype(cfg.compute_dtype)
    return _logits(x, wte)


def _logits(x, wte):
    return constrain(jnp.matmul(x, wte.T, preferred_element_type=jnp.float32),
                     "batch", "seq", "vocab")


def _chunked_xent(x, wte, targets, n_chunks: int):
    """Fused linear + softmax cross-entropy, chunked over tokens.

    The naive path materializes (B*S, V) f32 logits in HBM twice (forward
    residual + backward read) — ~3.3 GB at B=16, S=1024, V=50257, which
    dominates step time for a 124M model.  Instead: scan over token chunks,
    each chunk computing logits -> (lse, target-logit) under
    ``jax.checkpoint`` so the backward pass RECOMPUTES the chunk's logits
    and immediately contracts d_logits into (dx, dwte) — the full logits
    tensor never exists in HBM in either pass.  (Same idea as fused
    linear-cross-entropy kernels; here XLA fuses the chunk, no Pallas
    needed.)

    x: (N, E) compute-dtype; wte: (V, E); targets: (N,) int32.
    Returns summed loss (f32).
    """
    N, E = x.shape
    n_chunks = max(1, min(n_chunks, N))
    while N % n_chunks:
        n_chunks -= 1
    xc = x.reshape(n_chunks, N // n_chunks, E)
    tc = targets.reshape(n_chunks, N // n_chunks)

    @jax.checkpoint
    def chunk(carry, xt):
        xi, ti = xt
        logits = jnp.matmul(xi, wte.T,
                            preferred_element_type=jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, ti[:, None], axis=-1)[:, 0]
        return carry + jnp.sum(lse - tgt), None

    total, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32), (xc, tc))
    return total


def loss_fn(params, batch, cfg: GPT2Config, pp_microbatches: int = 2,
            xent_chunks: int = 0):
    """batch: {"tokens": (B, S+1)} — next-token cross entropy (+ MoE
    load-balancing aux when the model is a mixture).

    ``xent_chunks=0`` (default) materializes logits densely — measured
    FASTER on v5e at the 124M/seq-1024 bench shape, where HBM is not
    tight.  ``xent_chunks>0`` switches to the chunked rematerialized
    fused head (``_chunked_xent``) that never materializes (B, S, V)
    logits — for long-sequence / big-batch configs where the ~3 GB+
    logits tensor would evict everything else (it wins at B=32 already).
    """
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    aux_acc: list = []
    x = _trunk(params, inputs, cfg, aux_acc, pp_microbatches)
    B, S, E = x.shape
    wte = params["wte"]["embedding"].astype(cfg.compute_dtype)
    if xent_chunks > 0:
        total = _chunked_xent(x.reshape(B * S, E), wte,
                              targets.reshape(B * S), xent_chunks)
        loss = total / (B * S)
    else:
        # dense path: materialize logits (faster when HBM is not tight)
        logits = _logits(x, wte)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, targets[..., None],
                                  axis=-1)[..., 0]
        loss = jnp.mean(lse - tgt)
    if aux_acc:
        loss = loss + cfg.moe_aux_weight * sum(aux_acc) / len(aux_acc)
    return loss


def _cast_weights(params, dtype):
    """One whole-tree cast of the matmul weights (ndim >= 2) to the compute
    dtype.  Doing this ONCE up front instead of per-use matters on TPU:
    XLA fuses a single-consumer f32->bf16 cast INTO the consuming matmul,
    and a matmul with a fused operand conversion runs at ~0.4x the MXU
    rate (measured 137 -> 57 TFLOP/s on v5e).  A shared pre-cast
    materializes each bf16 weight once and every matmul runs full speed.
    1-D leaves (biases, LN scale) stay f32 — they only feed VPU ops."""
    return jax.tree.map(
        lambda x: x.astype(dtype)
        if x.dtype == jnp.float32 and x.ndim >= 2 else x, params)


def make_train_step(cfg: GPT2Config, optimizer, pp_microbatches: int = 2,
                    xent_chunks: int = 0):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics) — jit it with the appropriate shardings.  Works for dense,
    MoE, and pipeline-stacked params alike.

    Mixed precision: f32 master params; the loss closure casts the weight
    tree to ``cfg.compute_dtype`` once (see _cast_weights), autodiff flows
    back through the cast, so grads and the adamw update stay f32.

    ``xent_chunks>0`` enables the chunked fused lm-head cross-entropy for
    HBM-tight configs (see loss_fn)."""

    def train_step(params, opt_state, batch):
        def loss_cast(p):
            return loss_fn(_cast_weights(p, cfg.compute_dtype), batch, cfg,
                           pp_microbatches, xent_chunks)

        loss, grads = jax.value_and_grad(loss_cast)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        return params, opt_state, {"loss": loss}

    return train_step


def num_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def count_flops_per_token(cfg: GPT2Config, seq_len: int) -> float:
    """Training (fwd+bwd) FLOPs per token: 6N + 12*L*E*S (PaLM appendix B).

    N counts matmul params only: 12*L*E^2 for the blocks (c_attn 3E^2 +
    attn c_proj E^2 + mlp 8E^2) plus V*E for the tied lm head (the
    embedding gather is not a matmul).  The 6 covers fwd (2) + bwd (4);
    callers must NOT multiply by 3 again.
    """
    n = 12 * cfg.n_layer * cfg.n_embd ** 2 + cfg.vocab_size * cfg.n_embd
    return 6 * n + 12 * cfg.n_layer * cfg.n_embd * seq_len

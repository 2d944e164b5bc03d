"""DeepSeek-V3-shaped decoders (`model_type` `deepseek_v3`) for the Train
path: latent attention (MLA) and a sigmoid-scored, bias-corrected mixture
of experts with shared experts, after leading dense layers.

DeepSeek-AI, "DeepSeek-V3 Technical Report" (arXiv:2412.19437); layer
equations as the public `modeling_deepseek_v3.py`, for a config without
query compression (`q_lora_rank` null) and one routing group:

  h = x + Attn(RMSNorm(x));  y = h + F(RMSNorm(h));  final RMSNorm; an
  untied head.  F is a SwiGLU in the first `n_dense_layer` layers and the
  mixture after.
  Attn: q = u W_q, a head's [q_nope | q_rope];  [c | k_r] = u W_kv_a;
  c = RMSNorm(c);  a head's [k_nope | v] = c W_kv_b;  RoPE on q_rope of
  every head and on the ONE k_r all heads share;  k = [k_nope | k_r];
  causal softmax of q k' at (nope + rope)^-1/2;  o = P v;  W_o.
  Mixture: s = sigmoid(u W_g) in float32 over all experts; the top k of
  s + b (b the routing bias `e_score_correction_bias`); weights s at the
  chosen, over their sum + 1e-20, times `routed_scale`;
  F(u) = sum w_i E_i(u) + Shared(u), every one a SwiGLU, no token dropped,
  no auxiliary loss.
  b (`noaux_tc`) is no optimizer leaf: after a step
  b_e += speed * sign(mean(n) - n_e), n the rows each expert was sent.

Departures from that description, none of which changes a score or a sum:

- `rope_interleave`: the rotary dims are taken apart ([evens | odds]) and
  rotated as halves, as the public modeling code does, so q_rope and k_r
  hold the pairwise rotation in another order of their dims, the same on
  both (`layers.rope`).
- Training multiplies the latent out: a head's k and v exist.  The latent
  cache and the absorbed weights are serving's and are not here.
- ``held`` = (first, count): one chip's share of an expert-parallel layer.
  The router, the shared experts, attention and the dense layers are
  whole; only the held experts' matrices exist and only their part of the
  sum is computed (`ops/moe.py:moe_dispatch`); what the absent experts
  would add is another chip's, and the exchange that would bring it (and
  sum n over the data-parallel group for the bias rule) is not written.
- `vocab_size` is the rows of embedding and head held here (a slice of the
  vocabulary is a smaller vocabulary).

What it shares with the other models: `models/layers.py` (RMSNorm, RoPE,
the SwiGLU, the routed layer, the walk over the layers, the head and its
chunked loss, the mixed-precision step and its place for state that moves
by a rule), `parallel/attention.py` (the flash kernels, here with q/k and v
of two widths) and `ops/moe.py` (the sigmoid router, its account and its
bias rule); the names are those `parallel/sharding.py` lays out.

`jax.named_scope`s (`models/layers.py:SCOPES`): embed, norm,
attention/{latent_down,latent_up,kernel,out}, ffn/dense,
ffn/moe/{route,dispatch,experts,combine,shared}, head_and_loss,
optimizer_update, routing_bias_update.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.layers import (
    dense_ffn,
    head_and_loss,
    latent_attention,
    normal_kernel,
    num_params,  # noqa: F401  (`deepseek_v3.num_params` is public)
    rms_norm,
    routed_layer,
    swiglu,
    train_step,
    trunk,
    unit_scale,
)
from ray_tpu.ops.moe import (
    ROUTING_BIAS,
    routing_account,
    sigmoid_route,
    trained_by,  # noqa: F401  (`deepseek_v3.trained_by` is public)
)
from ray_tpu.ops.moe import routing_bias_rule as _bias_rule_over


@dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 128256          # rows of embedding and head held here
    n_layer: int = 48
    n_dense_layer: int = 1            # `first_k_dense_replace`
    n_head: int = 32
    n_embd: int = 2048
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    dense_width: int = 6144
    expert_width: int = 768
    shared_width: int = 1536          # n_shared_experts x expert_width
    n_experts: int = 128              # the router's width
    held: Optional[Tuple[int, int]] = None   # (first, count); None: all
    top_k: int = 6
    routed_scale: float = 2.448
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    bias_update_speed: float = 0.001  # gamma of arXiv:2412.19437
    compute_dtype: Any = jnp.bfloat16
    # jax.checkpoint each layer, keeping its attention kernel's output and
    # row statistics and, of `layers.KEPT_NAMES` (here the routers' products,
    # choices and sorted order, the latent, W_o's result, q, the gates and
    # ups of the dense and shared feed-forwards, k and v), those the chip
    # has room for over all layers (`layers.checkpoint_layer`)
    remat: bool = False
    loss_chunk_rows: int = 2048       # `layers.chunked_xent`

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def n_held(self) -> int:
        return self.held[1] if self.held else self.n_experts

    @property
    def moe_layers(self):
        return range(self.n_dense_layer, self.n_layer)


KANANA_2_30B_A3B = DeepseekV3Config()
DEEPSEEK_V3_TINY = DeepseekV3Config(
    vocab_size=512, n_layer=3, n_dense_layer=1, n_head=4, n_embd=64,
    kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
    dense_width=96, expert_width=24, shared_width=48, n_experts=8, top_k=3,
    loss_chunk_rows=32)


def init_params(rng, cfg: DeepseekV3Config) -> Dict[str, Any]:
    """Normal(0, 0.02) matrices, unit norm gains, routing biases 0.  Names
    are those `parallel/sharding.py:infer_param_logical_dims` lays out; the
    experts' stacks hold the `cfg.n_held` experts that live here."""
    E, H, R = cfg.n_embd, cfg.n_head, cfg.kv_lora_rank
    keys = jax.random.split(rng, 2 + cfg.n_layer)

    def mlp(ks, width):
        return {"gate_proj": normal_kernel(ks[0], E, width),
                "up_proj": normal_kernel(ks[1], E, width),
                "down_proj": normal_kernel(ks[2], width, E)}

    params = {
        "embed_tokens": {
            "embedding": normal_kernel(keys[0], cfg.vocab_size, E)["kernel"]},
        "norm_f": unit_scale(E),
        "lm_head": normal_kernel(keys[1], E, cfg.vocab_size),
    }
    for i in range(cfg.n_layer):
        ks = jax.random.split(keys[2 + i], 11)
        layer = {
            "input_norm": unit_scale(E),
            "attn": {
                "q_proj": normal_kernel(ks[0], E, H * cfg.qk_head_dim),
                "kv_a_proj": normal_kernel(ks[1], E, R + cfg.qk_rope_dim),
                "kv_a_norm": unit_scale(R),
                "kv_b_proj": normal_kernel(
                    ks[2], R, H * (cfg.qk_nope_dim + cfg.v_head_dim)),
                "o_proj": normal_kernel(ks[3], H * cfg.v_head_dim, E),
            },
            "post_norm": unit_scale(E),
        }
        if i < cfg.n_dense_layer:
            layer["mlp"] = mlp(ks[4:7], cfg.dense_width)
        else:
            n, W = cfg.n_held, cfg.expert_width
            layer["moe"] = {
                "router": {
                    **normal_kernel(ks[4], E, cfg.n_experts),
                    ROUTING_BIAS: jnp.zeros((cfg.n_experts,), jnp.float32)},
                "wi_gate": normal_kernel(ks[5], n, E, W)["kernel"],
                "wi_up": normal_kernel(ks[6], n, E, W)["kernel"],
                "wo": normal_kernel(ks[7], n, W, E)["kernel"],
                "shared": mlp(ks[8:11], cfg.shared_width),
            }
        params[f"layer_{i}"] = layer
    return params


def _route(cfg: DeepseekV3Config):
    """-> route(xt, router) -> (weights (T, k) f32, experts (T, k) int32)
    over all experts."""
    return functools.partial(sigmoid_route, top_k=cfg.top_k, eps=1e-20,
                             scale=cfg.routed_scale)


def _layer(x, p, cfg: DeepseekV3Config):
    """-> (x, the rows sent to each expert; None from a dense layer)."""
    u = rms_norm(x, p["input_norm"], cfg.rms_eps)
    with jax.named_scope("attention"):
        x = x + latent_attention(u, p["attn"], cfg)
    u = rms_norm(x, p["post_norm"], cfg.rms_eps)
    with jax.named_scope("ffn"):
        if "mlp" in p:
            with jax.named_scope("dense"):
                return x + dense_ffn(u, p["mlp"], swiglu), None
        with jax.named_scope("moe"):
            y, rows = routed_layer(u, p["moe"], _route(cfg), cfg.n_experts,
                                   cfg.held, swiglu)
    return x + y, rows


def _hidden(params, tokens, cfg: DeepseekV3Config):
    """-> ((B, S, E) after the final norm, the routers' statistics)."""
    x, rows = trunk(params, tokens, _layer, cfg)
    return x, routing_account(params, cfg.moe_layers, rows,
                              tokens.size * cfg.top_k, cfg.held)


def forward(params, tokens, cfg: DeepseekV3Config):
    """tokens (B, S) int32 -> (logits (B, S, rows held) f32, routers'
    statistics)."""
    x, stats = _hidden(params, tokens, cfg)
    head = params["lm_head"]["kernel"].astype(cfg.compute_dtype)
    return jnp.matmul(x, head, preferred_element_type=jnp.float32), stats


def loss_fn(params, batch, cfg: DeepseekV3Config):
    """batch {"tokens": (B, S+1)} -> (next-token cross-entropy over the
    rows of the vocabulary held here, its parts: "loss" the same, and the
    routers' statistics).  There is no auxiliary loss.  The head's logits
    are made `cfg.loss_chunk_rows` rows at a time and never all held."""
    tokens = batch["tokens"]
    x, stats = _hidden(params, tokens[:, :-1], cfg)
    xent = head_and_loss(x, params["lm_head"], tokens[:, 1:],
                         cfg.loss_chunk_rows)
    return xent, dict(stats, loss=xent)


def routing_bias_rule(cfg: DeepseekV3Config):
    """`ops/moe.py:routing_bias_rule` over this model's routed layers at
    its `bias_update_speed`."""
    return _bias_rule_over(cfg.moe_layers, cfg.bias_update_speed)


def make_train_step(cfg: DeepseekV3Config, optimizer):
    """train_step(params, opt_state, batch) -> (params, opt_state, out),
    to be jitted with its shardings and `donate_argnums=(0, 1)` as
    `gpt2.make_train_step`'s; ``optimizer`` comes through `trained_by`.
    `out["loss"]` is the cross-entropy; `out` also carries the routers'
    account (`ops/moe.py:routing_account`: "expert_rows", "rows_held",
    "moe_overflow_layers", "max_expert_rows", "max_routing_bias"), device
    values that cost nothing unless fetched."""
    return train_step(lambda params, batch: loss_fn(params, batch, cfg),
                      optimizer, cfg.compute_dtype,
                      rule=routing_bias_rule(cfg))


def count_flops_per_token(cfg: DeepseekV3Config, seq_len: int) -> float:
    """Training (forward + backward) operations per token HERE: 6 x the
    parameters a token multiplies on this chip (the head's rows held; per
    layer W_q, W_kv_a, W_kv_b, W_o; in a routed layer the router, the
    shared experts and the EXPECTED rows of held experts, top_k x held /
    experts of three matrices each; in a dense layer its MLP) + the full
    score squares, 6 L S heads (qk_head_dim + v_head_dim): QK' is
    qk_head_dim deep and PV v_head_dim, forward once and backward twice."""
    E, H = cfg.n_embd, cfg.n_head
    attn = (E * H * cfg.qk_head_dim + E * (cfg.kv_lora_rank + cfg.qk_rope_dim)
            + cfg.kv_lora_rank * H * (cfg.qk_nope_dim + cfg.v_head_dim)
            + H * cfg.v_head_dim * E)
    routed = (E * cfg.n_experts + 3 * E * cfg.shared_width
              + cfg.top_k * cfg.n_held / cfg.n_experts
              * 3 * E * cfg.expert_width)
    n = (cfg.vocab_size * E + cfg.n_layer * attn
         + cfg.n_dense_layer * 3 * E * cfg.dense_width
         + len(cfg.moe_layers) * routed)
    return 6 * n + 6 * cfg.n_layer * seq_len * H * (
        cfg.qk_head_dim + cfg.v_head_dim)

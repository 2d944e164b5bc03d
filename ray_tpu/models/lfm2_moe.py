"""LFM2-MoE decoders (`model_type` `lfm2_moe`: LiquidAI's LFM2-8B-A1B and
LFM2-24B-A2B) for the Train path: two kinds of operator in one stack, a
gated short convolution in most layers and grouped-query attention in the
others, each followed by a feed-forward that is dense in the leading layers
and a sigmoid-scored, bias-corrected mixture of experts after.

Layer equations, from the published `config.json` and the public
`modeling_lfm2_moe.py`.  Pre-norm residual layers, RMSNorm with `norm_eps`,
no bias anywhere (`conv_bias` false):

  h = x + Op_i(RMSNorm(x));  y = h + F_i(RMSNorm(h));  a final RMSNorm; the
  head is the embedding, transposed.
  Op of a `conv` layer, u of (B, S, E): [b | c | z] = u W_in (E x 3E,
    thirds in that order);  g = b * z;  v_t = sum_{j=0..L-1} w_j *
    g_{t-(L-1)+j} with L = `conv_L_cache`, w of (E, L), one filter a
    channel, g zero before the sequence starts (causal: position t sees
    t-2, t-1, t);  Op(u) = (c * v) W_out (E x E).  No activation function.
  Op of a `full_attention` layer: q = u W_q as H heads of D, k = u W_k and
    v = u W_v as H_kv heads of D;  RMSNorm over the D of every q head and
    every k head (one gain vector each);  RoPE on the whole head,
    rotate-half (not interleaved), `rope_theta`;  causal softmax at D^-1/2,
    query head h on key/value head h // (H / H_kv);  W_o.
  F of the first `num_dense_layers` layers: W_2 (silu(W_1 u) * W_3 u).
  F of the others: s = sigmoid(u W_g) in float32 over the experts; the top
    k of s + b (`use_expert_bias`; b picks and does not weigh); weights s at
    the chosen over their sum + 1e-6 (`norm_topk_prob`), times
    `routed_scaling_factor`;  sum w_i E_i(u), each E_i a SwiGLU; no shared
    expert, no token dropped.
  b is no optimizer leaf: after a step b_e += speed * sign(mean(n) - n_e),
  n the rows each expert was sent (the rule is assumed: DeepSeek-V3's).

Which operator and which feed-forward a layer has is read from its
parameters' names (a tree's structure is static), so `jax.checkpoint`
traces one layer per kind and shapes.

``held`` = (first, count): one chip's share of an expert-parallel layer, as
`models/deepseek_v3.py`: the router, both operators and the dense layers
are whole; only the held experts' matrices exist and only their part of the
sum is computed (`ops/moe.py:moe_dispatch`).  `vocab_size` is the rows of
the embedding held here (a slice of the vocabulary is a smaller
vocabulary).

What it shares with the other models: `models/layers.py` (RMSNorm, RoPE,
the projections into and out of attention, the SwiGLU, `short_conv`, the
routed layer, the walk over the layers, the head and its chunked loss, the
mixed-precision step and its place for state that moves by a rule),
`parallel/attention.py` (the flash kernels, here with fewer key/value heads
than query heads) and `ops/moe.py` (dispatch over a share of the experts,
the sigmoid router, its account and its bias rule); the names are those
`parallel/sharding.py` lays out.

`jax.named_scope`s (`models/layers.py:SCOPES`): embed, norm,
short_conv/{in_proj,gate_taps,out_proj}, attention/{qkv,kernel,out},
ffn/dense, ffn/moe/{route,dispatch,experts,combine}, head_and_loss,
optimizer_update, routing_bias_update.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.layers import (
    attention_out,
    attention_qkv,
    dense_ffn,
    head_and_loss,
    normal_kernel,
    num_params,  # noqa: F401  (`lfm2_moe.num_params` is public)
    rms_norm,
    routed_layer,
    short_conv,
    swiglu,
    train_step,
    trunk,
    unit_scale,
)
from ray_tpu.ops.moe import (
    ROUTING_BIAS,
    routing_account,
    routing_bias_rule,
    sigmoid_route,
    trained_by,  # noqa: F401  (`lfm2_moe.trained_by` is public)
)
from ray_tpu.parallel.attention import attention

CONV, ATTENTION = "conv", "full_attention"
# the published order: two leading conv layers, then attention and three
# conv layers, repeating, the last period cut after its first conv layer
_LFM2_24B_LAYERS = (CONV, CONV) + (ATTENTION, CONV, CONV, CONV) * 9 \
    + (ATTENTION, CONV)


@dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536           # rows of the embedding held here
    layer_types: Tuple[str, ...] = _LFM2_24B_LAYERS
    n_dense_layer: int = 2            # `num_dense_layers`
    n_head: int = 32
    n_kv_head: int = 8
    n_embd: int = 2048
    conv_taps: int = 3                # `conv_L_cache`
    dense_width: int = 11776
    expert_width: int = 1536
    n_experts: int = 64               # the router's width
    held: Optional[Tuple[int, int]] = None   # (first, count); None: all
    top_k: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scale: float = 1.0
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    bias_update_speed: float = 0.001  # assumed: arXiv:2412.19437's gamma
    compute_dtype: Any = jnp.bfloat16
    # jax.checkpoint each layer, keeping its attention kernel's output and
    # row statistics and, of `layers.KEPT_NAMES` (here the routers' products,
    # choices and sorted order, a convolution's [b c z], gates-and-taps and
    # W_out results, W_q's, W_k's, W_v's and W_o's results, the dense gate
    # and up), those the chip has room for over all layers
    # (`layers.checkpoint_layer`)
    remat: bool = False
    loss_chunk_rows: int = 2048       # `layers.chunked_xent`

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @property
    def n_held(self) -> int:
        return self.held[1] if self.held else self.n_experts

    @property
    def moe_layers(self):
        return range(self.n_dense_layer, self.n_layer)


LFM2_24B_A2B = Lfm2MoeConfig()
LFM2_MOE_TINY = Lfm2MoeConfig(
    vocab_size=512, layer_types=(CONV, ATTENTION, CONV), n_dense_layer=1,
    n_head=4, n_kv_head=2, n_embd=64, dense_width=96, expert_width=24,
    n_experts=8, top_k=3, loss_chunk_rows=32)


def init_params(rng, cfg: Lfm2MoeConfig) -> Dict[str, Any]:
    """Normal(0, 0.02) matrices, unit norm gains, routing biases 0, and the
    taps uniform(+-L^-1/2): what the public modeling code leaves a depthwise
    `Conv1d` of L taps with, since its `_init_weights` does not touch it.
    Names are those `parallel/sharding.py:infer_param_logical_dims` lays
    out; the experts' stacks hold the `cfg.n_held` experts that live here;
    there is no head: it is the embedding."""
    E, H, Hkv, D = cfg.n_embd, cfg.n_head, cfg.n_kv_head, cfg.head_dim
    keys = jax.random.split(rng, 1 + cfg.n_layer)
    params = {
        "embed_tokens": {
            "embedding": normal_kernel(keys[0], cfg.vocab_size, E)["kernel"]},
        "norm_f": unit_scale(E),
    }
    for i, kind in enumerate(cfg.layer_types):
        ks = jax.random.split(keys[1 + i], 8)
        layer = {"operator_norm": unit_scale(E), "ffn_norm": unit_scale(E)}
        if kind == CONV:
            bound = cfg.conv_taps ** -0.5
            layer["short_conv"] = {
                "in_proj": normal_kernel(ks[0], E, 3 * E),
                "conv": {"kernel": jax.random.uniform(
                    ks[1], (E, cfg.conv_taps), jnp.float32, -bound, bound)},
                "out_proj": normal_kernel(ks[2], E, E),
            }
        else:
            layer["attn"] = {
                "q_proj": normal_kernel(ks[0], E, H * D),
                "k_proj": normal_kernel(ks[1], E, Hkv * D),
                "v_proj": normal_kernel(ks[2], E, Hkv * D),
                "o_proj": normal_kernel(ks[3], H * D, E),
                "q_norm": unit_scale(D),
                "k_norm": unit_scale(D),
            }
        if i < cfg.n_dense_layer:
            layer["mlp"] = {
                "gate_proj": normal_kernel(ks[4], E, cfg.dense_width),
                "up_proj": normal_kernel(ks[5], E, cfg.dense_width),
                "down_proj": normal_kernel(ks[6], cfg.dense_width, E)}
        else:
            n, W = cfg.n_held, cfg.expert_width
            router = normal_kernel(ks[4], E, cfg.n_experts)
            if cfg.use_expert_bias:
                router[ROUTING_BIAS] = jnp.zeros((cfg.n_experts,),
                                                 jnp.float32)
            layer["moe"] = {
                "router": router,
                "wi_gate": normal_kernel(ks[5], n, E, W)["kernel"],
                "wi_up": normal_kernel(ks[6], n, E, W)["kernel"],
                "wo": normal_kernel(ks[7], n, W, E)["kernel"],
            }
        params[f"layer_{i}"] = layer
    return params


def _attention(x, p, cfg: Lfm2MoeConfig):
    q, k, v = attention_qkv(x, p, cfg.head_dim, cfg.rms_eps, jnp.arange,
                            cfg.rope_theta)
    with jax.named_scope("kernel"):
        o = attention(q, k, v)        # 8 key/value heads go in as they are
    return attention_out(o, p)


def _route(cfg: Lfm2MoeConfig):
    """-> route(xt, router) -> (weights (T, k) f32, experts (T, k) int32)
    over all experts."""
    return functools.partial(
        sigmoid_route, top_k=cfg.top_k,
        eps=1e-6 if cfg.norm_topk_prob else None, scale=cfg.routed_scale)


def _layer(x, p, cfg: Lfm2MoeConfig):
    """-> (x, the rows sent to each expert; None from a dense layer)."""
    u = rms_norm(x, p["operator_norm"], cfg.rms_eps)
    if "short_conv" in p:
        with jax.named_scope("short_conv"):
            x = x + short_conv(u, p["short_conv"])
    else:
        with jax.named_scope("attention"):
            x = x + _attention(u, p["attn"], cfg)
    u = rms_norm(x, p["ffn_norm"], cfg.rms_eps)
    with jax.named_scope("ffn"):
        if "mlp" in p:
            with jax.named_scope("dense"):
                return x + dense_ffn(u, p["mlp"], swiglu), None
        with jax.named_scope("moe"):
            y, rows = routed_layer(u, p["moe"], _route(cfg), cfg.n_experts,
                                   cfg.held, swiglu)
    return x + y, rows


def _hidden(params, tokens, cfg: Lfm2MoeConfig):
    """-> ((B, S, E) after the final norm, the routers' statistics)."""
    x, rows = trunk(params, tokens, _layer, cfg)
    return x, routing_account(params, cfg.moe_layers, rows,
                              tokens.size * cfg.top_k, cfg.held)


def forward(params, tokens, cfg: Lfm2MoeConfig):
    """tokens (B, S) int32 -> (logits (B, S, rows held) f32, routers'
    statistics)."""
    x, stats = _hidden(params, tokens, cfg)
    head = params["embed_tokens"]["embedding"].astype(cfg.compute_dtype)
    return jnp.matmul(x, head.T, preferred_element_type=jnp.float32), stats


def loss_fn(params, batch, cfg: Lfm2MoeConfig):
    """batch {"tokens": (B, S+1)} -> (next-token cross-entropy over the
    rows of the vocabulary held here, its parts: "loss" the same, and the
    routers' statistics).  There is no auxiliary loss.  The head's logits
    are made `cfg.loss_chunk_rows` rows at a time and never all held."""
    tokens = batch["tokens"]
    x, stats = _hidden(params, tokens[:, :-1], cfg)
    xent = head_and_loss(x, params["embed_tokens"], tokens[:, 1:],
                         cfg.loss_chunk_rows)
    return xent, dict(stats, loss=xent)


def make_train_step(cfg: Lfm2MoeConfig, optimizer):
    """train_step(params, opt_state, batch) -> (params, opt_state, out),
    to be jitted with its shardings and `donate_argnums=(0, 1)` as
    `gpt2.make_train_step`'s; ``optimizer`` comes through `trained_by`.
    `out` carries "loss" and the routers' account
    (`ops/moe.py:routing_account`), device values that cost nothing unless
    fetched."""
    rule = routing_bias_rule(cfg.moe_layers, cfg.bias_update_speed) \
        if cfg.use_expert_bias else None
    return train_step(lambda params, batch: loss_fn(params, batch, cfg),
                      optimizer, cfg.compute_dtype, rule=rule)


def count_flops_per_token(cfg: Lfm2MoeConfig, seq_len: int) -> float:
    """Training (forward + backward) operations per token HERE: 6 x the
    parameters a token multiplies on this chip (the tied head's rows held;
    a conv operator's W_in, W_out and taps; an attention operator's four
    matrices; a dense layer's feed-forward; in a routed layer the router
    and the EXPECTED rows of held experts, top_k x held / experts of three
    matrices each) + the full score squares of the attention layers alone,
    6 S heads (D + D): QK' and PV forward once and backward twice."""
    E, H, D = cfg.n_embd, cfg.n_head, cfg.head_dim
    conv = 4 * E * E + E * cfg.conv_taps
    attn = 2 * E * H * D + 2 * E * cfg.n_kv_head * D
    routed = E * cfg.n_experts + cfg.top_k * cfg.n_held / cfg.n_experts \
        * 3 * E * cfg.expert_width
    n_attn = cfg.layer_types.count(ATTENTION)
    n = (cfg.vocab_size * E + (cfg.n_layer - n_attn) * conv + n_attn * attn
         + cfg.n_dense_layer * 3 * E * cfg.dense_width
         + len(cfg.moe_layers) * routed)
    return 6 * n + 6 * n_attn * seq_len * H * 2 * D

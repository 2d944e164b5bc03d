"""Mellum 2 (`model_type` `mellum`: JetBrains' Mellum2-12B-A2.5B-Instruct)
for the Train path: a Qwen3-MoE-lineage decoder whose layers are of TWO
kinds in one stack, three that attend a window of the latest keys to one
that attends every earlier key, with rotary tables of their own.

From the model's published `config.json`.  S tokens a sequence; layer l is
`layer_types[l]`, `sliding_attention` or `full_attention` (published:
sliding, sliding, sliding, full, repeating); every layer's feed-forward is
the mixture (`mlp_layer_types` all "sparse"):

  a layer, pre-norm, no bias: u = RMSNorm(x); q = u W_q in H heads, k =
    u W_k and v = u W_v in H_kv; q and k through an RMSNorm over the head's
    D with a gain (the lineage's, which the config has no key for either
    way: the cell's file says why), then rotate-half RoPE over all D BY
    THE LAYER'S KIND; o = softmax(c^2 q k' D^-1/2 over the attended keys) v,
    query head h on key/value head h // (H / H_kv); x += o W_o.
    u = RMSNorm(x); p = softmax(u W_r) in float32 over all the experts; the
    `top_k` largest, their weights over their sum (`norm_topk_prob`);
    x += sum over the chosen experts HELD here of w_e SwiGLU_e(u).
  which keys: a full layer, j <= i.  A sliding layer, i - W < j <= i: the
    W = `sliding_window` latest keys, the row's own among them
    (`ops/flash_attention.py:BlockRule(window=W)`: a second bound of the
    kernels' rule, whose empty tiles are never visited).
  RoPE of a sliding layer: frequencies theta^(-2i/D), c = 1.  Of a full
    layer: YaRN's (`layers.yarn_frequencies`, from `rope_parameters`'
    keys), cos and sin times c = `attention_factor` on q and on k; static,
    at every length.
  loss: the final RMSNorm, the untied head, next-token cross-entropy; the
    objective adds `aux_weight` x L_B, the routers' load-balancing loss
    (Switch Transformer's: `ops/moe.py:balance_loss`), summed over the
    layers.
    `out["loss"]` is the cross-entropy.

A layer's kind is the NAME of its attention subtree (`models/lfm2_moe.py`'s
idiom): both kinds have the same leaves, so nothing but the name tells
them apart, and a name is static: `trunk` walks once and `jax.checkpoint`
traces one body a kind.

``held`` = (first, count): one chip's share of an expert-parallel layer:
router and attention are whole; only the held experts' matrices exist and
only their part of the sum is computed (`ops/moe.py:moe_dispatch`).
`vocab_size` is the rows of embedding and head held here.

What it shares with the other models: `models/layers.py` (RMSNorm, RoPE and
YaRN's table, the projections into and out of attention, the SwiGLU, the
routed layer, the walk over the layers, the head and its chunked loss, the
mixed-precision step), `parallel/attention.py` (the flash kernels, here
under a rule a kind of layer) and `ops/moe.py` (the softmax route, its
balance loss, the routers' account); this file is the configuration, the
table of parameters, `rotary` and `rule` by a layer's kind and the `_layer`.

Not here: a multi-token head (the config has no key for one), and serving
(a cache that holds window and global layers side by side).

`jax.named_scope`s (`models/layers.py:SCOPES`): embed, norm,
attention/{qkv,kernel,out}, ffn/moe/{route,dispatch,experts,combine},
head_and_loss, optimizer_update.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.layers import (
    attention_out,
    attention_qkv,
    head_and_loss,
    normal_kernel,
    num_params,  # noqa: F401  (`mellum.num_params` is public)
    rms_norm,
    routed_layer,
    swiglu,
    train_step,
    trunk,
    unit_scale,
    yarn_frequencies,
)
from ray_tpu.ops.flash_attention import BlockRule
from ray_tpu.ops.moe import balance_loss, routing_account, softmax_route
from ray_tpu.parallel.attention import attention

SLIDING, FULL = "sliding_attention", "full_attention"


class Yarn(NamedTuple):
    """`rope_parameters.full_attention`'s keys (`rope_type` "yarn")."""
    factor: float = 16.0
    original_max_position: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = 1.2772588722239782


@dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304           # rows of embedding and head held here
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 7
    sliding_window: int = 1024
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    n_embd: int = 2304
    expert_width: int = 896
    n_experts: int = 64               # the router's width
    held: Optional[Tuple[int, int]] = None   # (first, count); None: all
    top_k: int = 8
    norm_topk_prob: bool = True
    # the load-balancing loss's weight: the lineage's `router_aux_loss_coef`
    # (Qwen3-MoE's).  A chip's SHARE of the experts may need more, as
    # `models/keye_vl.py` says; the cell's file states what it runs
    aux_weight: float = 0.001
    rope_theta: float = 5e5           # both kinds' base
    yarn: Yarn = Yarn()               # the full layers' scaling
    rms_eps: float = 1e-6
    compute_dtype: Any = jnp.bfloat16
    # jax.checkpoint each layer, keeping its attention kernel's output and
    # row statistics and, of `layers.KEPT_NAMES`, those the chip has room
    # for over all layers (`layers.checkpoint_layer`)
    remat: bool = False
    loss_chunk_rows: int = 2048       # `layers.chunked_xent`

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def n_held(self) -> int:
        return self.held[1] if self.held else self.n_experts

    @property
    def moe_layers(self):
        return range(self.n_layer)


MELLUM2_12B = MellumConfig()
# a window of 48 neither divides a tile nor is divided by one
MELLUM_TINY = MellumConfig(
    vocab_size=512, layer_types=(SLIDING, SLIDING, SLIDING, FULL),
    sliding_window=48, n_head=8, n_kv_head=2, head_dim=16, n_embd=64,
    expert_width=24, n_experts=8, top_k=3,
    yarn=Yarn(factor=4.0, original_max_position=32, attention_factor=None),
    loss_chunk_rows=32)


def init_params(rng, cfg: MellumConfig) -> Dict[str, Any]:
    """Normal(0, 0.02) matrices, unit norm gains.  Names are those
    `parallel/sharding.py:infer_param_logical_dims` lays out; a layer's
    attention subtree is named by its kind; the experts' stacks hold the
    `cfg.n_held` experts that live here."""
    E, H, Hkv, D = cfg.n_embd, cfg.n_head, cfg.n_kv_head, cfg.head_dim
    keys = jax.random.split(rng, 2 + cfg.n_layer)
    params = {
        "embed_tokens": {
            "embedding": normal_kernel(keys[0], cfg.vocab_size, E)["kernel"]},
        "norm_f": unit_scale(E),
        "lm_head": normal_kernel(keys[1], E, cfg.vocab_size),
    }
    for i, kind in enumerate(cfg.layer_types):
        assert kind in (SLIDING, FULL), kind
        ks = jax.random.split(keys[2 + i], 8)
        n, W = cfg.n_held, cfg.expert_width
        params[f"layer_{i}"] = {
            "input_norm": unit_scale(E),
            kind: {
                "q_proj": normal_kernel(ks[0], E, H * D),
                "k_proj": normal_kernel(ks[1], E, Hkv * D),
                "v_proj": normal_kernel(ks[2], E, Hkv * D),
                "o_proj": normal_kernel(ks[3], H * D, E),
                "q_norm": unit_scale(D),
                "k_norm": unit_scale(D),
            },
            "post_norm": unit_scale(E),
            "moe": {
                "router": normal_kernel(ks[4], E, cfg.n_experts),
                "wi_gate": normal_kernel(ks[5], n, E, W)["kernel"],
                "wi_up": normal_kernel(ks[6], n, E, W)["kernel"],
                "wo": normal_kernel(ks[7], n, W, E)["kernel"],
            },
        }
    return params


def rotary(cfg: MellumConfig, kind: str):
    """(what `layers.rope` takes for theta, its scale) of a kind of layer:
    the base alone for a sliding layer, YaRN's table for a full one."""
    if kind == SLIDING:
        return cfg.rope_theta, None
    y = cfg.yarn
    return yarn_frequencies(cfg.head_dim, cfg.rope_theta, y.factor,
                            y.original_max_position, y.beta_fast,
                            y.beta_slow, y.attention_factor)


def rule(cfg: MellumConfig, kind: str) -> BlockRule:
    """The keys a kind of layer attends, as the kernels' rule."""
    return BlockRule(window=cfg.sliding_window if kind == SLIDING else None)


def _attention(x, p, cfg: MellumConfig, kind: str):
    q, k, v = attention_qkv(x, p, cfg.head_dim, cfg.rms_eps, jnp.arange,
                            *rotary(cfg, kind))
    with jax.named_scope("kernel"):
        o = attention(q, k, v, causal=rule(cfg, kind))
    return attention_out(o, p)


def _route(cfg: MellumConfig):
    """-> route(xt, router) -> (weights (T, k) f32, experts (T, k) int32,
    the softmax's mean over the rows (N,)) over all experts."""
    return functools.partial(softmax_route, top_k=cfg.top_k,
                             renormalise=cfg.norm_topk_prob)


def _layer(x, p, cfg: MellumConfig):
    """-> (x, {the rows sent to each expert, the router's load-balancing
    loss}); the layer's kind is the name of its attention subtree."""
    kind = SLIDING if SLIDING in p else FULL
    u = rms_norm(x, p["input_norm"], cfg.rms_eps)
    with jax.named_scope("attention"):
        y = _attention(u, p[kind], cfg, kind)
    x = x + y
    u = rms_norm(x, p["post_norm"], cfg.rms_eps)
    with jax.named_scope("ffn"), jax.named_scope("moe"):
        y, rows, mean_prob = routed_layer(u, p["moe"], _route(cfg),
                                          cfg.n_experts, cfg.held, swiglu)
        balance = balance_loss(rows, mean_prob,
                               u.shape[0] * u.shape[1] * cfg.top_k)
    return x + y, {"rows": rows, "aux_loss": balance}


def _hidden(params, tokens, cfg: MellumConfig):
    """-> ((B, S, E) after the final norm, the routers' statistics and
    load-balancing loss)."""
    x, seconds = trunk(params, tokens, _layer, cfg)
    stats = routing_account(params, cfg.moe_layers,
                            [s["rows"] for s in seconds],
                            tokens.size * cfg.top_k, cfg.held)
    return x, dict(stats, aux_loss=sum(s["aux_loss"] for s in seconds))


def forward(params, tokens, cfg: MellumConfig):
    """tokens (B, S) int32 -> (logits (B, S, rows held) f32, the routers'
    statistics)."""
    x, stats = _hidden(params, tokens, cfg)
    head = params["lm_head"]["kernel"].astype(cfg.compute_dtype)
    return jnp.matmul(x, head, preferred_element_type=jnp.float32), stats


def loss_fn(params, batch, cfg: MellumConfig):
    """batch {"tokens": (B, S + 1)} -> (the objective, next-token
    cross-entropy + `cfg.aux_weight` x L_B; its parts: "loss" the
    cross-entropy, "aux_loss" L_B, the routers' statistics).  The head's
    logits are made `cfg.loss_chunk_rows` rows at a time and never all
    held."""
    tokens = batch["tokens"]
    x, stats = _hidden(params, tokens[:, :-1], cfg)
    xent = head_and_loss(x, params["lm_head"], tokens[:, 1:],
                         cfg.loss_chunk_rows)
    return xent + cfg.aux_weight * stats["aux_loss"], dict(stats, loss=xent)


def make_train_step(cfg: MellumConfig, optimizer):
    """train_step(params, opt_state, batch) -> (params, opt_state, out),
    to be jitted with its shardings and `donate_argnums=(0, 1)` as
    `gpt2.make_train_step`'s.  `out["loss"]` is the cross-entropy,
    `out["aux_loss"]` the routers' load-balancing loss; beside them the
    routers' account (`ops/moe.py:routing_account`), device values that
    cost nothing unless fetched."""
    return train_step(lambda params, batch: loss_fn(params, batch, cfg),
                      optimizer, cfg.compute_dtype)


def attended_pairs(seq_len: int, window: Optional[int]) -> int:
    """(query, key) pairs a sequence attends, a head: the triangle, or
    under a window the triangle of its first W rows and W a row after."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def count_flops_per_token(cfg: MellumConfig, seq_len: int) -> float:
    """Training (forward + backward) operations per token trained HERE,
    the work the model asks for whatever implements it: 6 x the parameters
    a token multiplies on this chip (a layer's four attention matrices, the
    router and the EXPECTED rows of held experts, top_k x held / experts of
    three matrices each; the head's rows held) + per layer the attention
    products over the pairs ITS KIND attends (QK' and PV forward once and
    backward twice, 2 D operations a pair and head each)."""
    E, H, D = cfg.n_embd, cfg.n_head, cfg.head_dim
    attn = 2 * E * H * D + 2 * E * cfg.n_kv_head * D
    routed = E * cfg.n_experts + cfg.top_k * cfg.n_held / cfg.n_experts \
        * 3 * E * cfg.expert_width
    n = cfg.n_layer * (attn + routed) + cfg.vocab_size * E
    pairs = sum(attended_pairs(
        seq_len, cfg.sliding_window if kind == SLIDING else None)
        for kind in cfg.layer_types) / seq_len
    return 6 * n + 6 * pairs * H * 2 * D

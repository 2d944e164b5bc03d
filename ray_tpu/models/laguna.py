"""Laguna (`model_type` `laguna`: poolside's Laguna-XS.2, "33B-A3B") for the
Train path: a decoder whose layers are of TWO kinds in one stack, one that
attends every earlier key to three that attend a window of the latest, and
the kinds differ in more than their rule: in their number of query heads,
in their rotary table and in how much of a head it turns.  Attention's
result is gated a head before W_o; the first layer's feed-forward is dense
and the others' a sigmoid-scored mixture beside a shared expert.

From the model's published `config.json`.  S tokens a sequence; layer l is
`layer_types[l]`, `full_attention` or `sliding_attention` (published: full,
sliding, sliding, sliding, repeating) with H_l =
`num_attention_heads_per_layer[l]` query heads (48 on a full layer, 64 on
a sliding one) on H_kv = 8 key/value heads of D = 128; its feed-forward is
`mlp_layer_types[l]`, `dense` (layer 0) or `sparse`.  Pre-norm, no bias:

  u = RMSNorm(x); q = u W_q in H_l heads, k = u W_k and v = u W_v in H_kv.
  RoPE by the layer's kind, rotate-half.  Sliding: all D dims, frequencies
    theta_s^(-2i/D), c = 1.  Full: the FIRST R = `partial_rotary_factor` D
    dims of each head turn and the last D - R pass as they are; the
    frequencies are YaRN's at dim R (`layers.yarn_frequencies(R, theta_f,
    ...)`), cos and sin times c = `attention_factor` on q and on k, so the
    rotated dims' part of a score carries c squared and the passed dims'
    none; static, at every length.
  o_h = softmax(q_h k'_{h // (H_l / H_kv)} D^-1/2 over the attended keys) v.
    Full: j <= i.  Sliding: i - W < j <= i, the W = `sliding_window` latest
    keys, the row's own among them (`ops/flash_attention.py:BlockRule(
    window=W)`).
  g = sigmoid(u W_g) in float32, W_g (E, H_l): one scalar a head and token
    (`gating`; `layers.attention_out`); o_h <- g_h o_h;
    x += [o_1 .. o_{H_l}] W_o.
  u = RMSNorm(x).  A dense layer: x += SwiGLU(u) at `dense_width`.  A
    sparse one: s = sigmoid(u W_r) in float32 over all the experts; the
    `top_k` largest of s + b (b the routing bias, which picks and does not
    weigh); w = `routed_scale` s_e / the sum of the chosen s;
    x += SwiGLU_shared(u) + sum over the chosen experts HELD here of
    w_e SwiGLU_e(u).
  b is no optimizer leaf: after a step b_e += speed * sign(mean(n) - n_e),
    n the rows each expert was sent (`ops/moe.py:routing_bias_rule`,
    DeepSeek-V3's auxiliary-loss-free balancing, arXiv:2412.19437, whose
    router this is to the digit: 256 routed, 8 a token, 1 shared, x 2.5).
  loss: the final RMSNorm, the untied head, next-token cross-entropy.

What the config has no key for and this file chooses (the cell's file says
why, under `assumed`): the gate is per head and reads the layer's normed
input; the router is the sigmoid one with its weights over their sum; no
norm over q's and k's heads; no gate on the shared expert.

A layer's kind is the NAME of its attention subtree (`models/mellum.py`'s
idiom) and its feed-forward the leaves it is given (`models/
deepseek_v3.py`'s): both static, so `trunk` walks once and `jax.checkpoint`
traces one body a shape of layer.  The two kinds' leaves have other SHAPES
(W_q, W_o and W_g by H_l), which `layers.keep_plan` keys its marks by.

``held`` = (first, count): one chip's share of an expert-parallel layer:
router, shared expert, attention and the dense layer are whole; only the
held experts' matrices exist and only their part of the sum is computed
(`ops/moe.py:moe_dispatch`).  `vocab_size` is the rows of embedding and
head held here.

What it shares with the other models: `models/layers.py` (RMSNorm, RoPE and
YaRN's table, the projections into attention with the rotated width, the
gate and W_o out of it, the SwiGLU, the routed layer, the walk over the
layers, the head and its chunked loss, the mixed-precision step and its
place for state that moves by a rule), `parallel/attention.py` (the flash
kernels, here under a rule and with a head count a kind of layer) and
`ops/moe.py` (the sigmoid route, its account and its bias rule); this file
is the configuration, the table of parameters, `heads`, `rotary` and `rule`
by a layer's kind and the `_layer`.

Not here: serving (a cache that holds two head counts and a window side by
side).

`jax.named_scope`s (`models/layers.py:SCOPES`): embed, norm,
attention/{qkv,kernel,gate,out}, ffn/dense,
ffn/moe/{route,dispatch,experts,combine,shared}, head_and_loss,
optimizer_update, routing_bias_update.
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
    dense_ffn,
    head_and_loss,
    normal_kernel,
    num_params,  # noqa: F401  (`laguna.num_params` is public)
    rms_norm,
    routed_layer,
    swiglu,
    train_step,
    trunk,
    unit_scale,
    yarn_frequencies,
)
from ray_tpu.ops.flash_attention import BlockRule
from ray_tpu.ops.moe import (
    ROUTING_BIAS,
    routing_account,
    sigmoid_route,
    trained_by,  # noqa: F401  (`laguna.trained_by` is public)
)
from ray_tpu.ops.moe import routing_bias_rule as _bias_rule_over
from ray_tpu.parallel.attention import attention

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


class Yarn(NamedTuple):
    """`rope_parameters.full_attention`'s keys (`rope_type` "yarn")."""
    factor: float = 64.0
    original_max_position: int = 4096
    beta_fast: float = 64.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = 1.4158883083359672


@dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352          # rows of embedding and head held here
    layer_types: Tuple[str, ...] = (FULL, SLIDING, SLIDING, SLIDING) * 10
    mlp_layer_types: Tuple[str, ...] = (DENSE,) + (SPARSE,) * 39
    n_head_full: int = 48             # `num_attention_heads_per_layer`,
    n_head_sliding: int = 64          # which goes by a layer's kind
    n_kv_head: int = 8
    head_dim: int = 128
    n_embd: int = 2048
    sliding_window: int = 512
    dense_width: int = 8192           # `intermediate_size`: layer 0's alone
    expert_width: int = 512
    shared_width: int = 512
    n_experts: int = 256              # the router's width
    held: Optional[Tuple[int, int]] = None   # (first, count); None: all
    top_k: int = 8
    routed_scale: float = 2.5
    theta_full: float = 5e5           # each kind's base
    theta_sliding: float = 1e4
    rotary_full: int = 64             # `partial_rotary_factor` x head_dim:
    rotary_sliding: int = 128         # the first dims of a head that turn
    yarn: Yarn = Yarn()               # the full layers' scaling
    rms_eps: float = 1e-6
    bias_update_speed: float = 0.001  # gamma of arXiv:2412.19437
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
        return [i for i, kind in enumerate(self.mlp_layer_types)
                if kind == SPARSE]


LAGUNA_XS_2 = LagunaConfig()
# a window of 48 neither divides a tile nor is divided by one; groups of 3
# and of 4 query heads; half a head of 16 turns under YaRN at dim 8
LAGUNA_TINY = LagunaConfig(
    vocab_size=512, layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
    mlp_layer_types=(DENSE,) + (SPARSE,) * 4, n_head_full=6,
    n_head_sliding=8, n_kv_head=2, head_dim=16, n_embd=64, sliding_window=48,
    dense_width=96, expert_width=24, shared_width=24, n_experts=8, top_k=3,
    rotary_full=8, rotary_sliding=16,
    yarn=Yarn(factor=4.0, original_max_position=32, beta_fast=8.0,
              attention_factor=None),
    loss_chunk_rows=32)


def heads(cfg: LagunaConfig, kind: str) -> int:
    """The query heads of a kind of layer."""
    return cfg.n_head_sliding if kind == SLIDING else cfg.n_head_full


def init_params(rng, cfg: LagunaConfig) -> Dict[str, Any]:
    """Normal(0, 0.02) matrices, unit norm gains, routing biases 0.  Names
    are those `parallel/sharding.py:infer_param_logical_dims` lays out; a
    layer's attention subtree is named by its kind and has that kind's
    heads; its feed-forward is "mlp" or "moe" by `mlp_layer_types`; the
    experts' stacks hold the `cfg.n_held` experts that live here."""
    E, Hkv, D = cfg.n_embd, cfg.n_kv_head, cfg.head_dim
    assert len(cfg.mlp_layer_types) == cfg.n_layer, cfg.mlp_layer_types
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
    for i, (kind, ffn) in enumerate(zip(cfg.layer_types,
                                        cfg.mlp_layer_types)):
        assert kind in (FULL, SLIDING) and ffn in (DENSE, SPARSE), (kind, ffn)
        ks = jax.random.split(keys[2 + i], 12)
        H = heads(cfg, kind)
        layer = {
            "input_norm": unit_scale(E),
            kind: {
                "q_proj": normal_kernel(ks[0], E, H * D),
                "k_proj": normal_kernel(ks[1], E, Hkv * D),
                "v_proj": normal_kernel(ks[2], E, Hkv * D),
                "g_proj": normal_kernel(ks[3], E, H),
                "o_proj": normal_kernel(ks[4], H * D, E),
            },
            "post_norm": unit_scale(E),
        }
        if ffn == DENSE:
            layer["mlp"] = mlp(ks[5:8], cfg.dense_width)
        else:
            n, W = cfg.n_held, cfg.expert_width
            layer["moe"] = {
                "router": {
                    **normal_kernel(ks[5], E, cfg.n_experts),
                    ROUTING_BIAS: jnp.zeros((cfg.n_experts,), jnp.float32)},
                "wi_gate": normal_kernel(ks[6], n, E, W)["kernel"],
                "wi_up": normal_kernel(ks[7], n, E, W)["kernel"],
                "wo": normal_kernel(ks[8], n, W, E)["kernel"],
                "shared": mlp(ks[9:12], cfg.shared_width),
            }
        params[f"layer_{i}"] = layer
    return params


def rotary(cfg: LagunaConfig, kind: str):
    """(what `layers.rope` takes for theta, its scale, the first dims of a
    head that turn) of a kind of layer: its own base over the whole head
    for a sliding layer, YaRN's table at the rotated width for a full
    one."""
    if kind == SLIDING:
        return cfg.theta_sliding, None, cfg.rotary_sliding
    y = cfg.yarn
    return (*yarn_frequencies(cfg.rotary_full, cfg.theta_full, y.factor,
                              y.original_max_position, y.beta_fast,
                              y.beta_slow, y.attention_factor),
            cfg.rotary_full)


def rule(cfg: LagunaConfig, kind: str) -> BlockRule:
    """The keys a kind of layer attends, as the kernels' rule."""
    return BlockRule(window=cfg.sliding_window if kind == SLIDING else None)


def _attention(u, p, cfg: LagunaConfig, kind: str):
    """u: the layer's normed input, which the gate reads too."""
    q, k, v = attention_qkv(u, p, cfg.head_dim, None, jnp.arange,
                            *rotary(cfg, kind))
    with jax.named_scope("kernel"):
        o = attention(q, k, v, causal=rule(cfg, kind))
    return attention_out(o, p, gate_input=u)


def _route(cfg: LagunaConfig):
    """-> route(xt, router) -> (weights (T, k) f32, experts (T, k) int32)
    over all experts."""
    return functools.partial(sigmoid_route, top_k=cfg.top_k, eps=1e-20,
                             scale=cfg.routed_scale)


def _layer(x, p, cfg: LagunaConfig):
    """-> (x, the rows sent to each expert; None from a dense layer); the
    layer's kind is the name of its attention subtree."""
    kind = SLIDING if SLIDING in p else FULL
    u = rms_norm(x, p["input_norm"], cfg.rms_eps)
    with jax.named_scope("attention"):
        x = x + _attention(u, p[kind], cfg, kind)
    u = rms_norm(x, p["post_norm"], cfg.rms_eps)
    with jax.named_scope("ffn"):
        if "mlp" in p:
            with jax.named_scope("dense"):
                return x + dense_ffn(u, p["mlp"], swiglu), None
        with jax.named_scope("moe"):
            y, rows = routed_layer(u, p["moe"], _route(cfg), cfg.n_experts,
                                   cfg.held, swiglu)
    return x + y, rows


def _hidden(params, tokens, cfg: LagunaConfig):
    """-> ((B, S, E) after the final norm, the routers' statistics)."""
    x, rows = trunk(params, tokens, _layer, cfg)
    return x, routing_account(params, cfg.moe_layers, rows,
                              tokens.size * cfg.top_k, cfg.held)


def forward(params, tokens, cfg: LagunaConfig):
    """tokens (B, S) int32 -> (logits (B, S, rows held) f32, the routers'
    statistics)."""
    x, stats = _hidden(params, tokens, cfg)
    head = params["lm_head"]["kernel"].astype(cfg.compute_dtype)
    return jnp.matmul(x, head, preferred_element_type=jnp.float32), stats


def loss_fn(params, batch, cfg: LagunaConfig):
    """batch {"tokens": (B, S + 1)} -> (next-token cross-entropy over the
    rows of the vocabulary held here, its parts: "loss" the same, and the
    routers' statistics).  There is no auxiliary loss.  The head's logits
    are made `cfg.loss_chunk_rows` rows at a time and never all held."""
    tokens = batch["tokens"]
    x, stats = _hidden(params, tokens[:, :-1], cfg)
    xent = head_and_loss(x, params["lm_head"], tokens[:, 1:],
                         cfg.loss_chunk_rows)
    return xent, dict(stats, loss=xent)


def routing_bias_rule(cfg: LagunaConfig):
    """`ops/moe.py:routing_bias_rule` over this model's sparse layers at
    its `bias_update_speed`."""
    return _bias_rule_over(cfg.moe_layers, cfg.bias_update_speed)


def make_train_step(cfg: LagunaConfig, optimizer):
    """train_step(params, opt_state, batch) -> (params, opt_state, out),
    to be jitted with its shardings and `donate_argnums=(0, 1)` as
    `gpt2.make_train_step`'s; ``optimizer`` comes through `trained_by`.
    `out["loss"]` is the cross-entropy; `out` also carries the routers'
    account (`ops/moe.py:routing_account`), device values that cost
    nothing unless fetched."""
    return train_step(lambda params, batch: loss_fn(params, batch, cfg),
                      optimizer, cfg.compute_dtype,
                      rule=routing_bias_rule(cfg))


def attended_pairs(seq_len: int, window: Optional[int]) -> int:
    """(query, key) pairs a sequence attends, a head: the triangle, or
    under a window the triangle of its first W rows and W a row after."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def count_flops_per_token(cfg: LagunaConfig, seq_len: int) -> float:
    """Training (forward + backward) operations per token trained HERE, the
    work the model asks for whatever implements it: 6 x the parameters a
    token multiplies on this chip (a layer's attention matrices at ITS
    heads, the gate's among them; a dense layer's MLP; in a sparse layer
    the router, the shared expert and the EXPECTED rows of held experts,
    top_k x held / experts of three matrices each; the head's rows held) +
    per layer the attention products over the pairs ITS KIND attends at ITS
    heads (QK' and PV forward once and backward twice, 2 D operations a
    pair and head each)."""
    E, D = cfg.n_embd, cfg.head_dim
    routed = (E * cfg.n_experts + 3 * E * cfg.shared_width
              + cfg.top_k * cfg.n_held / cfg.n_experts
              * 3 * E * cfg.expert_width)
    n = cfg.vocab_size * E
    products = 0.0
    for kind, ffn in zip(cfg.layer_types, cfg.mlp_layer_types):
        H = heads(cfg, kind)
        n += 2 * E * H * D + 2 * E * cfg.n_kv_head * D + E * H
        n += 3 * E * cfg.dense_width if ffn == DENSE else routed
        products += attended_pairs(
            seq_len, cfg.sliding_window if kind == SLIDING else None) \
            / seq_len * H * 2 * D
    return 6 * n + 6 * products

"""Ling-3.0-flash (`model_type` `bailing_hybrid`, inclusionAI) for the Train
path: a decoder whose mixers are of TWO kinds by the published index, Kimi
Delta Attention (Kimi Linear, arXiv:2510.26692) in five layers of six and
latent attention (MLA) in the sixth, over a mixture of sigmoid-routed experts
picked in groups, after leading dense layers.

The equations (u the normed input, H heads of K = V = 128, E the stream):

  every layer:  h = x + Mix(RMSNorm(x));  y = h + F(RMSNorm(h));  a final
    RMSNorm; an untied head.  F a SwiGLU in the leading dense layers, the
    mixture after.  Layer i (published) is MLA where (i + 1) % `layer_group`
    == 0 and KDA otherwise.
  KDA mixer, no position embedding:
    q, k, v = SiLU(conv4(u W_q)), SiLU(conv4(u W_k)), SiLU(conv4(u W_v)): a
    causal depthwise convolution of 4 taps a channel, no bias; q and k then
    L2-normalised over a head's 128, x * rsqrt(sum x^2 + 1e-6), and q times
    128^-1/2;
    the decay, a KEY CHANNEL: g_t = -5 sigmoid(exp(A_log_h) * (u W_f +
    dt_bias)) in float32, (H x 128) a position in (-5, 0), -5 the published
    `kda_lower_bound` (`gate_bound`); alpha_t = exp(g_t);
    beta_t = sigmoid(u W_b), a scalar a head;
    the state S (K x V a head, float32, zero where the sequence starts):
      S_t = (I - beta_t k_t k_t') Diag(alpha_t) S_{t-1} + beta_t k_t v_t';
      o_t = S_t' q_t            (`ops/kda.py`: chunked, a kernel a pass);
    out = (RMSNorm over each head's 128 of o_t, one gain of 128 a layer)
      * sigmoid(u W_g), W_g (E, H x 128); then W_o.
  MLA mixer: `layers.latent_attention` (DeepSeek-V3's: no query compression,
    the latent with its RMSNorm, one shared rotary key part, interleaved
    pairs, softmax at (128 + 64)^-1/2), its result gated a head by
    sigmoid(u W_gate) before W_o (`layers.attention_out`).
  Mixture: s = sigmoid(u W_r) in float32 over all N experts; c = s + b; a
    group (N / `n_group` consecutive experts) scores the sum of its two
    largest c; the `topk_group` best groups stay; the top k of c among
    theirs; weights s at the chosen over their sum + 1e-20, times
    `routed_scale`; + one shared SwiGLU.  b (`noaux_tc`) moves by
    `ops/moe.py:routing_bias_rule`, no optimizer leaf.

What a chip holds (`benchmark/configs/ling-3.0-flash-ep64.json`): ``n_head``
is the heads HELD here of both mixers (`n_head_published` the model's): a
KDA head's state, gates and norm and an MLA head's q, k, v and gate are its
own, so the chip computes its heads' part of W_o's sum and nothing stands in
for the rest; the latent's down-projection, the router, the shared expert
and the dense feed-forward are whole.  ``held`` = (first, count) of the
experts as `models/deepseek_v3.py`'s.  `vocab_size` is the rows of embedding
and head held.  `n_dense_layer` leading dense layers are held (published 0
..), then routed layers from the published index `first_layer` on.

The multi-token module is not built: its published loss weight is 0.

`jax.named_scope`s (`models/layers.py:SCOPES`): embed, norm,
kda/{proj,conv,gate,rule,gate_norm,out_proj}, attention/{latent_down,
latent_up,kernel,gate,out}, ffn/dense, ffn/moe/{route,dispatch,experts,
combine,shared}, head_and_loss, optimizer_update, routing_bias_update.
Counted on the job timeline as the step is traced: `kda.layers`,
`kda.rule_kernel`, `kda.rule_plain`, `kda.bwd_kernel`, `kda.kernel_passes`,
`kda.heads_per_step` (`ops/kda.py`),
`kda.head_norm_rows_fused` (`ops/gated_norm.py`: the rows of q's and k's L2
norms and of the head's norm whose kernels ran, three calls a traced KDA
layer), `moe.route_groups` (`ops/moe.py`), `attention.gated`.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.layers import (
    causal_conv,
    dense_ffn,
    head_and_loss,
    latent_attention,
    named,
    normal_kernel,
    num_params,  # noqa: F401  (`bailing_hybrid.num_params` is public)
    rms_norm,
    routed_layer,
    swiglu,
    train_step,
    trunk,
    unit_scale,
)
from ray_tpu.ops.gated_norm import head_rms_norm
from ray_tpu.ops.kda import kda
from ray_tpu.ops.moe import (
    ROUTING_BIAS,
    routing_account,
    sigmoid_route,
    trained_by,  # noqa: F401  (`bailing_hybrid.trained_by` is public)
)
from ray_tpu.ops.moe import routing_bias_rule as _bias_rule_over

KDA, MLA = "kda", "attn"


@dataclass(frozen=True)
class BailingHybridConfig:
    vocab_size: int = 157184          # rows of embedding and head held here
    n_layer: int = 42                 # layers held here
    n_dense_layer: int = 2            # leading dense layers held
    first_layer: int = 2              # published index of the first routed
                                      # layer held
    layer_group: int = 6              # `layer_group_size`: MLA every sixth
    n_embd: int = 2560
    n_head: int = 32                  # heads HELD here, of both mixers
    n_head_published: int = 32
    head_dim: int = 128               # a KDA head's keys and values
    conv_taps: int = 4                # `short_conv_kernel_size`
    gate_bound: float = -5.0          # `kda_lower_bound`
    kda_chunk: int = 64
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    dense_width: int = 6144
    expert_width: int = 768
    shared_width: int = 768
    n_experts: int = 512              # the router's width
    held: Optional[Tuple[int, int]] = None   # (first, count); None: all
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scale: float = 2.5
    rope_theta: float = 6e6
    rms_eps: float = 1e-6
    l2_eps: float = 1e-6
    bias_update_speed: float = 0.001
    compute_dtype: Any = jnp.bfloat16
    # jax.checkpoint each layer, keeping its attention kernel's residuals
    # and, of `layers.KEPT_NAMES`, what the chip has room for
    # (`layers.checkpoint_layer`)
    remat: bool = True
    loss_chunk_rows: int = 2048       # `layers.chunked_xent`

    @property
    def n_held(self) -> int:
        return self.held[1] if self.held else self.n_experts

    @property
    def moe_layers(self):
        return range(self.n_dense_layer, self.n_layer)

    def published(self, i: int) -> int:
        """The published index of the layer held at ``i``."""
        return i if i < self.n_dense_layer \
            else self.first_layer + i - self.n_dense_layer

    def kind(self, i: int) -> str:
        """The mixer of the layer held at ``i``, by its published index."""
        return MLA if (self.published(i) + 1) % self.layer_group == 0 else KDA


LING_3_FLASH = BailingHybridConfig()
# a leading dense layer and a whole period: published 0, then 2..7 (the
# fourth of them, published 5, is MLA), 2 of 4 heads, 4 of 16 experts
BAILING_HYBRID_TINY = BailingHybridConfig(
    vocab_size=512, n_layer=7, n_dense_layer=1, first_layer=2, n_embd=64,
    n_head=2, n_head_published=4, head_dim=16, kda_chunk=32, kv_lora_rank=32,
    qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, dense_width=96,
    expert_width=24, shared_width=24, n_experts=16, held=(4, 4), top_k=4,
    n_group=4, topk_group=2, loss_chunk_rows=32)


def _mlp(ks, E, width):
    return {"gate_proj": normal_kernel(ks[0], E, width),
            "up_proj": normal_kernel(ks[1], E, width),
            "down_proj": normal_kernel(ks[2], width, E)}


def _inverse_softplus(dt):
    return dt + jnp.log(-jnp.expm1(-dt))


def init_params(rng, cfg: BailingHybridConfig) -> Dict[str, Any]:
    """Normal(0, 0.02) matrices, unit norm gains, routing biases 0; a KDA
    mixer's taps uniform(+-taps^-1/2) as a depthwise `Conv1d` is left,
    A_log = log of uniform(1, 16) a head, dt_bias the inverse softplus of dt
    drawn log-uniform in [0.001, 0.1] (the open linear-attention layers'
    start: a slow decay).
    Names are those `parallel/sharding.py:infer_param_logical_dims` lays
    out; the heads' matrices are as wide as the `cfg.n_head` held, the
    experts' stacks hold the `cfg.n_held` that live here."""
    E, H, D = cfg.n_embd, cfg.n_head, cfg.head_dim
    R, taps = cfg.kv_lora_rank, cfg.conv_taps
    keys = jax.random.split(rng, 2 + cfg.n_layer)
    params = {
        "embed_tokens": {
            "embedding": normal_kernel(keys[0], cfg.vocab_size, E)["kernel"]},
        "norm_f": unit_scale(E),
        "lm_head": normal_kernel(keys[1], E, cfg.vocab_size),
    }
    for i in range(cfg.n_layer):
        ks = jax.random.split(keys[2 + i], 14)
        layer = {"input_norm": unit_scale(E), "post_norm": unit_scale(E)}
        if cfg.kind(i) == KDA:
            bound = taps ** -0.5
            layer[KDA] = {
                # [q | k | v], each H x D wide: the convolution's channels
                "qkv_proj": normal_kernel(ks[0], E, 3 * H * D),
                "conv": {"kernel": jax.random.uniform(
                    ks[1], (3 * H * D, taps), jnp.float32, -bound, bound)},
                "f_proj": normal_kernel(ks[2], E, H * D),
                "A_log": jnp.log(jax.random.uniform(
                    ks[9], (H,), jnp.float32, 1.0, 16.0)),
                "dt_bias": _inverse_softplus(jnp.exp(jax.random.uniform(
                    ks[10], (H * D,), jnp.float32, math.log(0.001),
                    math.log(0.1)))),
                "b_proj": normal_kernel(ks[3], E, H),
                "g_proj": normal_kernel(ks[11], E, H * D),
                "head_norm": unit_scale(D),
                "o_proj": normal_kernel(ks[12], H * D, E),
            }
        else:
            layer[MLA] = {
                "q_proj": normal_kernel(
                    ks[0], E, H * (cfg.qk_nope_dim + cfg.qk_rope_dim)),
                "kv_a_proj": normal_kernel(ks[1], E, R + cfg.qk_rope_dim),
                "kv_a_norm": unit_scale(R),
                "kv_b_proj": normal_kernel(
                    ks[2], R, H * (cfg.qk_nope_dim + cfg.v_head_dim)),
                "g_proj": normal_kernel(ks[3], E, H),
                "o_proj": normal_kernel(ks[12], H * cfg.v_head_dim, E),
            }
        if i < cfg.n_dense_layer:
            layer["mlp"] = _mlp(ks[4:7], E, cfg.dense_width)
        else:
            n, W = cfg.n_held, cfg.expert_width
            layer["moe"] = {
                "router": {
                    **normal_kernel(ks[4], E, cfg.n_experts),
                    ROUTING_BIAS: jnp.zeros((cfg.n_experts,), jnp.float32)},
                "wi_gate": normal_kernel(ks[5], n, E, W)["kernel"],
                "wi_up": normal_kernel(ks[6], n, E, W)["kernel"],
                "wo": normal_kernel(ks[7], n, W, E)["kernel"],
                "shared": _mlp(ks[8:11], E, cfg.shared_width),
            }
        params[f"layer_{i}"] = layer
    return params


def _l2(x, eps):
    """x (B, S, H, D) over each head's D, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _bounded(f, rate, bias, bound):
    """bound sigmoid(rate (f + bias)) in float32: f (B, S, C) rows, rate and
    bias (C,) a lane.  Its own backward rule for the two vectors' gradients,
    the columns' sums of (rows, C) products: as `jnp.sum`s XLA took them
    over a (B, S, H, D) view whose float32 tiles it re-laid the rows for,
    and pulled the replayed map into the same view (PERF.md section 6, PR
    66); as products with a row of ones they ride the pass that makes df."""
    return bound * jax.nn.sigmoid(rate * (f.astype(jnp.float32) + bias))


def _bounded_fwd(f, rate, bias, bound):
    return _bounded(f, rate, bias, bound), (f, rate, bias)


def _bounded_bwd(bound, inputs, dg):
    f, rate, bias = inputs
    C = f.shape[-1]
    x = f.astype(jnp.float32) + bias
    s = jax.nn.sigmoid(rate * x)
    t = dg * (bound * s * (1 - s))
    dx = t * rate
    ones = jnp.ones((1, f.size // C), jnp.float32)
    sums = lambda v: jnp.dot(ones, v.reshape(-1, C), precision="highest")[0]
    return dx.astype(f.dtype), sums(t * x), sums(dx)


_bounded.defvjp(_bounded_fwd, _bounded_bwd)


def _decay(f, p, cfg: BailingHybridConfig):
    """u W_f (B, S, H D) -> g (B, S, H, D) float32, the log of the decay a
    key channel, in (`gate_bound`, 0)."""
    B, S, _ = f.shape
    H, D = cfg.n_head, cfg.head_dim
    g = _bounded(f, jnp.repeat(jnp.exp(p["A_log"]), D), p["dt_bias"],
                 cfg.gate_bound)
    return g.reshape(B, S, H, D)


def _head_norm(o, gain, eps):
    """An RMSNorm over each head's D of o (B, S, H, D), float32."""
    o = o.astype(jnp.float32)
    return o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                             + eps) * gain


def _query_scale(cfg: BailingHybridConfig) -> float:
    return cfg.head_dim ** -0.5


def _beta(b):
    """u W_b (B, S, H) float32 -> the rule's beta, a scalar a head."""
    return jax.nn.sigmoid(b)


def _out_gate(gate):
    """u W_g (B, S, H, D) -> what the normed o is multiplied by."""
    return jax.nn.sigmoid(gate.astype(jnp.float32))


# `_l2`, `_head_norm` and `_out_gate` are the mixer's norms on a (B, S, H, D)
# view, as PR 65 ran them: what the two functions below are tested against,
# and names the benchmark's seeded faults patch
# (`benchmark/tests/bailing_hybrid_faults.py`), so a mixer traced while one
# of them is not its own runs the view with it
_VIEWED = (_l2, _head_norm, _out_gate)


def _unit(x, cfg: BailingHybridConfig, scale=1.0):
    """x (B, S, H D) as the convolution wrote it -> each head's D lanes over
    their L2 norm, times ``scale``, in x's type: `_l2` as
    `ops/gated_norm.py`'s second rule over the rows as they lie,
    x rsqrt(sum x^2 + eps) = x rsqrt(mean x^2 + eps / D) D^-1/2."""
    H, D = cfg.n_head, cfg.head_dim
    if _l2 is not _VIEWED[0]:
        viewed = _l2(x.reshape(*x.shape[:2], H, D), cfg.l2_eps) * scale
        return viewed.astype(x.dtype).reshape(x.shape)
    return head_rms_norm(x, scale * D ** -0.5, H, cfg.l2_eps / D)


def _gated_head_norm(o, gate, gain, cfg: BailingHybridConfig):
    """o (B, S, H D) as the rule wrote it, gate = u W_g its like, gain (D,)
    the heads share -> RMSNorm over each head's D of o, times sigmoid(gate),
    in o's type, by the same rule: the gain tiled a head, so that the
    tiling's transpose sums the heads' gradients."""
    H, D = cfg.n_head, cfg.head_dim
    if (_head_norm, _out_gate) != _VIEWED[1:]:
        viewed = lambda x: x.reshape(*x.shape[:2], H, D)
        out = _head_norm(viewed(o), gain, cfg.rms_eps) \
            * _out_gate(viewed(gate))
        return out.astype(o.dtype).reshape(o.shape)
    return head_rms_norm(o, jnp.tile(gain, H), H, cfg.rms_eps, gate)


def _kda_mixer(u, p, cfg: BailingHybridConfig):
    """u (B, S, E) the normed stream -> (B, S, E): the held heads' part.
    Between the convolution's kernel and W_o a head stays 128 lanes of a
    (B, S, H D) row: the rule's (B, S, H, D) operands are views that its
    own `_flat` undoes, and nothing reduces over one."""
    B, S, _ = u.shape
    H, D = cfg.n_head, cfg.head_dim
    kernel = lambda name: p[name]["kernel"].astype(u.dtype)
    heads = lambda x: x.reshape(B, S, H, D)
    with jax.named_scope("proj"):
        qkv, f, gate = named(
            (u @ kernel("qkv_proj"), u @ kernel("f_proj"),
             u @ kernel("g_proj")), "kda/proj")
        b = named(jnp.matmul(u, kernel("b_proj"),
                             preferred_element_type=jnp.float32), "kda/proj")
    with jax.named_scope("conv"):
        taps = {"kernel": p["conv"]["kernel"],
                "bias": jnp.zeros((3 * H * D,), jnp.float32)}
        q, k, v = named(causal_conv(qkv, taps, jax.nn.silu,
                                    widths=(H * D,) * 3), "kda/conv")
    with jax.named_scope("gate"):
        q = _unit(q, cfg, _query_scale(cfg))
        k = _unit(k, cfg)
        g = _decay(f, p, cfg)
        beta = _beta(b)
    with jax.named_scope("rule"):
        o = kda(heads(q), heads(k), heads(v), g, beta, chunk=cfg.kda_chunk)
    with jax.named_scope("gate_norm"):
        o = _gated_head_norm(o.reshape(B, S, H * D), gate,
                             p["head_norm"]["scale"], cfg)
    with jax.named_scope("out_proj"):
        return named(o @ kernel("o_proj"), "kda/out_proj")


def _route(cfg: BailingHybridConfig):
    """-> route(xt, router) -> (weights (T, k) f32, experts (T, k) int32)
    over all experts, picked inside the best groups."""
    return functools.partial(
        sigmoid_route, top_k=cfg.top_k, eps=1e-20, scale=cfg.routed_scale,
        n_group=cfg.n_group, topk_group=cfg.topk_group)


def _layer(x, p, cfg: BailingHybridConfig):
    """-> (x, the rows sent to each expert; None from a dense layer).  The
    layer's kind is its parameters': a KDA mixer's subtree or MLA's."""
    u = rms_norm(x, p["input_norm"], cfg.rms_eps)
    if KDA in p:
        with jax.named_scope("kda"):
            x = x + _kda_mixer(u, p[KDA], cfg)
    else:
        with jax.named_scope("attention"):
            x = x + latent_attention(u, p[MLA], cfg, gated=True)
    u = rms_norm(x, p["post_norm"], cfg.rms_eps)
    with jax.named_scope("ffn"):
        if "mlp" in p:
            with jax.named_scope("dense"):
                return x + dense_ffn(u, p["mlp"], swiglu), None
        with jax.named_scope("moe"):
            y, rows = routed_layer(u, p["moe"], _route(cfg), cfg.n_experts,
                                   cfg.held, swiglu)
    return x + y, rows


def hidden(params, tokens, cfg: BailingHybridConfig, streams: bool = False):
    """tokens (B, S) int32 -> ((B, S, E) after the final norm, the routers'
    statistics); with ``streams`` the second is instead the stream after
    each of the layers held, in order."""
    if streams:
        def watched(x, p, cfg):
            x, _ = _layer(x, p, cfg)
            return x, x
        return trunk(params, tokens, watched, cfg)
    x, rows = trunk(params, tokens, _layer, cfg)
    return x, routing_account(params, cfg.moe_layers, rows,
                              tokens.size * cfg.top_k, cfg.held)


def forward(params, tokens, cfg: BailingHybridConfig):
    """tokens (B, S) int32 -> (logits (B, S, rows held) f32, routers'
    statistics)."""
    x, stats = hidden(params, tokens, cfg)
    head = params["lm_head"]["kernel"].astype(cfg.compute_dtype)
    return jnp.matmul(x, head, preferred_element_type=jnp.float32), stats


def loss_fn(params, batch, cfg: BailingHybridConfig):
    """batch {"tokens": (B, S + 1)} -> (next-token cross-entropy over the
    rows of the vocabulary held here, its parts: "loss" the same, and the
    routers' statistics).  No auxiliary loss; the multi-token module's
    weight is 0 and it is not built."""
    tokens = batch["tokens"]
    x, stats = hidden(params, tokens[:, :-1], cfg)
    xent = head_and_loss(x, params["lm_head"], tokens[:, 1:],
                         cfg.loss_chunk_rows)
    return xent, dict(stats, loss=xent)


def routing_bias_rule(cfg: BailingHybridConfig):
    """`ops/moe.py:routing_bias_rule` over this model's routed layers."""
    return _bias_rule_over(cfg.moe_layers, cfg.bias_update_speed)


def make_train_step(cfg: BailingHybridConfig, optimizer):
    """train_step(params, opt_state, batch) -> (params, opt_state, out), to
    be jitted with its shardings and `donate_argnums=(0, 1)` as
    `gpt2.make_train_step`'s; ``optimizer`` comes through `trained_by`.
    `out` carries "loss" and the routers' account
    (`ops/moe.py:routing_account`)."""
    return train_step(lambda params, batch: loss_fn(params, batch, cfg),
                      optimizer, cfg.compute_dtype,
                      rule=routing_bias_rule(cfg))


def rule_flops_per_token(cfg: BailingHybridConfig) -> float:
    """Forward operations a token of ONE KDA layer's rule as the chunked
    form at C = `kda_chunk` makes them, a multiply and an add two, K = V =
    D a head: the pair products A and P (2 x 2 C D a row over the whole
    square), T (I + A)^-1 as 10 products of (C, C) (2 C^2 each a row), T on
    beta V and on beta K exp(G) (2 x 2 C D), W S_0 and Q S_0 (2 x 2 D^2),
    P U (2 C D) and the state's K' U (2 D^2)."""
    C, D = cfg.kda_chunk, cfg.head_dim
    return cfg.n_head * (10 * C * D + 20 * C * C + 6 * D * D)


def count_flops_per_token(cfg: BailingHybridConfig, seq_len: int) -> float:
    """Training (forward + backward) operations per token HERE: 6 x the
    parameters a token multiplies on this chip (the head's rows held; a KDA
    mixer's six matrices and W_b; MLA's four and its gate; in a routed layer
    the router, the shared expert and the EXPECTED rows of held experts; in
    a dense layer its MLP) + MLA's full score squares, 6 S heads (192 + 128)
    a layer + the rules, forward once and backward twice."""
    E, H, D = cfg.n_embd, cfg.n_head, cfg.head_dim
    kinds = [cfg.kind(i) for i in range(cfg.n_layer)]
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    mixer = {
        KDA: 6 * E * H * D + E * H + 3 * H * D * cfg.conv_taps,
        MLA: E * H * qk + E * (cfg.kv_lora_rank + cfg.qk_rope_dim)
        + cfg.kv_lora_rank * H * (cfg.qk_nope_dim + cfg.v_head_dim)
        + E * H + H * cfg.v_head_dim * E,
    }
    routed = (E * cfg.n_experts + 3 * E * cfg.shared_width
              + cfg.top_k * cfg.n_held / cfg.n_experts
              * 3 * E * cfg.expert_width)
    n = (cfg.vocab_size * E + sum(mixer[k] for k in kinds)
         + cfg.n_dense_layer * 3 * E * cfg.dense_width
         + len(cfg.moe_layers) * routed)
    return 6 * n + 6 * kinds.count(MLA) * seq_len * H * (
        qk + cfg.v_head_dim) + 3 * kinds.count(KDA) * rule_flops_per_token(cfg)

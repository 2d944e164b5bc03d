"""Nemotron-H decoders (`model_type` `nemotron_h`: NVIDIA's
Nemotron-3-Nano-30B-A3B) for the Train path: ONE mixer a layer, of three
kinds chosen per layer by `hybrid_override_pattern` (`M` a Mamba-2
state-space mixer, `*` grouped-query attention, `E` a mixture of experts
that are not gated).

Layer equations, from the published `config.json` and the public
`modeling_nemotron_h.py`:

  x <- x + Mixer_i(RMSNorm(x)) for each layer, RMSNorm with
  `layer_norm_epsilon`, no bias in any projection; a final RMSNorm
  (`norm_f`); the head is its own matrix (`tie_word_embeddings` false).
  `residual_in_fp32` false: the stream is in the compute type.

  `M`, u of (B, S, E); H heads (`mamba_num_heads`) of P (`mamba_head_dim`),
  inner width HP (NOT `expand` x E), G groups (`n_groups`), state N
  (`ssm_state_size`), K taps (`conv_kernel`):
    1. [z | xBC | dt] = u W_in, widths HP | HP + 2GN | H in that order.
    2. xBC <- silu(conv(xBC) + b): conv(v)_t = sum_{j=0..K-1} w_j *
       v_{t-(K-1)+j}, w of (HP + 2GN, K), one filter a channel, zeros
       before the sequence starts, bias b (`use_conv_bias`).
    3. [x | B | C] = xBC, widths HP | GN | GN: x as (S, H, P), B and C as
       (S, G, N).
    4. In float32: D_t = softplus(dt_t + dt_bias) (H values a position; the
       public code's clamp to `time_step_limit` (0, inf) does nothing),
       A = -exp(A_log) (H values).
    5. For head h, with group g = h // (H / G) and state h_t of (P, N),
       h_{-1} = 0:  h_t = exp(D_t A) h_{t-1} + D_t x_t (x) B_t,
       y_t = h_t C_t + D_h x_t.
    6. y <- y * silu(z), THEN RMSNorm over each of the G groups of HP / G
       channels, times a gain of (HP) (`MambaRMSNormGated`, gate before
       norm).
    7. Mixer(u) = y W_out (HP x E).
    The program runs step 5 by chunks of `chunk_size` positions
    (`ops/ssd.py`, which writes the chunked form out); it gives step 5's
    result at every chunk size.
  `*`: q = u W_q as H_a heads of D, k = u W_k and v = u W_v as H_kv heads
    of D; no rotary embedding, no norm over a head, no bias; causal softmax
    at D^-1/2, query head h on key/value head h // (H_a / H_kv); W_o.
  `E`: s = sigmoid(u W_g) in float32 over the experts; the top k of s + b
    (`e_score_correction_bias`; b picks and does not weigh; `n_group` 1 and
    `topk_group` 1 make the group limit nothing); weights s at the chosen
    over their sum + 1e-20 (`norm_topk_prob`), times
    `routed_scaling_factor`; sum w_i E_i(u) + Shared(u); every expert
    W_down relu(W_up u)^2 (`mlp_hidden_act` relu2, NOT gated: two
    matrices), the routed `moe_intermediate_size` wide, the shared one
    `moe_shared_expert_intermediate_size`; no token dropped.
  b is no optimizer leaf: after a step b_e += speed * sign(mean(n) - n_e),
  n the rows each expert was sent (the rule is assumed: DeepSeek-V3's).

A layer's kind is read from its parameters' names (a tree's structure is
static), so `jax.checkpoint` traces one layer per kind.

``held`` = (first, count): one chip's share of an expert-parallel layer, as
`models/deepseek_v3.py`: the router, the shared expert and the other two
mixers are whole; only the held experts' matrices exist and only their part
of the sum is computed (`ops/moe.py:moe_dispatch`).  `vocab_size` is the
rows of the embedding and of the head held here.

What it shares with the other models: `models/layers.py` (RMSNorm,
`causal_conv`, the projections into attention (W_o's result carries no name
here, so the way out is this file's), the ungated feed-forward `relu2`, the
routed layer, the walk over the layers, the head and its chunked loss, the
mixed-precision step and its place for state that moves by a rule),
`parallel/attention.py` (the flash kernels, 16 query heads on each
key/value head), `ops/moe.py` (dispatch over a share of the experts, the
sigmoid router, its account and its bias rule), `ops/ssd.py` (the scan's
kernels), `ops/causal_conv.py` (behind `layers.causal_conv`: the taps, bias
and SiLU of xBC read where W_in wrote it and x, B and C written as three
results, one Mosaic kernel a pass at the published widths) and
`ops/gated_norm.py` (`gated_rms_norm`: the gate and the groups' norm behind
the scan, likewise); the names are those `parallel/sharding.py` lays out.

`jax.named_scope`s (`models/layers.py:SCOPES`): embed, norm,
ssm/{in_proj,conv,scan,gate_norm,out_proj}, attention/{qkv,kernel,out},
ffn/moe/{route,dispatch,experts,combine,shared}, head_and_loss,
optimizer_update, routing_bias_update: the mixture stands under `ffn`.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.layers import (
    attention_qkv,
    causal_conv,
    head_and_loss,
    named,
    normal_kernel,
    num_params,  # noqa: F401  (`nemotron_h.num_params` is public)
    relu2,
    rms_norm,
    routed_layer,
    train_step,
    trunk,
    unit_scale,
)
from ray_tpu.ops.gated_norm import gated_rms_norm
from ray_tpu.ops.moe import (
    ROUTING_BIAS,
    routing_account,
    routing_bias_rule,
    sigmoid_route,
    trained_by,  # noqa: F401  (`nemotron_h.trained_by` is public)
)
from ray_tpu.ops.ssd import ssd_scan
from ray_tpu.parallel.attention import attention

MAMBA, ATTENTION, MOE = "M", "*", "E"
_NANO_30B_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072          # rows of the embedding and head here
    pattern: str = _NANO_30B_PATTERN  # `hybrid_override_pattern`
    n_embd: int = 2688
    mamba_heads: int = 64             # `mamba_num_heads`
    mamba_head_dim: int = 64
    n_groups: int = 8
    state_size: int = 128             # `ssm_state_size`
    conv_taps: int = 4                # `conv_kernel`
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    n_head: int = 32
    n_kv_head: int = 2
    head_dim: int = 128
    expert_width: int = 1856          # `moe_intermediate_size`
    shared_width: int = 3712          # `moe_shared_expert_intermediate_size`
    n_experts: int = 128              # `n_routed_experts`: the router's width
    held: Optional[Tuple[int, int]] = None   # (first, count); None: all
    top_k: int = 6
    norm_topk_prob: bool = True
    routed_scale: float = 2.5
    rms_eps: float = 1e-5
    bias_update_speed: float = 0.001  # assumed: arXiv:2412.19437's gamma
    # `rescale_prenorm_residual`: W_out, W_o and every W_down start divided
    # by the root of the PUBLISHED depth, whatever depth is held here
    rescale_depth: int = 52
    compute_dtype: Any = jnp.bfloat16
    # jax.checkpoint each layer, keeping its attention kernel's output and
    # row statistics and, of `layers.KEPT_NAMES` (here the routers'
    # products, a mixer's W_in and scan results, W_q's, W_k's and W_v's
    # results, the experts' W_up results), those the chip has room for over
    # all layers (`layers.checkpoint_layer`)
    remat: bool = False
    loss_chunk_rows: int = 2048       # `layers.chunked_xent`

    @property
    def n_layer(self) -> int:
        return len(self.pattern)

    @property
    def mamba_width(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:
        """[x | B | C]: what the convolution runs over."""
        return self.mamba_width + 2 * self.n_groups * self.state_size

    @property
    def n_held(self) -> int:
        return self.held[1] if self.held else self.n_experts

    @property
    def moe_layers(self):
        return tuple(i for i, kind in enumerate(self.pattern) if kind == MOE)


NEMOTRON_3_NANO_30B = NemotronHConfig()
NEMOTRON_H_TINY = NemotronHConfig(
    vocab_size=512, pattern="MEM*E", n_embd=64, mamba_heads=8,
    mamba_head_dim=8, n_groups=2, state_size=16, chunk_size=8, n_head=4,
    n_kv_head=2, head_dim=16, expert_width=24, shared_width=48, n_experts=8,
    top_k=3, loss_chunk_rows=32)


def init_params(rng, cfg: NemotronHConfig) -> Dict[str, Any]:
    """Normal(0, 0.02) matrices, W_out, W_o and every W_down divided by
    sqrt(`rescale_depth`); unit gains; routing biases 0; A_log = log(1..H),
    D = 1, dt_bias the inverse softplus of dt drawn log-uniform in
    [`time_step_min`, `time_step_max`] and floored at `time_step_floor`; the
    taps and their bias uniform(+-K^-1/2), as a depthwise `Conv1d` of K taps
    is left.  Names are those `parallel/sharding.py:
    infer_param_logical_dims` lays out; the experts' stacks hold the
    `cfg.n_held` experts that live here."""
    E = cfg.n_embd
    down = 0.02 * (1.0 / math.sqrt(cfg.rescale_depth))
    keys = jax.random.split(rng, 2 + cfg.n_layer)
    params = {
        "embed_tokens": {
            "embedding": normal_kernel(keys[0], cfg.vocab_size, E)["kernel"]},
        "norm_f": unit_scale(E),
        "lm_head": normal_kernel(keys[1], E, cfg.vocab_size),
    }
    for i, kind in enumerate(cfg.pattern):
        ks = jax.random.split(keys[2 + i], 6)
        layer = {"norm": unit_scale(E)}
        if kind == MAMBA:
            H, HP, C = cfg.mamba_heads, cfg.mamba_width, cfg.conv_width
            bound = cfg.conv_taps ** -0.5
            dt = jnp.exp(jax.random.uniform(
                ks[2], (H,), jnp.float32, math.log(cfg.time_step_min),
                math.log(cfg.time_step_max)))
            dt = jnp.maximum(dt, cfg.time_step_floor)
            layer["mamba"] = {
                "in_proj": normal_kernel(ks[0], E, HP + C + H),
                "conv": {
                    "kernel": jax.random.uniform(
                        ks[1], (C, cfg.conv_taps), jnp.float32, -bound,
                        bound),
                    "bias": jax.random.uniform(
                        ks[3], (C,), jnp.float32, -bound, bound)},
                "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
                "D": jnp.ones((H,), jnp.float32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "norm": unit_scale(HP),
                "out_proj": normal_kernel(ks[4], HP, E, std=down),
            }
        elif kind == ATTENTION:
            H, Hkv, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
            layer["attn"] = {
                "q_proj": normal_kernel(ks[0], E, H * D),
                "k_proj": normal_kernel(ks[1], E, Hkv * D),
                "v_proj": normal_kernel(ks[2], E, Hkv * D),
                "o_proj": normal_kernel(ks[3], H * D, E, std=down),
            }
        elif kind == MOE:
            n, W = cfg.n_held, cfg.expert_width
            layer["moe"] = {
                "router": {
                    **normal_kernel(ks[0], E, cfg.n_experts),
                    ROUTING_BIAS: jnp.zeros((cfg.n_experts,), jnp.float32)},
                "wi_up": normal_kernel(ks[1], n, E, W)["kernel"],
                "wo": normal_kernel(ks[2], n, W, E, std=down)["kernel"],
                "shared": {
                    "up_proj": normal_kernel(ks[3], E, cfg.shared_width),
                    "down_proj": normal_kernel(ks[4], cfg.shared_width, E,
                                               std=down)},
            }
        else:
            raise ValueError(f"layer {i}: {kind!r} is no kind of mixer")
        params[f"layer_{i}"] = layer
    return params


def _mamba(u, p, cfg: NemotronHConfig):
    B, S, _ = u.shape
    H, P, G, N = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.n_groups,
                  cfg.state_size)
    HP = H * P
    with jax.named_scope("in_proj"):
        zxbcdt = named(u @ p["in_proj"]["kernel"].astype(u.dtype),
                       "ssm/in_proj")
        dt = zxbcdt[..., HP + cfg.conv_width:]
    with jax.named_scope("conv"):
        # xBC where it lies, W_in's columns behind z, and x, B and C each a
        # result of its own: no slice before the taps and none behind them
        x, Bm, Cm = causal_conv(zxbcdt, p["conv"], jax.nn.silu, start=HP,
                                widths=(HP, G * N, G * N))
    with jax.named_scope("scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
        y = named(ssd_scan(
            x.reshape(B, S, H, P), dt, -jnp.exp(p["A_log"]),
            Bm.reshape(B, S, G, N), Cm.reshape(B, S, G, N), p["D"],
            cfg.chunk_size), "ssm/scan").reshape(B, S, HP)
    with jax.named_scope("gate_norm"):
        # z where it lies, W_in's first HP columns: sliced out for a kernel
        # it cost a copy a layer and pass (PERF.md §6, PR 55)
        y = gated_rms_norm(y, zxbcdt, p["norm"]["scale"], G, cfg.rms_eps)
    with jax.named_scope("out_proj"):
        # the layer's last product: it is added to the stream and no
        # backward reads it, so it carries no name to keep
        return y @ p["out_proj"]["kernel"].astype(u.dtype)


def _attention(u, p, cfg: NemotronHConfig):
    B, S, _ = u.shape
    q, k, v = attention_qkv(u, p, cfg.head_dim)     # no norm, no RoPE
    with jax.named_scope("kernel"):
        o = attention(q, k, v)        # 2 key/value heads go in as they are
    with jax.named_scope("out"):
        # as W_out's: no name, so not `layers.attention_out`
        return o.reshape(B, S, -1) @ p["o_proj"]["kernel"].astype(u.dtype)


def _route(cfg: NemotronHConfig):
    """-> route(xt, router) -> (weights (T, k) f32, experts (T, k) int32)
    over all experts."""
    return functools.partial(
        sigmoid_route, top_k=cfg.top_k,
        eps=1e-20 if cfg.norm_topk_prob else None, scale=cfg.routed_scale)


def _layer(x, p, cfg: NemotronHConfig):
    """-> (x, the rows sent to each expert; None from a layer that is no
    mixture)."""
    u = rms_norm(x, p["norm"], cfg.rms_eps)
    if "mamba" in p:
        with jax.named_scope("ssm"):
            return x + _mamba(u, p["mamba"], cfg), None
    if "attn" in p:
        with jax.named_scope("attention"):
            return x + _attention(u, p["attn"], cfg), None
    with jax.named_scope("ffn"), jax.named_scope("moe"):
        y, rows = routed_layer(u, p["moe"], _route(cfg), cfg.n_experts,
                               cfg.held, relu2)
    return x + y, rows


def _hidden(params, tokens, cfg: NemotronHConfig):
    """-> ((B, S, E) after the final norm, the routers' statistics)."""
    x, rows = trunk(params, tokens, _layer, cfg)
    return x, routing_account(params, cfg.moe_layers, rows,
                              tokens.size * cfg.top_k, cfg.held)


def forward(params, tokens, cfg: NemotronHConfig):
    """tokens (B, S) int32 -> (logits (B, S, rows held) f32, routers'
    statistics)."""
    x, stats = _hidden(params, tokens, cfg)
    head = params["lm_head"]["kernel"].astype(cfg.compute_dtype)
    return jnp.matmul(x, head, preferred_element_type=jnp.float32), stats


def loss_fn(params, batch, cfg: NemotronHConfig):
    """batch {"tokens": (B, S+1)} -> (next-token cross-entropy over the
    rows of the vocabulary held here, its parts: "loss" the same, and the
    routers' statistics).  There is no auxiliary loss.  The head's logits
    are made `cfg.loss_chunk_rows` rows at a time and never all held."""
    tokens = batch["tokens"]
    x, stats = _hidden(params, tokens[:, :-1], cfg)
    xent = head_and_loss(x, params["lm_head"], tokens[:, 1:],
                         cfg.loss_chunk_rows)
    return xent, dict(stats, loss=xent)


def make_train_step(cfg: NemotronHConfig, optimizer):
    """train_step(params, opt_state, batch) -> (params, opt_state, out),
    to be jitted with its shardings and `donate_argnums=(0, 1)` as
    `gpt2.make_train_step`'s; ``optimizer`` comes through `trained_by`.
    `out` carries "loss" and the routers' account
    (`ops/moe.py:routing_account`, a row a mixture layer), device values
    that cost nothing unless fetched."""
    return train_step(lambda params, batch: loss_fn(params, batch, cfg),
                      optimizer, cfg.compute_dtype,
                      rule=routing_bias_rule(cfg.moe_layers,
                                             cfg.bias_update_speed))


def scan_flops_per_token(cfg: NemotronHConfig) -> float:
    """Forward operations a token of ONE Mamba-2 layer's chunked scan: C B'
    and (L o C B') x over the causal half of a chunk's square, a chunk's
    own state, and what earlier chunks add."""
    Q, H, P, G, N = (cfg.chunk_size, cfg.mamba_heads, cfg.mamba_head_dim,
                     cfg.n_groups, cfg.state_size)
    return 2 * Q * N * G / 2 + 2 * Q * P * H / 2 + 2 * 2 * N * P * H


def count_flops_per_token(cfg: NemotronHConfig, seq_len: int) -> float:
    """Training (forward + backward) operations per token HERE: 6 x the
    parameters a token multiplies on this chip (the embedding is a gather;
    the head's rows held; a Mamba-2 mixer's W_in, W_out and taps; an
    attention mixer's four matrices; in a mixture the router, the shared
    expert and the EXPECTED rows of held experts, top_k x held / experts of
    two matrices each) + the full score squares of the attention layers, 6
    S heads (D + D) + the scans' four products, forward once and backward
    twice."""
    E = cfg.n_embd
    mamba = E * (cfg.mamba_width + cfg.conv_width + cfg.mamba_heads) \
        + cfg.mamba_width * E + cfg.conv_width * cfg.conv_taps
    attn = 2 * E * cfg.n_head * cfg.head_dim \
        + 2 * E * cfg.n_kv_head * cfg.head_dim
    mixture = E * cfg.n_experts + 2 * E * cfg.shared_width \
        + cfg.top_k * cfg.n_held / cfg.n_experts * 2 * E * cfg.expert_width
    n_mamba, n_attn = cfg.pattern.count(MAMBA), cfg.pattern.count(ATTENTION)
    n = (cfg.vocab_size * E + n_mamba * mamba + n_attn * attn
         + len(cfg.moe_layers) * mixture)
    return (6 * n + 6 * n_attn * seq_len * cfg.n_head * 2 * cfg.head_dim
            + 3 * n_mamba * scan_flops_per_token(cfg))

"""OLMoE — a routed mixture-of-experts decoder for the Train path.

Muennighoff et al., "OLMoE: Open Mixture-of-Experts Language Models"
(arXiv:2409.02060); layer equations as `allenai/OLMoE-1B-7B-0125`'s public
`modeling_olmoe.py`:

  h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h));  final RMSNorm; an
  untied head.  Attention: q, k, v without bias, RMSNorm over the whole q
  and k projections (before the split into heads), rotate-half RoPE on all
  of a head's dimensions, causal softmax at head_dim^-1/2.  MoE: a softmax
  router over all experts in float32, the top k of it as weights (not
  renormalised), every expert a SiLU-gated feed-forward, no shared expert
  and no token dropped.  Trained on cross-entropy + a load-balancing loss
  + a router z-loss.

What it shares with the other models it takes from `models/layers.py`
(RMSNorm, RoPE, the SwiGLU over grouped matmuls, the walk over the layers,
the head and its chunked loss, f32 master parameters cast once in the
mixed-precision step) and `parallel/attention.py` (the flash kernels under
a mesh); its names are those `parallel/sharding.py` lays out.  The experts
run dropless (`ops/moe.py:moe_dispatch`): the (token,
expert) rows are sorted by expert and each group is multiplied by its
expert with XLA:TPU's grouped-matmul kernel (`layers.grouped_ffn`).  Its
routed layer is written out here and is not `layers.routed_layer`: the two
auxiliary losses read the router's logits and probabilities, and the
benchmark's seeded fault (`benchmark/tests/test_olmoe_cell.py`) patches
this module's `moe_dispatch`.

`jax.named_scope`s (`models/layers.py:SCOPES`): embed, norm,
attention/{qkv,kernel,out}, ffn/moe/{route,dispatch,experts,combine},
head_and_loss, optimizer_update.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.layers import (
    grouped_ffn,
    head_and_loss,
    named,
    normal_kernel,
    num_params,  # noqa: F401  (`olmoe.num_params` is public)
    rms_norm,
    rope,
    swiglu,
    train_step,
    trunk,
    unit_scale,
)
from ray_tpu.ops.moe import ROUTE_NAME, moe_dispatch
from ray_tpu.parallel.attention import attention


@dataclass(frozen=True)
class OlmoeConfig:
    vocab_size: int = 50304
    max_seq: int = 4096
    n_layer: int = 16
    n_head: int = 16
    n_embd: int = 2048
    expert_width: int = 1024
    n_experts: int = 64
    top_k: int = 8
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    aux_weight: float = 0.01      # load balancing (the paper's alpha)
    z_weight: float = 0.001       # router z-loss (the paper's beta)
    compute_dtype: Any = jnp.bfloat16
    # jax.checkpoint each layer, keeping its attention kernel's output and
    # row statistics and, of `layers.KEPT_NAMES` (here the router's logits
    # and the rows' order by expert, W_q's, W_k's, W_v's and W_o's results,
    # the experts' gate and up), those the chip has room for over all layers
    # (`layers.checkpoint_layer`)
    remat: bool = False
    # rows of the head's logits alive at once (`layers.chunked_xent`): at
    # 16,384 x 50,304 the whole of them and their gradient are 6.6 GB
    loss_chunk_rows: int = 2048

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head


OLMOE_1B_7B = OlmoeConfig()
OLMOE_TINY = OlmoeConfig(vocab_size=512, max_seq=64, n_layer=2, n_head=4,
                         n_embd=64, expert_width=32, n_experts=8, top_k=2,
                         loss_chunk_rows=32)


def init_params(rng, cfg: OlmoeConfig) -> Dict[str, Any]:
    """Normal(0, 0.02) matrices (`initializer_range` of the published
    config), unit norm scales.  Names are those `parallel/sharding.py:
    infer_param_logical_dims` lays out: the experts' stacks are
    ("expert", "embed", "mlp") / ("expert", "mlp", "embed")."""
    E, W, N = cfg.n_embd, cfg.expert_width, cfg.n_experts
    keys = jax.random.split(rng, 2 + cfg.n_layer)
    params = {
        "embed_tokens": {
            "embedding": normal_kernel(keys[0], cfg.vocab_size, E)["kernel"]},
        "norm_f": unit_scale(E),
        "lm_head": normal_kernel(keys[1], E, cfg.vocab_size),
    }
    for i in range(cfg.n_layer):
        ks = jax.random.split(keys[2 + i], 8)
        params[f"layer_{i}"] = {
            "input_norm": unit_scale(E),
            "attn": {
                "q_proj": normal_kernel(ks[0], E, E),
                "k_proj": normal_kernel(ks[1], E, E),
                "v_proj": normal_kernel(ks[2], E, E),
                "o_proj": normal_kernel(ks[3], E, E),
                "q_norm": unit_scale(E),
                "k_norm": unit_scale(E),
            },
            "post_norm": unit_scale(E),
            "moe": {
                "router": normal_kernel(ks[4], E, N),
                "wi_gate": normal_kernel(ks[5], N, E, W)["kernel"],
                "wi_up": normal_kernel(ks[6], N, E, W)["kernel"],
                "wo": normal_kernel(ks[7], N, W, E)["kernel"],
            },
        }
    return params


def _attention(x, p, cfg: OlmoeConfig):
    B, S, E = x.shape
    H, D = cfg.n_head, cfg.head_dim
    kernel = lambda name: p[name]["kernel"].astype(x.dtype)
    with jax.named_scope("qkv"):
        # the products, before the norms: a norm's backward reads them
        q, k, v = named((x @ kernel("q_proj"), x @ kernel("k_proj"),
                         x @ kernel("v_proj")), "attention/qkv")
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
        positions = jnp.arange(S)
        q = rope(q.reshape(B, S, H, D), positions, cfg.rope_theta)
        k = rope(k.reshape(B, S, H, D), positions, cfg.rope_theta)
        v = v.reshape(B, S, H, D)
    with jax.named_scope("kernel"):
        o = attention(q, k, v)
    with jax.named_scope("out"):
        return named(o.reshape(B, S, E) @ kernel("o_proj"), "attention/out")


def _moe(x, p, cfg: OlmoeConfig):
    """-> (y, {load-balancing loss, router z-loss, most rows an expert
    got}) for x of shape (B, S, E)."""
    B, S, E = x.shape
    xt = x.reshape(B * S, E)
    with jax.named_scope("route"):
        # the logits: the z-loss reads them, and a softmax's and a top-k's
        # backward read their own results, which a replay makes from these
        logits = named((xt @ p["router"]["kernel"].astype(x.dtype)
                        ).astype(jnp.float32), ROUTE_NAME)    # (T, N)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, cfg.top_k)    # (T, k)
    y, group_sizes = moe_dispatch(xt, weights, experts, cfg.n_experts,
                                  grouped_ffn(p, swiglu))
    with jax.named_scope("route"):
        # f: each expert's share of the T*k assignments (a count: no
        # gradient); P: its mean router probability
        share = group_sizes.astype(jnp.float32) / (B * S * cfg.top_k)
        balance = cfg.n_experts * jnp.sum(share * jnp.mean(probs, axis=0))
        z = jnp.mean(jnp.square(jax.scipy.special.logsumexp(logits, -1)))
    return y.reshape(B, S, E), {"aux_loss": balance, "z_loss": z,
                                "max_expert_rows": jnp.max(group_sizes)}


def _layer(x, p, cfg: OlmoeConfig):
    u = rms_norm(x, p["input_norm"], cfg.rms_eps)
    with jax.named_scope("attention"):
        x = x + _attention(u, p["attn"], cfg)
    u = rms_norm(x, p["post_norm"], cfg.rms_eps)
    with jax.named_scope("ffn"), jax.named_scope("moe"):
        y, stats = _moe(u, p["moe"], cfg)
    return x + y, stats


def _hidden(params, tokens, cfg: OlmoeConfig):
    """-> ((B, S, E) after the final norm, the routers' statistics: the
    auxiliary losses averaged over the layers, the fullest expert of
    any)."""
    x, stats = trunk(params, tokens, _layer, cfg)
    mean = lambda key: sum(s[key] for s in stats) / len(stats)
    return x, {
        "aux_loss": mean("aux_loss"), "z_loss": mean("z_loss"),
        "max_expert_rows": functools.reduce(
            jnp.maximum, [s["max_expert_rows"] for s in stats])}


def forward(params, tokens, cfg: OlmoeConfig):
    """tokens (B, S) int32 -> (logits (B, S, vocab) f32, routers'
    statistics)."""
    x, stats = _hidden(params, tokens, cfg)
    head = params["lm_head"]["kernel"].astype(cfg.compute_dtype)
    return jnp.matmul(x, head, preferred_element_type=jnp.float32), stats


def loss_fn(params, batch, cfg: OlmoeConfig):
    """batch {"tokens": (B, S+1)} -> (the objective that is
    differentiated, its parts).  The objective is next-token cross-entropy
    + aux_weight x load balancing + z_weight x router z-loss; `parts`
    holds the cross-entropy as "loss", the two auxiliary losses and the
    fullest expert's rows.  The head's logits are made
    `cfg.loss_chunk_rows` rows at a time and never all held."""
    tokens = batch["tokens"]
    x, stats = _hidden(params, tokens[:, :-1], cfg)
    xent = head_and_loss(x, params["lm_head"], tokens[:, 1:],
                         cfg.loss_chunk_rows)
    objective = (xent + cfg.aux_weight * stats["aux_loss"]
                 + cfg.z_weight * stats["z_loss"])
    return objective, dict(stats, loss=xent)


def make_train_step(cfg: OlmoeConfig, optimizer):
    """train_step(params, opt_state, batch) -> (params, opt_state, out),
    to be jitted with its shardings and `donate_argnums=(0, 1)` as
    `gpt2.make_train_step`'s.  `out["loss"]` is the cross-entropy alone;
    `out` also carries "aux_loss", "z_loss" and "max_expert_rows", device
    scalars that cost nothing unless fetched.  Mixed precision as
    `layers.train_step` says."""
    return train_step(lambda params, batch: loss_fn(params, batch, cfg),
                      optimizer, cfg.compute_dtype)


def count_flops_per_token(cfg: OlmoeConfig, seq_len: int) -> float:
    """Training (forward + backward) operations per token: 6 N + 12 L E S
    as `gpt2.count_flops_per_token`, N the parameters a token multiplies:
    the head, and per layer the four attention matrices, the router and
    the token's top_k experts (three matrices each), not all of them."""
    E = cfg.n_embd
    per_layer = (4 * E * E + E * cfg.n_experts
                 + cfg.top_k * 3 * E * cfg.expert_width)
    n = cfg.vocab_size * E + cfg.n_layer * per_layer
    return 6 * n + 12 * cfg.n_layer * E * seq_len

"""Keye-VL-2.0's language model (`model_type` `KeyeVL2`: Kwai-Keye's
Keye-VL-2.0-30B-A3B) for the Train path: grouped-query attention that
SELECTS its keys (a lightning indexer scores every causal pair, each query
attends the 2,048 keys of largest score) beside a softmax-routed mixture of
experts in every layer.

The language model only: the published `config.json` as the catalog of
public architectures holds it carries no setting of the vision tower, and
for text tokens the three position ids of M-RoPE (`mrope_section`
[16, 24, 24]) coincide, so M-RoPE is plain rotate-half RoPE here.

Layer equations.  The config's keys are Qwen3-MoE's, whose public modeling
code gives the block; `sa_config` is a DeepSeek-Sparse-Attention indexer
(the catalog says so), whose equations are DeepSeek-V3.2-Exp's public
lightning indexer and its sparse training stage.  Pre-norm residual layers,
RMSNorm at `rms_norm_eps`, no bias in any projection:

  h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h));  a final RMSNorm; an
  untied head.
  Attn, u of (B, S, E): q = u W_q as H heads of D, k = u W_k and v = u W_v
    as H_kv heads of D;  RMSNorm over the D of every q head and every k
    head (one gain vector each);  RoPE on the whole head, rotate-half,
    `rope_theta`;  o_t = sum_{s in S_t} softmax_{s in S_t}(q_t . k_s
    D^-1/2) v_s, query head h on key/value head h // (H / H_kv), every
    head over the same S_t;  W_o.
  The indexer, on ub = stop_gradient(u):  qI = ub W_Iq as J heads of D_I;
    kI = LayerNorm(ub W_Ik), ONE key head of D_I;  RoPE on the first half
    of a head's D_I dims;  w = ub W_Iw * J^-1/2 * D_I^-1/2;
    I_{t,s} = sum_j w_{t,j} relu(qI_{t,j} . kI_s) for s <= t, in float32;
    S_t = the min(`topk`, t + 1) keys s <= t of largest I_{t,s}, of equal
    scores the lower s (`ops/sparse_index.py`).  The public code's Hadamard
    rotation of qI and kI is orthogonal on both, leaves every product as it
    is and exists for fp8: left out.
  The indexer's loss: p_t = the heads' attention probabilities over S_t,
    averaged, a constant;  L_I = mean_t KL(p_t || softmax_{s in S_t}
    I_{t,s}), summed over the layers.
  MoE: g = softmax over the router's logits in float32, the top k of it,
    their weights over their sum (`norm_topk_prob`);  sum w_i E_i(u), each
    E_i a SwiGLU; no bias, no shared expert, no token dropped.
  The routers' load-balancing loss (Switch Transformer's, which the
    lineage's `router_aux_loss_coef` weighs): per layer N sum_e f_e P_e, N
    the experts, f_e the share of the batch's T k assignments that went to
    e (a count: no gradient), P_e the batch's mean of g_e; summed over the
    layers, times `aux_weight`.

Losses over one graph that do not mix: the step minimises L_LM + L_I +
`aux_weight` L_B; ub, p and the selection are constants, so L_LM's and L_B's
gradients on the indexer's leaves are exactly zero and L_I's on every other
leaf is exactly zero.  `out["loss"]` is L_LM + L_I, the two that train the
model's leaves (the benchmark's `correct` compares it, so the indexer is in
the compared number); its parts are `out["lm_loss"]` and
`out["indexer_loss"]`, and `out["aux_loss"]` is L_B.

``held`` = (first, count): one chip's share of an expert-parallel layer, as
`models/deepseek_v3.py`: router, attention and indexer are whole; only the
held experts' matrices exist and only their part of the sum is computed
(`ops/moe.py:moe_dispatch`).  `vocab_size` is the rows of embedding and
head held here.

What it shares with the other models: `models/layers.py` (RMSNorm,
LayerNorm, RoPE, the projections into and out of attention, between which
the indexer, the masked kernels and the indexer's loss stand here, the
SwiGLU, the routed layer, the walk over the layers, the head and its
chunked loss, the mixed-precision step), `parallel/attention.py` (the flash
kernels, here under a mask that is data) and `ops/moe.py` (the softmax
route, its balance loss, the routers' account); the names are those
`parallel/sharding.py` lays out.

`jax.named_scope`s (`models/layers.py:SCOPES`): embed, norm,
attention/{qkv,indexer/{proj,scores,select,loss},kernel,out},
ffn/moe/{route,dispatch,experts,combine}, head_and_loss, optimizer_update.
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
    head_and_loss,
    layer_norm,
    named,
    normal_kernel,
    num_params,  # noqa: F401  (`keye_vl.num_params` is public)
    rms_norm,
    rope,
    routed_layer,
    swiglu,
    train_step,
    trunk,
    unit_scale,
)
from ray_tpu.ops.moe import balance_loss, routing_account, softmax_route
from ray_tpu.ops.sparse_index import (
    index_scores,
    indexer_loss,
    select_top_k,
)
from ray_tpu.parallel.attention import attention


@dataclass(frozen=True)
class KeyeVlConfig:
    vocab_size: int = 151936          # rows of embedding and head held here
    n_layer: int = 48
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    n_embd: int = 2048
    expert_width: int = 768
    n_experts: int = 128              # the router's width
    held: Optional[Tuple[int, int]] = None   # (first, count); None: all
    top_k: int = 8
    norm_topk_prob: bool = True
    # the load-balancing loss's weight: the lineage's published
    # `router_aux_loss_coef` (Qwen3-MoE's config.json; the catalog's copy of
    # Keye's drops the key).  A chip's SHARE of the experts needs far more:
    # its router is trained through the held experts alone, and under 1 the
    # share is sent several times its expected rows within thirty steps of
    # a resident batch (PERF.md §6, PR 47; the cell's file states 1.0)
    aux_weight: float = 0.001
    rope_theta: float = 1e7
    rms_eps: float = 1e-6
    index_heads: int = 16             # `sa_config.indexer_num_heads`
    index_dim: int = 64               # `sa_config.indexer_head_dim`
    index_top_k: int = 2048           # `sa_config.topk`
    # `sa_config.q_chunk_size`: the rows of queries whose index scores,
    # selection and loss are made at once (`ops/sparse_index.py`)
    index_block: int = 512
    compute_dtype: Any = jnp.bfloat16
    # jax.checkpoint each layer, keeping its attention kernel's output and
    # row statistics and, of `layers.KEPT_NAMES` (here the router's logits,
    # choices and sorted order, the selection's mask, W_o's result, W_q's,
    # W_k's and W_v's, the experts' gate and up, the index scores), those
    # the chip has room for over all layers (`layers.checkpoint_layer`)
    remat: bool = False
    loss_chunk_rows: int = 2048       # `layers.chunked_xent`

    @property
    def n_held(self) -> int:
        return self.held[1] if self.held else self.n_experts

    @property
    def moe_layers(self):
        return range(self.n_layer)


KEYE_VL_2_30B_A3B = KeyeVlConfig()
KEYE_VL_TINY = KeyeVlConfig(
    vocab_size=512, n_layer=2, n_head=8, n_kv_head=2, head_dim=16, n_embd=64,
    expert_width=24, n_experts=8, top_k=3, index_heads=4, index_dim=8,
    index_top_k=16, index_block=16, loss_chunk_rows=32)


def init_params(rng, cfg: KeyeVlConfig) -> Dict[str, Any]:
    """Normal(0, 0.02) matrices, unit norm gains, the indexer's LayerNorm
    at gain 1 and bias 0.  Names are those `parallel/sharding.py:
    infer_param_logical_dims` lays out; the experts' stacks hold the
    `cfg.n_held` experts that live here."""
    E, H, Hkv, D = cfg.n_embd, cfg.n_head, cfg.n_kv_head, cfg.head_dim
    J, Di = cfg.index_heads, cfg.index_dim
    keys = jax.random.split(rng, 2 + cfg.n_layer)
    params = {
        "embed_tokens": {
            "embedding": normal_kernel(keys[0], cfg.vocab_size, E)["kernel"]},
        "norm_f": unit_scale(E),
        "lm_head": normal_kernel(keys[1], E, cfg.vocab_size),
    }
    for i in range(cfg.n_layer):
        ks = jax.random.split(keys[2 + i], 11)
        n, W = cfg.n_held, cfg.expert_width
        params[f"layer_{i}"] = {
            "input_norm": unit_scale(E),
            "attn": {
                "q_proj": normal_kernel(ks[0], E, H * D),
                "k_proj": normal_kernel(ks[1], E, Hkv * D),
                "v_proj": normal_kernel(ks[2], E, Hkv * D),
                "o_proj": normal_kernel(ks[3], H * D, E),
                "q_norm": unit_scale(D),
                "k_norm": unit_scale(D),
                "indexer": {
                    "q_proj": normal_kernel(ks[4], E, J * Di),
                    "k_proj": normal_kernel(ks[5], E, Di),
                    "weights_proj": normal_kernel(ks[6], E, J),
                    "k_norm": {**unit_scale(Di),
                               "bias": jnp.zeros((Di,), jnp.float32)},
                },
            },
            "post_norm": unit_scale(E),
            "moe": {
                "router": normal_kernel(ks[7], E, cfg.n_experts),
                "wi_gate": normal_kernel(ks[8], n, E, W)["kernel"],
                "wi_up": normal_kernel(ks[9], n, E, W)["kernel"],
                "wo": normal_kernel(ks[10], n, W, E)["kernel"],
            },
        }
    return params


def _rope_first_half(x, positions, theta):
    """RoPE on the first half of the last dim, the rest as it is."""
    half = x.shape[-1] // 2
    return jnp.concatenate(
        [rope(x[..., :half], positions, theta), x[..., half:]], axis=-1)


def _select(u, p, cfg: KeyeVlConfig):
    """The indexer on u (B, S, E), which it reads as a constant -> (I
    (B, S, S) float32, the mask (B, S, S) int8 of the keys each query
    attends); the caller stands in `attention/indexer`."""
    B, S, _ = u.shape
    J, Di = cfg.index_heads, cfg.index_dim
    u = jax.lax.stop_gradient(u)
    kernel = lambda name: p[name]["kernel"].astype(u.dtype)
    with jax.named_scope("proj"):
        positions = jnp.arange(S)
        q = _rope_first_half((u @ kernel("q_proj")).reshape(B, S, J, Di),
                             positions, cfg.rope_theta)
        k = layer_norm(u @ kernel("k_proj"), p["k_norm"], cfg.rms_eps)
        k = _rope_first_half(k[:, :, None], positions, cfg.rope_theta)[:, :, 0]
        w = jnp.matmul(u, kernel("weights_proj"),
                       preferred_element_type=jnp.float32) \
            * (J ** -0.5 * Di ** -0.5)
    with jax.named_scope("scores"):
        scores = named(index_scores(q, k, w, cfg.index_block),
                       "attention/indexer/scores")
    with jax.named_scope("select"):
        mask = select_top_k(scores, cfg.index_top_k, cfg.index_block)
        return scores, named(mask, "attention/indexer/select")


def _attention(x, p, cfg: KeyeVlConfig):
    """-> (the operator's result (B, S, E), the layer's indexer loss)."""
    q, k, v = attention_qkv(x, p, cfg.head_dim, cfg.rms_eps, jnp.arange,
                            cfg.rope_theta)
    with jax.named_scope("indexer"):
        scores, mask = _select(x, p["indexer"], cfg)
    with jax.named_scope("kernel"):
        o, lse = attention(q, k, v, mask=mask, with_lse=True)
    with jax.named_scope("indexer"), jax.named_scope("loss"):
        loss = indexer_loss(scores, mask, q, k, lse, cfg.index_block)
    return attention_out(o, p), loss


def _route(cfg: KeyeVlConfig):
    """-> route(xt, router) -> (weights (T, k) f32, experts (T, k) int32,
    the softmax's mean over the tokens (N,)) over all experts."""
    return functools.partial(softmax_route, top_k=cfg.top_k,
                             renormalise=cfg.norm_topk_prob)


def _layer(x, p, cfg: KeyeVlConfig):
    """-> (x, {the rows sent to each expert, the indexer's loss, the
    router's load-balancing loss})."""
    u = rms_norm(x, p["input_norm"], cfg.rms_eps)
    with jax.named_scope("attention"):
        y, loss = _attention(u, p["attn"], cfg)
    x = x + y
    u = rms_norm(x, p["post_norm"], cfg.rms_eps)
    with jax.named_scope("ffn"), jax.named_scope("moe"):
        y, rows, mean_prob = routed_layer(u, p["moe"], _route(cfg),
                                          cfg.n_experts, cfg.held, swiglu)
        balance = balance_loss(rows, mean_prob,
                               u.shape[0] * u.shape[1] * cfg.top_k)
    return x + y, {"rows": rows, "indexer_loss": loss, "aux_loss": balance}


def _hidden(params, tokens, cfg: KeyeVlConfig):
    """-> ((B, S, E) after the final norm, the routers' statistics, the
    indexers' loss and the routers' load-balancing loss, each summed over
    the layers)."""
    x, seconds = trunk(params, tokens, _layer, cfg)
    stats = routing_account(params, cfg.moe_layers,
                            [s["rows"] for s in seconds],
                            tokens.size * cfg.top_k, cfg.held)
    return x, dict(
        stats, indexer_loss=sum(s["indexer_loss"] for s in seconds),
        aux_loss=sum(s["aux_loss"] for s in seconds))


def forward(params, tokens, cfg: KeyeVlConfig):
    """tokens (B, S) int32 -> (logits (B, S, rows held) f32, the routers'
    and indexers' statistics)."""
    x, stats = _hidden(params, tokens, cfg)
    head = params["lm_head"]["kernel"].astype(cfg.compute_dtype)
    return jnp.matmul(x, head, preferred_element_type=jnp.float32), stats


def loss_fn(params, batch, cfg: KeyeVlConfig):
    """batch {"tokens": (B, S+1)} -> (the objective that is differentiated:
    next-token cross-entropy over the rows of the vocabulary held here +
    the indexers' loss + `cfg.aux_weight` x the routers' load-balancing
    loss; its parts: "lm_loss" the cross-entropy alone, "indexer_loss",
    "aux_loss"; "loss" the first two summed; the routers' statistics).  The
    indexers' loss and the other two do not mix: each side's gradient is
    exactly zero on the other's leaves.  The head's logits are made
    `cfg.loss_chunk_rows` rows at a time and never all held."""
    tokens = batch["tokens"]
    x, stats = _hidden(params, tokens[:, :-1], cfg)
    xent = head_and_loss(x, params["lm_head"], tokens[:, 1:],
                         cfg.loss_chunk_rows)
    objective = xent + stats["indexer_loss"] \
        + cfg.aux_weight * stats["aux_loss"]
    return objective, dict(stats, lm_loss=xent,
                           loss=xent + stats["indexer_loss"])


def make_train_step(cfg: KeyeVlConfig, optimizer):
    """train_step(params, opt_state, batch) -> (params, opt_state, out),
    to be jitted with its shardings and `donate_argnums=(0, 1)` as
    `gpt2.make_train_step`'s.  `out["loss"]` is the cross-entropy alone,
    `out["indexer_loss"]` the indexers' and `out["aux_loss"]` the routers'
    load-balancing loss; beside them the routers' account
    (`ops/moe.py:routing_account`), device values that cost nothing unless
    fetched."""
    return train_step(lambda params, batch: loss_fn(params, batch, cfg),
                      optimizer, cfg.compute_dtype)


def selected_pairs(seq_len: int, top_k: int) -> int:
    """(query, key) pairs a sequence attends: sum_t min(t + 1, top_k)."""
    full = min(top_k, seq_len)
    return full * (full + 1) // 2 + (seq_len - full) * top_k


def count_flops_per_token(cfg: KeyeVlConfig, seq_len: int) -> float:
    """Training (forward + backward) operations per token HERE, the work
    the model asks for whatever implements it: 6 x the parameters a token
    multiplies on this chip outside the indexer (the head's rows held, the
    four attention matrices, the router, the EXPECTED rows of held experts:
    top_k x held / experts of three matrices each) + 4 x the indexer's
    three matrices (forward and their own gradient: nothing goes back to
    its input) + per layer, with c = causal pairs a token and s = selected
    pairs a token: the main attention over the selected pairs, QK' and PV
    forward once and backward twice, 6 s H 2D; the index scores over every
    causal pair forward, 2 c J D_I, and their two backward products over
    the selected pairs, 4 s J D_I; the loss's target, QK' of every head
    over the selected pairs once, 2 s H D."""
    E, H, D = cfg.n_embd, cfg.n_head, cfg.head_dim
    J, Di = cfg.index_heads, cfg.index_dim
    attn = 2 * E * H * D + 2 * E * cfg.n_kv_head * D
    indexer = E * J * Di + E * Di + E * J
    routed = E * cfg.n_experts + cfg.top_k * cfg.n_held / cfg.n_experts \
        * 3 * E * cfg.expert_width
    c = (seq_len + 1) / 2
    s = selected_pairs(seq_len, cfg.index_top_k) / seq_len
    pairs = 6 * s * H * 2 * D + 2 * c * J * Di + 4 * s * J * Di \
        + 2 * s * H * D
    return 6 * (cfg.vocab_size * E + cfg.n_layer * (attn + routed)) \
        + cfg.n_layer * (4 * indexer + pairs)

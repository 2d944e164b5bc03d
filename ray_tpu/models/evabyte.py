"""EvaByte (`model_type` `evabyte`, `attention_class` `eva`: a 6.5 B
tokenizer-free decoder over bytes) for the Train path: every layer's mixer is
EVA attention (`ops/eva.py`), and the head predicts the next EIGHT bytes.

From the model's published `config.json` (32 layers, hidden 4,096, 32 heads
of 128, SwiGLU 11,008, 320 bytes and specials, `chunk_size` 16,
`window_size` 2,048, `num_pred_heads` 8, `rope_theta` 100,000,
`norm_add_unit_offset`, `fp32_skip_add`, `mixedp_attn`, `fp32_logits`,
`init_std` 0.01275).  Per layer, x the FLOAT32 stream, the products in the
compute type with float32 accumulation:

  u = RMSNorm(x): x rsqrt(mean x^2 + eps) (1 + w).
  q = RoPE(u W_q), k = RoPE(u W_k), v = u W_v; H heads of D = 128, no bias,
    rotate-half RoPE over the whole head at theta.
  chunks of c positions; chunk n of head h, with its learned phi_h, mu_h (D):
    a_t = softmax over the chunk's c positions of (k_t . phi_h);
    ks_n = sum_t a_t k_t + mu_h;  vs_n = sum_t a_t v_t.
    (The score is of the ROTATED keys with no further scale; the summaries
    carry no rotation of their own: assumptions the cell's file lists.)
  windows of w positions, aligned (whole chunks): query i attends the keys
    A_i = {j : j // w = i // w, j <= i} and the summaries
    B_i = {n : (c n) // w < i // w} under ONE softmax at D^-1/2:
    o_i = [sum_A e^(s q_i.k_j) v_j + sum_B e^(s q_i.ks_n) vs_n]
          / [sum_A e^(s q_i.k_j) + sum_B e^(s q_i.ks_n)], float32 inside.
  x <- x + o W_o;  x <- x + (silu(g W_gate) * (g W_up)) W_down, g =
    RMSNorm(x).
  after the last layer RMSNorm, then `n_pred_heads` heads as ONE matrix
    W_head (E, P V): head p at position i predicts the byte at i + 1 + p,
    logits in float32.  Loss: the mean over the heads of each head's mean
    cross-entropy over the positions that have a target.

``n_head`` is the heads HELD here (`n_head_published` the model's): one chip
of the `n_head_published / n_head` that share each layer holds a share of
W_q, W_k, W_v's columns and W_o's rows with their phi and mu, and computes
its heads' part of W_o's sum; the feed-forward, the norms and both ends are
whole.  Nothing stands in for the other chips or their exchange.
``first_layer`` is the published index of the first layer held (all layers
are alike: it names them and changes nothing).

What it shares with the other models: `models/layers.py` (RMSNorm, RoPE, the
projections into and out of attention, the SwiGLU, the walk over the layers,
the head's chunked loss, the mixed-precision step) and the flash kernels,
which are EVA's local half (`ops/flash_attention.py:BlockRule(aligned=w)`);
this file is the configuration, the table of parameters, the `_layer` and
the eight-offset loss.

Not here: serving (a window's keys and values beside a cache of summaries
that grows a row a chunk; the eight heads as a self-drafting decoder), and
the heads' exchange across the chips that share a layer.

`jax.named_scope`s (`models/layers.py:SCOPES`): embed, norm,
eva/{qkv,summary,local,remote,merge,out}, ffn/dense, head_and_loss,
optimizer_update.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.layers import (
    attention_out,
    attention_qkv,
    dense_ffn,
    head_and_weighted_loss,
    normal_kernel,
    num_params,  # noqa: F401  (`evabyte.num_params` is public)
    rms_norm,
    swiglu,
    train_step,
    trunk,
)
from ray_tpu.ops.eva import attended_pairs, eva_attention
from ray_tpu.parallel.context import get_mesh


@dataclass(frozen=True)
class EvaByteConfig:
    vocab_size: int = 320
    n_layer: int = 32
    first_layer: int = 0              # published index of the first held
    n_embd: int = 4096
    n_head: int = 32                  # the heads HELD here
    n_head_published: int = 32
    head_dim: int = 128
    ffn_width: int = 11008
    chunk: int = 16
    window: int = 2048
    n_pred_heads: int = 8
    rope_theta: float = 1e5
    rms_eps: float = 1e-5
    init_std: float = 0.01275
    norm_unit_offset: bool = True     # a norm's gain is 1 + w
    stream_dtype: Any = jnp.float32   # the residual stream (`fp32_skip_add`)
    compute_dtype: Any = jnp.bfloat16
    # jax.checkpoint each layer, keeping its attention's output and row
    # statistics and, of `layers.KEPT_NAMES`, those the chip has room for
    # over all layers (`layers.checkpoint_layer`)
    remat: bool = False
    loss_chunk_rows: int = 4096       # `layers.chunked_xent`


EVABYTE_6B = EvaByteConfig()
# four windows of four chunks: a shape the kernels decline (the plain form)
EVABYTE_TINY = EvaByteConfig(
    vocab_size=64, n_layer=2, n_embd=64, n_head=2, n_head_published=4,
    head_dim=16, ffn_width=96, chunk=4, window=16, n_pred_heads=3,
    loss_chunk_rows=32)


def init_params(rng, cfg: EvaByteConfig) -> Dict[str, Any]:
    """Normal(0, `init_std`) matrices; the norms' w 0 (the gain 1 + w);
    phi and mu Normal clipped to +-1, x D^-1/2.  Names are those
    `parallel/sharding.py:infer_param_logical_dims` lays out.  The head is
    ONE matrix (E, P V), head p its columns p V .. (p + 1) V."""
    E, H, D = cfg.n_embd, cfg.n_head, cfg.head_dim
    std = cfg.init_std
    keys = jax.random.split(rng, 2 + cfg.n_layer)
    zero = lambda: {"scale": jnp.zeros((E,), jnp.float32)}

    def vector(key):
        return jnp.clip(jax.random.normal(key, (H, D), jnp.float32), -1, 1) \
            * D ** -0.5

    params = {
        "embed_tokens": {"embedding": normal_kernel(
            keys[0], cfg.vocab_size, E, std=std)["kernel"]},
        "norm_f": zero(),
        "lm_head": normal_kernel(
            keys[1], E, cfg.n_pred_heads * cfg.vocab_size, std=std),
    }
    for i in range(cfg.n_layer):
        ks = jax.random.split(keys[2 + i], 9)
        params[f"layer_{i}"] = {
            "input_norm": zero(),
            "eva": {
                "q_proj": normal_kernel(ks[0], E, H * D, std=std),
                "k_proj": normal_kernel(ks[1], E, H * D, std=std),
                "v_proj": normal_kernel(ks[2], E, H * D, std=std),
                "o_proj": normal_kernel(ks[3], H * D, E, std=std),
                "phi": vector(ks[4]),
                "mu": vector(ks[5]),
            },
            "post_norm": zero(),
            "mlp": {
                "gate_proj": normal_kernel(ks[6], E, cfg.ffn_width, std=std),
                "up_proj": normal_kernel(ks[7], E, cfg.ffn_width, std=std),
                "down_proj": normal_kernel(ks[8], cfg.ffn_width, E, std=std),
            },
        }
    return params


def _norm(x, p, cfg: EvaByteConfig):
    """The float32 stream's RMSNorm, in the compute type for the products."""
    return rms_norm(x, p, cfg.rms_eps, cfg.norm_unit_offset).astype(
        cfg.compute_dtype)


def _mixer(u, m, cfg: EvaByteConfig):
    """u (B, S, E) the normed stream in the compute type -> the HELD heads'
    part of W_o's sum (B, S, E); the caller stands in `eva`."""
    mesh = get_mesh()
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "evabyte: the EVA kernels under a mesh of several devices are "
            "not written (a shard_map over batch and heads: no cell asks)")
    q, k, v = attention_qkv(u, m, cfg.head_dim, None, jnp.arange,
                            cfg.rope_theta)
    o = eva_attention(q, k, v, m["phi"], m["mu"], window=cfg.window,
                      chunk=cfg.chunk)
    return attention_out(o, m)


def _layer(x, p, cfg: EvaByteConfig):
    """x (B, S, E) in the stream's type -> (x, None)."""
    u = _norm(x, p["input_norm"], cfg)
    with jax.named_scope("eva"):
        y = _mixer(u, p["eva"], cfg)
    x = x + y
    g = _norm(x, p["post_norm"], cfg)
    with jax.named_scope("ffn"), jax.named_scope("dense"):
        y = dense_ffn(g, p["mlp"], swiglu)
    return x + y, None


def hidden(params, tokens, cfg: EvaByteConfig, streams: bool = False):
    """tokens (B, S) int32 -> (B, S, E) after the final norm, in the stream's
    type; with ``streams`` (that, the stream after each layer held)."""
    if streams:
        def watched(x, p, cfg):
            x, _ = _layer(x, p, cfg)
            return x, x
        return trunk(params, tokens, watched, cfg)
    return trunk(params, tokens, _layer, cfg)[0]


def forward(params, tokens, cfg: EvaByteConfig):
    """tokens (B, S) int32 -> logits (B, S, P, V) float32: head p's at
    position i are of the byte at i + 1 + p."""
    x = hidden(params, tokens, cfg).astype(cfg.compute_dtype)
    head = params["lm_head"]["kernel"].astype(cfg.compute_dtype)
    logits = jnp.matmul(x, head, preferred_element_type=jnp.float32)
    return logits.reshape(*tokens.shape, cfg.n_pred_heads, cfg.vocab_size)


def _targets(tokens, p: int):
    """tokens (B, S + 1), head p -> (its targets (B, S): the byte at
    i + 1 + p; which positions i have one (S,): the S - p first)."""
    S = tokens.shape[1] - 1
    at = jnp.arange(S)
    return (jnp.take(tokens, jnp.minimum(at + 1 + p, S), axis=1),
            at + p < S)


def loss_fn(params, batch, cfg: EvaByteConfig):
    """batch {"tokens": (B, S + 1)} -> (the loss, {"loss": it}): the mean
    over the P heads of head p's mean cross-entropy over the S - p positions
    i whose target, the byte at i + 1 + p, the batch holds.  A head's logits
    are made `cfg.loss_chunk_rows` rows at a time and never all held, each
    head a walk of `layers.head_and_weighted_loss` over its columns of the
    one matrix, the rows without a target at weight 0."""
    tokens = batch["tokens"]
    B, S = tokens.shape[0], tokens.shape[1] - 1
    P, V = cfg.n_pred_heads, cfg.vocab_size
    x = hidden(params, tokens[:, :-1], cfg).astype(cfg.compute_dtype)
    total = 0.0
    for p in range(P):
        targets, held = _targets(tokens, p)
        weights = jnp.broadcast_to(
            jnp.where(held, 1.0 / (P * B * (S - p)), 0.0), (B, S))
        head = {"kernel": params["lm_head"]["kernel"][:, p * V:(p + 1) * V]}
        total = total + head_and_weighted_loss(
            x, head, targets, weights, cfg.loss_chunk_rows)[0]
    return total, {"loss": total}


def make_train_step(cfg: EvaByteConfig, optimizer):
    """train_step(params, opt_state, batch) -> (params, opt_state, out), to
    be jitted with its shardings and `donate_argnums=(0, 1)` as
    `gpt2.make_train_step`'s.  `out["loss"]` is the eight heads' mean
    cross-entropy."""
    return train_step(lambda params, batch: loss_fn(params, batch, cfg),
                      optimizer, cfg.compute_dtype)


def pool_flops_per_token(cfg: EvaByteConfig) -> float:
    """Forward operations a token of ONE layer's summaries, a multiply and
    an add two: k . phi, a k and a v, 2 D a head each."""
    return cfg.n_head * 6 * cfg.head_dim


def count_flops_per_token(cfg: EvaByteConfig, seq_len: int) -> float:
    """Training (forward + backward) operations per token trained HERE, the
    work the model asks for whatever implements it: 6 x the parameters a
    token multiplies on this chip (a layer's four attention matrices at the
    heads held, the feed-forward's three whole; the P heads' matrix) + per
    layer the attention products over the pairs ATTENDED, |A_i| + |B_i|
    (QK' and PV forward once and backward twice, 2 D operations a pair and
    head each) and the pooling, forward once and backward twice.
    Recomputation not counted."""
    E, H, D = cfg.n_embd, cfg.n_head, cfg.head_dim
    n = cfg.n_layer * (4 * E * H * D + 3 * E * cfg.ffn_width) \
        + cfg.n_pred_heads * cfg.vocab_size * E
    pairs = sum(attended_pairs(seq_len, cfg.window, cfg.chunk)) / seq_len
    return 6 * n + cfg.n_layer * (6 * pairs * H * 2 * D
                                  + 3 * pool_flops_per_token(cfg))

"""Ouro — a looped language model for the Train path: one stack of layers
walked several times over the same parameters, an exit gate after every
walk, and a loss over all the walks' heads.

Zhu et al., "Scaling Latent Reasoning via Looped Language Models"
(arXiv:2510.25741); layer equations as `ByteDance/Ouro-2.6B`'s public
`modeling_ouro.py` (`model_type` `ouro`):

  a layer, norms before AND after each operator ("sandwich", four gains):
    h = x + N2(Attn(N1(x)));  y = h + N4(FFN(N3(h))).
    Attn: q, k, v, o without bias, rotate-half RoPE over all of a head's
    dims, causal softmax at head_dim^-1/2, no norm on q or k.
    FFN(u) = W_down(silu(W_gate u) * W_up u).
  the loop: x_0 the embedding; for t = 1..T (`total_ut_steps`):
    x_t = N_f(layers(x_{t-1})), the SAME layers and the same final norm
    every time; the normed x_t is what walk t + 1 starts from and what the
    head and the gate read.
  the gate, one for the model: lambda_t = sigmoid(w_g . x_t + b_g), a token;
    the exit distribution p_1 = lambda_1, p_t = lambda_t prod_{j<t}
    (1 - lambda_j) for t < T, p_T = prod_{j<T} (1 - lambda_j).
  the loss (the paper's first stage), a token:
    sum_t p_t CE_t - beta H(p), CE_t the next-token cross-entropy of
    W_head x_t, H(p) = -sum_t p_t log p_t; the mean over the tokens; every
    leaf, the gate's among them, is trained by it.

Not here: exit at inference (the last walk's logits are the model's:
`forward`), a key/value cache per (walk, layer), and the paper's second
stage, which trains the gate alone.

The walk over the layers T times is `models/layers.py:trunk`'s (``walks``),
as are RMSNorm, RoPE, the projections into and out of attention, the SwiGLU,
what a recomputed layer keeps, the head with its chunked loss, the rows
weighted, and the mixed-precision step; the kernels between the projections
are `parallel/attention.py`'s.  This file is the configuration,
`init_params`, the sandwich `_layer`, the gate and the objective.  A weight's
gradient is the sum of its T uses: `layers.train_step` hands the objective
the matrices cast to the compute type once, so the T cotangents meet in that
type before the float32 master sees their sum (`tests/test_ouro.py` has the
reading).

`jax.named_scope`s (`models/layers.py:SCOPES`): embed, norm,
attention/{qkv,kernel,out}, ffn/dense, head_and_loss, exit_gate,
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
    num_params,  # noqa: F401  (`ouro.num_params` is public)
    rms_norm,
    swiglu,
    train_step,
    trunk,
    unit_scale,
)
from ray_tpu.parallel.attention import attention


@dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49152
    n_layer: int = 48
    n_head: int = 16
    n_kv_head: int = 16
    head_dim: int = 128
    n_embd: int = 2048
    dense_width: int = 5632
    n_walk: int = 4               # the published `total_ut_steps`
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    entropy_weight: float = 0.05  # the loss's beta
    compute_dtype: Any = jnp.bfloat16
    # jax.checkpoint each of the n_walk x n_layer calls of a layer, keeping
    # its attention kernel's output and row statistics and, of
    # `layers.KEPT_NAMES` (W_q's, W_k's, W_v's and W_o's results, the
    # feed-forward's gate and up), those the chip has room for over all the
    # calls (`layers.checkpoint_layer`)
    remat: bool = True
    # rows of the head's logits alive at once (`layers.chunked_xent`):
    # the n_walk heads of 16,384 tokens are 65,536 rows of 49,152 logits
    loss_chunk_rows: int = 2048


OURO_2_6B = OuroConfig()
OURO_TINY = OuroConfig(vocab_size=512, n_layer=2, n_head=4, n_kv_head=4,
                       head_dim=16, n_embd=64, dense_width=160, n_walk=3,
                       loss_chunk_rows=32)


def init_params(rng, cfg: OuroConfig) -> Dict[str, Any]:
    """Normal(0, 0.02) matrices, the gate's among them, unit norm gains,
    the gate's bias 0.  Names are those `parallel/sharding.py:
    infer_param_logical_dims` lays out."""
    E, W = cfg.n_embd, cfg.dense_width
    H, Hkv, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    keys = jax.random.split(rng, 3 + cfg.n_layer)
    params = {
        "embed_tokens": {
            "embedding": normal_kernel(keys[0], cfg.vocab_size, E)["kernel"]},
        "norm_f": unit_scale(E),
        "lm_head": normal_kernel(keys[1], E, cfg.vocab_size),
        "exit_gate": dict(normal_kernel(keys[2], E, 1),
                          bias=jnp.zeros((1,), jnp.float32)),
    }
    for i in range(cfg.n_layer):
        ks = jax.random.split(keys[3 + i], 7)
        params[f"layer_{i}"] = {
            "input_norm": unit_scale(E),
            "attn": {"q_proj": normal_kernel(ks[0], E, H * D),
                     "k_proj": normal_kernel(ks[1], E, Hkv * D),
                     "v_proj": normal_kernel(ks[2], E, Hkv * D),
                     "o_proj": normal_kernel(ks[3], H * D, E)},
            "input_norm_2": unit_scale(E),
            "post_norm": unit_scale(E),
            "mlp": {"gate_proj": normal_kernel(ks[4], E, W),
                    "up_proj": normal_kernel(ks[5], E, W),
                    "down_proj": normal_kernel(ks[6], W, E)},
            "post_norm_2": unit_scale(E),
        }
    return params


def _attention(x, p, cfg: OuroConfig):
    # no norm on q or k: the parameters have no gain for one
    q, k, v = attention_qkv(x, p, cfg.head_dim, positions=jnp.arange,
                            theta=cfg.rope_theta)
    with jax.named_scope("kernel"):
        o = attention(q, k, v)
    return attention_out(o, p)


def _layer(x, p, cfg: OuroConfig):
    """The sandwich: a norm before each operator and one on its result,
    before the residual takes it."""
    u = rms_norm(x, p["input_norm"], cfg.rms_eps)
    with jax.named_scope("attention"):
        a = _attention(u, p["attn"], cfg)
    x = x + rms_norm(a, p["input_norm_2"], cfg.rms_eps)
    u = rms_norm(x, p["post_norm"], cfg.rms_eps)
    with jax.named_scope("ffn"), jax.named_scope("dense"):
        f = dense_ffn(u, p["mlp"], swiglu)
    return x + rms_norm(f, p["post_norm_2"], cfg.rms_eps), None


def _exit_log_probs(states, gate):
    """Every walk's normed state (T, B, S, E) -> log p (T, B, S) float32,
    the exit distribution's logarithm: p_t = lambda_t prod_{j<t}
    (1 - lambda_j), the last walk taking what is left.  In logarithms: a
    gate that saturates gives a p of 0 and an entropy term of 0, never a
    log of 0."""
    z = jnp.matmul(states, gate["kernel"].astype(states.dtype),
                   preferred_element_type=jnp.float32)[..., 0] + gate["bias"]
    stay = jax.nn.log_sigmoid(-z)                  # log(1 - lambda_t)
    stayed = jnp.cumsum(stay, axis=0) - stay       # sum over j < t
    return jnp.concatenate(
        [jax.nn.log_sigmoid(z[:-1]) + stayed[:-1], stayed[-1:]], axis=0)


def hidden(params, tokens, cfg: OuroConfig):
    """tokens (B, S) int32 -> (every walk's normed state (T, B, S, E), the
    exit distribution's logarithm (T, B, S) float32)."""
    states, _ = trunk(params, tokens, _layer, cfg, walks=cfg.n_walk)
    with jax.named_scope("exit_gate"):
        return states, _exit_log_probs(states, params["exit_gate"])


def forward(params, tokens, cfg: OuroConfig):
    """tokens (B, S) int32 -> (every walk's logits (T, B, S, vocab) f32, the
    last of them the model's; the exit distribution (T, B, S))."""
    states, log_p = hidden(params, tokens, cfg)
    head = params["lm_head"]["kernel"].astype(cfg.compute_dtype)
    return (jnp.matmul(states, head, preferred_element_type=jnp.float32),
            jnp.exp(log_p))


def loss_fn(params, batch, cfg: OuroConfig):
    """batch {"tokens": (B, S+1)} -> (the objective, its parts): the mean
    over the tokens of sum_t p_t CE_t - beta H(p).  `parts`: "loss" the
    objective, "xent" (T,) each walk's mean cross-entropy, "exit" (T,) the
    mean exit distribution, "entropy" the mean H(p).  The T heads' logits
    are made `cfg.loss_chunk_rows` rows at a time, once, and never all
    held: p goes into the head's loss as its rows' weights, so the head
    forms its gradient as it walks, and the gradient reaches the gate
    through the weights; the rows' losses it hands back are for `parts`
    and carry none."""
    tokens = batch["tokens"]
    states, log_p = hidden(params, tokens[:, :-1], cfg)
    targets = jnp.broadcast_to(tokens[:, 1:], states.shape[:-1])
    with jax.named_scope("exit_gate"):
        p = jnp.exp(log_p)
    weighted, xent = head_and_weighted_loss(
        states, params["lm_head"], targets, p, cfg.loss_chunk_rows)
    with jax.named_scope("exit_gate"):
        entropy = jnp.mean(-jnp.sum(p * log_p, axis=0))
        loss = weighted / tokens[:, 1:].size - cfg.entropy_weight * entropy
        return loss, {"loss": loss, "xent": jnp.mean(xent, axis=(1, 2)),
                      "exit": jnp.mean(p, axis=(1, 2)),
                      "entropy": entropy}


def make_train_step(cfg: OuroConfig, optimizer):
    """train_step(params, opt_state, batch) -> (params, opt_state, out),
    to be jitted with its shardings and `donate_argnums=(0, 1)` as
    `gpt2.make_train_step`'s.  `out` is `loss_fn`'s parts.  Mixed
    precision as `layers.train_step` says."""
    return train_step(lambda params, batch: loss_fn(params, batch, cfg),
                      optimizer, cfg.compute_dtype)


def count_flops_per_token(cfg: OuroConfig, seq_len: int) -> float:
    """Training (forward + backward) operations per token: 6 N with N the
    parameters a token multiplies over all its walks (T times a layer's
    seven matrices, T times the head) + the attention products of the
    T x n calls over the causal pairs ((S + 1) / 2 keys a query: QK' and PV
    forward once and backward twice, 2 D operations a pair and head
    each)."""
    E, D = cfg.n_embd, cfg.head_dim
    per_layer = (2 * E * cfg.n_head * D + 2 * E * cfg.n_kv_head * D
                 + 3 * E * cfg.dense_width)
    calls = cfg.n_walk * cfg.n_layer
    n = calls * per_layer + cfg.n_walk * cfg.vocab_size * E
    return 6 * n + calls * 6 * ((seq_len + 1) / 2) * cfg.n_head * 2 * D

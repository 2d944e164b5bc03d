"""SDAR (`model_type` `sdar_moe`: JetLM's SDAR-30B-A3B-Chat) for the Train
path: a Qwen3-MoE trunk trained as a BLOCK-DIFFUSION model.  Nothing is
predicted from the left: a noised copy of every sequence runs beside the
clean one, a masked position is predicted from that position's own logits,
and attention follows a rule of blocks with no diagonal.

JetLM / Shanghai AI Lab, "SDAR: A Synergistic Diffusion-AutoRegression
Paradigm for Scalable Sequence Generation" (2025); the training form is
Arriola et al., "Block Diffusion" (ICLR 2025), BD3-LM's vectorised
objective, which SDAR's adaptation stage keeps.  L tokens a sequence, block
length b, block(i) = i // b, MASK an id of its own:

  noise, step n, a sequence: t_k = 1 - u_k, u_k uniform on [0, 1), one a
    block; r_i uniform on [0, 1), one a token; m_i = [r_i < t_block(i)];
    xn_i = MASK where m_i, else x_i.  (seed, n) alone decide it.
  input: every layer sees 2 L rows a sequence, the clean sequence x (rows
    c_i = i) and then the noised copy xn (rows n_i = L + i); c_i and n_i
    both stand at position i for RoPE.
  a layer (Qwen3-MoE's, whose keys the config carries), pre-norm, no bias:
    u = RMSNorm(x); q = u W_q in H heads, k = u W_k and v = u W_v in H_kv;
    q and k through an RMSNorm over the head's D with a gain, then
    rotate-half RoPE over all D at `rope_theta`; o = softmax(q k' D^-1/2
    under the rule) v; x += o W_o.  u = RMSNorm(x); p = softmax(u W_r) in
    float32 over all the experts; the `top_k` largest, their weights over
    their sum (`norm_topk_prob`); x += sum over the chosen experts HELD
    here of w_e SwiGLU_e(u).
  the rule (`ops/flash_attention.py:BlockRule(b, 2)`): c_i attends c_j iff
    block(j) <= block(i) (block-causal: a block sees itself whole); n_i
    attends c_j iff block(j) < block(i) (strictly earlier blocks, clean)
    and n_j iff block(j) = block(i) (its own block, noised, both ways);
    c_i attends no n_j.  L (L + b) pairs a head of the square's 4 L^2.
  loss: the final RMSNorm and the untied head over the NOISED rows only,
    no shift (row n_i predicts x_i): L_D = 1 / (batch x L) sum_i
    (m_i / t_block(i)) CE(n_i, x_i).  The objective is L_D + `aux_weight`
    x L_B, the routers' load-balancing loss (Switch Transformer's:
    `ops/moe.py:balance_loss`) over all 2 L rows.  `out["loss"]` is L_D; E[m / t]
    is 1, so it starts near log(vocabulary).

``held`` = (first, count): one chip's share of an expert-parallel layer:
router and attention are whole; only the held experts'
matrices exist and only their part of the sum is computed
(`ops/moe.py:moe_dispatch`).  `vocab_size` is the rows of embedding and
head held here, `mask_token` the row among them that is MASK.

Not here: generation (a block at a time over a key/value cache, several of
a block's tokens unmasked a step by confidence), which is serving.

What it shares with the other models: `models/layers.py` (RMSNorm, RoPE,
the projections into and out of attention, the SwiGLU, the routed layer,
the walk over the layers, the head with its chunked loss, the rows
weighted, and the mixed-precision step, which hands this objective the
optimizer's count of updates), `parallel/attention.py` (the flash kernels,
here under a rule) and `ops/moe.py` (the softmax route, its balance loss,
the routers' account); the names are those `parallel/sharding.py` lays out.

`jax.named_scope`s (`models/layers.py:SCOPES`): diffusion, embed, norm,
attention/{qkv,kernel,out}, ffn/moe/{route,dispatch,experts,combine},
head_and_loss, optimizer_update.
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
    head_and_weighted_loss,
    normal_kernel,
    num_params,  # noqa: F401  (`sdar.num_params` is public)
    rms_norm,
    routed_layer,
    swiglu,
    train_step,
    trunk,
    unit_scale,
)
from ray_tpu.ops.flash_attention import BlockRule
from ray_tpu.ops.moe import balance_loss, routing_account, softmax_route
from ray_tpu.parallel.attention import attention
from ray_tpu.util import tracing

# folded into a run's key before the step's number, so that the noise is no
# stream `init_params` draws from the same seed
_NOISE_STREAM = 0x5DA2


@dataclass(frozen=True)
class SdarConfig:
    vocab_size: int = 151936          # rows of embedding and head held here
    # MASK's row, as SDAR's public generation code passes it (`mask_id`)
    mask_token: int = 151669
    # tokens a block: the config.json has no key for it; the family's models
    # without a `-b<n>` suffix are the block-4 ones
    block_length: int = 4
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
    # `router_aux_loss_coef` (Qwen3-MoE's).  A chip's SHARE of the experts
    # needs far more, as `models/keye_vl.py` says; the cell's file states it
    aux_weight: float = 0.001
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    compute_dtype: Any = jnp.bfloat16
    # jax.checkpoint each layer, keeping its attention kernel's output and
    # row statistics and, of `layers.KEPT_NAMES`, those the chip has room
    # for over all layers (`layers.checkpoint_layer`)
    remat: bool = False
    loss_chunk_rows: int = 2048       # `layers.chunked_xent`

    @property
    def n_held(self) -> int:
        return self.held[1] if self.held else self.n_experts

    @property
    def moe_layers(self):
        return range(self.n_layer)


SDAR_30B_A3B = SdarConfig()
SDAR_TINY = SdarConfig(
    vocab_size=512, mask_token=500, n_layer=2, n_head=8, n_kv_head=2,
    head_dim=16, n_embd=64, expert_width=24, n_experts=8, top_k=3,
    loss_chunk_rows=32)


def init_params(rng, cfg: SdarConfig) -> Dict[str, Any]:
    """Normal(0, 0.02) matrices, unit norm gains.  Names are those
    `parallel/sharding.py:infer_param_logical_dims` lays out; the experts'
    stacks hold the `cfg.n_held` experts that live here."""
    E, H, Hkv, D = cfg.n_embd, cfg.n_head, cfg.n_kv_head, cfg.head_dim
    keys = jax.random.split(rng, 2 + cfg.n_layer)
    params = {
        "embed_tokens": {
            "embedding": normal_kernel(keys[0], cfg.vocab_size, E)["kernel"]},
        "norm_f": unit_scale(E),
        "lm_head": normal_kernel(keys[1], E, cfg.vocab_size),
    }
    for i in range(cfg.n_layer):
        ks = jax.random.split(keys[2 + i], 8)
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


def noise_key(seed: int):
    """The key a run's noise is drawn from: the step folds its number in."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), _NOISE_STREAM)


def draw_noise(key, count, batch: int, seq_len: int, block: int):
    """Step ``count``'s noise -> (masked (batch, seq_len) bool, the rows'
    weights m / t (batch, seq_len) float32): one level t = 1 - u in (0, 1]
    a block, u uniform on [0, 1); every token of the block masked
    independently with probability t (a linear schedule: alpha_t = 1 - t);
    a masked row weighs 1 / t, which is alpha_t' / (1 - alpha_t) up to its
    sign."""
    levels, tokens = jax.random.split(jax.random.fold_in(key, count))
    t = 1.0 - jax.random.uniform(levels, (batch, seq_len // block))
    t = jnp.repeat(t, block, axis=1)
    masked = jax.random.uniform(tokens, (batch, seq_len)) < t
    return masked, masked / t


def _attention(x, p, cfg: SdarConfig):
    """x (B, 2 L, E): a sequence's clean rows and then its noised ones."""
    # a noised row stands where its clean one does
    q, k, v = attention_qkv(x, p, cfg.head_dim, cfg.rms_eps,
                            lambda S: jnp.arange(S) % (S // 2),
                            cfg.rope_theta)
    with jax.named_scope("kernel"):
        o = attention(q, k, v, causal=BlockRule(cfg.block_length, 2))
    return attention_out(o, p)


def _route(cfg: SdarConfig):
    """-> route(xt, router) -> (weights (T, k) f32, experts (T, k) int32,
    the softmax's mean over the rows (N,)) over all experts."""
    return functools.partial(softmax_route, top_k=cfg.top_k,
                             renormalise=cfg.norm_topk_prob)


def _layer(x, p, cfg: SdarConfig):
    """-> (x, {the rows sent to each expert, the router's load-balancing
    loss}), over both kinds of row alike."""
    u = rms_norm(x, p["input_norm"], cfg.rms_eps)
    with jax.named_scope("attention"):
        y = _attention(u, p["attn"], cfg)
    x = x + y
    u = rms_norm(x, p["post_norm"], cfg.rms_eps)
    with jax.named_scope("ffn"), jax.named_scope("moe"):
        y, rows, mean_prob = routed_layer(u, p["moe"], _route(cfg),
                                          cfg.n_experts, cfg.held, swiglu)
        balance = balance_loss(rows, mean_prob,
                               u.shape[0] * u.shape[1] * cfg.top_k)
    return x + y, {"rows": rows, "aux_loss": balance}


def noised_hidden(params, tokens, masked, cfg: SdarConfig):
    """tokens (B, L) int32 and which of them are masked (B, L) bool -> (the
    NOISED rows after the final norm (B, L, E), the routers' statistics
    and load-balancing loss over all 2 L rows).  The clean rows are walked
    for the keys and values they give the noised ones and go no further."""
    L = tokens.shape[1]
    with jax.named_scope("diffusion"):
        tracing.count("diffusion.rows", 2 * L)
        tracing.count("diffusion.rows_noised", L)
        tracing.count("diffusion.block_length", cfg.block_length)
        both = jnp.concatenate(
            [tokens, jnp.where(masked, cfg.mask_token, tokens)], axis=1)
    x, seconds = trunk(params, both, _layer, cfg)
    stats = routing_account(params, cfg.moe_layers,
                            [s["rows"] for s in seconds],
                            both.size * cfg.top_k, cfg.held)
    with jax.named_scope("diffusion"):
        return x[:, L:], dict(
            stats, aux_loss=sum(s["aux_loss"] for s in seconds))


def forward(params, tokens, masked, cfg: SdarConfig):
    """-> (the noised rows' logits (B, L, rows held) f32, the routers'
    statistics): row i is the model's distribution over x_i."""
    x, stats = noised_hidden(params, tokens, masked, cfg)
    head = params["lm_head"]["kernel"].astype(cfg.compute_dtype)
    return jnp.matmul(x, head, preferred_element_type=jnp.float32), stats


def loss_fn(params, batch, cfg: SdarConfig, key, count):
    """batch {"tokens": (B, L + 1)}, the first L of which are the sequence
    (the column a next-token model shifts by is not read); ``key`` the
    run's (`noise_key`), ``count`` the step's number -> (the objective L_D
    + `cfg.aux_weight` x L_B; its parts: "loss" L_D, "aux_loss" L_B,
    "masked_share" the mean of m, the routers' statistics).  The noised
    rows' logits are made `cfg.loss_chunk_rows` rows at a time and never all
    held; the clean rows' never."""
    tokens = batch["tokens"][:, :-1]
    B, L = tokens.shape
    with jax.named_scope("diffusion"):
        masked, weights = draw_noise(key, count, B, L, cfg.block_length)
    x, stats = noised_hidden(params, tokens, masked, cfg)
    weighted, _ = head_and_weighted_loss(x, params["lm_head"], tokens,
                                         weights, cfg.loss_chunk_rows)
    with jax.named_scope("diffusion"):
        loss = weighted / tokens.size
        return loss + cfg.aux_weight * stats["aux_loss"], dict(
            stats, loss=loss, masked_share=jnp.mean(masked))


def make_train_step(cfg: SdarConfig, optimizer, seed: int):
    """train_step(params, opt_state, batch) -> (params, opt_state, out),
    to be jitted with its shardings and `donate_argnums=(0, 1)` as
    `gpt2.make_train_step`'s.  The step draws its own noise, from ``seed``
    (bound here) and the optimizer's count of updates in ``opt_state``
    (`layers.train_step` hands it over): the same batch at every step is
    noised anew.  `out["loss"]` is L_D, `out["aux_loss"]` the routers'
    load-balancing loss; beside them the routers' account
    (`ops/moe.py:routing_account`), device values that cost nothing unless
    fetched."""
    key = noise_key(seed)
    return train_step(
        lambda params, batch, count: loss_fn(params, batch, cfg, key, count),
        optimizer, cfg.compute_dtype, counted=True)


def attended_pairs(seq_len: int, block: int) -> int:
    """(query, key) pairs a sequence of ``seq_len`` tokens attends, a head,
    over its 2 x seq_len rows: the clean rows L (L + b) / 2, the noised
    rows' clean keys L (L - b) / 2 and their own blocks L b."""
    return seq_len * (seq_len + block)


def count_flops_per_token(cfg: SdarConfig, seq_len: int) -> float:
    """Training (forward + backward) operations per CLEAN token trained
    HERE, the work the model asks for whatever implements it: 6 x the
    parameters a token's two rows multiply on this chip (in every layer but
    the last both rows through the four attention matrices, the router and
    the EXPECTED rows of held experts, top_k x held / experts of three
    matrices each; in the last layer the noised row through all of it and
    the clean row through W_k and W_v alone, which is all the noised rows
    need of it; the head's rows held, once) + per layer the attention
    products over the attended pairs (L + b a token: QK' and PV forward once
    and backward twice, 2 D operations a pair and head each)."""
    E, H, D = cfg.n_embd, cfg.n_head, cfg.head_dim
    kv = 2 * E * cfg.n_kv_head * D
    attn = 2 * E * H * D + kv
    routed = E * cfg.n_experts + cfg.top_k * cfg.n_held / cfg.n_experts \
        * 3 * E * cfg.expert_width
    n = (2 * cfg.n_layer - 1) * (attn + routed) + kv + cfg.vocab_size * E
    pairs = attended_pairs(seq_len, cfg.block_length) / seq_len
    return 6 * n + cfg.n_layer * 6 * pairs * H * 2 * D

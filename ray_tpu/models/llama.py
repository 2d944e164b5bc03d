"""Llama-family decoder (RMSNorm + RoPE + SwiGLU + GQA) with a KV-cache
decode path — the serving flagship (BASELINE.json: "Ray Serve Llama-2-7B JAX
inference deployment").

Decode is a `lax.scan`-friendly single-token step over a static-shape KV
cache (XLA-compatible: no dynamic shapes; position is a carried index), so
the whole generate loop compiles once.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.layers import rms_norm, rope
from ray_tpu.ops.flash_attention import flash_attention_bshd


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    n_layer: int = 32
    n_head: int = 32
    n_kv_head: int = 32
    n_embd: int = 4096
    intermediate: int = 11008
    max_seq: int = 4096
    rope_theta: float = 10000.0
    compute_dtype: Any = jnp.bfloat16

    @property
    def head_dim(self):
        return self.n_embd // self.n_head


LLAMA_7B = LlamaConfig()
LLAMA_TINY = LlamaConfig(vocab_size=256, n_layer=2, n_head=4, n_kv_head=2,
                         n_embd=64, intermediate=128, max_seq=128)


def init_params(rng, cfg: LlamaConfig) -> Dict[str, Any]:
    std = 0.02
    keys = jax.random.split(rng, 2 + cfg.n_layer)
    D = cfg.head_dim

    def normal(key, shape):
        return jax.random.normal(key, shape, jnp.float32) * std

    params = {
        "embed_tokens": {"embedding": normal(keys[0], (cfg.vocab_size, cfg.n_embd))},
        "norm_f": {"scale": jnp.ones((cfg.n_embd,))},
        "lm_head": {"kernel": normal(keys[1], (cfg.n_embd, cfg.vocab_size))},
    }
    for i in range(cfg.n_layer):
        ks = jax.random.split(keys[2 + i], 7)
        params[f"layer_{i}"] = {
            "input_norm": {"scale": jnp.ones((cfg.n_embd,))},
            "attn": {
                "q_proj": {"kernel": normal(ks[0], (cfg.n_embd, cfg.n_head * D))},
                "k_proj": {"kernel": normal(ks[1], (cfg.n_embd, cfg.n_kv_head * D))},
                "v_proj": {"kernel": normal(ks[2], (cfg.n_embd, cfg.n_kv_head * D))},
                "o_proj": {"kernel": normal(ks[3], (cfg.n_head * D, cfg.n_embd))},
            },
            "post_norm": {"scale": jnp.ones((cfg.n_embd,))},
            "mlp": {
                "gate_proj": {"kernel": normal(ks[4], (cfg.n_embd, cfg.intermediate))},
                "up_proj": {"kernel": normal(ks[5], (cfg.n_embd, cfg.intermediate))},
                "down_proj": {"kernel": normal(ks[6], (cfg.intermediate, cfg.n_embd))},
            },
        }
    return params


def _repeat_kv(x, n_rep: int):
    if n_rep == 1:
        return x
    B, S, H, D = x.shape
    return jnp.repeat(x, n_rep, axis=2)


def _attn_block(x, p, cfg: LlamaConfig, positions, cache=None,
                cache_index=None):
    B, S, E = x.shape
    H, Hk, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    q = (x @ p["q_proj"]["kernel"].astype(x.dtype)).reshape(B, S, H, D)
    k = (x @ p["k_proj"]["kernel"].astype(x.dtype)).reshape(B, S, Hk, D)
    v = (x @ p["v_proj"]["kernel"].astype(x.dtype)).reshape(B, S, Hk, D)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    new_cache = None
    if cache is not None:
        ck, cv = cache  # (B, max_seq, Hk, D)
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                          (0, cache_index, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                          (0, cache_index, 0, 0))
        new_cache = (ck, cv)
        # decode: attend q (S tokens) over cache prefix with position mask
        kk = _repeat_kv(ck, H // Hk).transpose(0, 2, 1, 3)
        vv = _repeat_kv(cv, H // Hk).transpose(0, 2, 1, 3)
        qq = q.transpose(0, 2, 1, 3)
        s = jnp.einsum("bhqd,bhkd->bhqk", qq.astype(jnp.float32),
                       kk.astype(jnp.float32)) * D ** -0.5
        kv_pos = jnp.arange(ck.shape[1])
        # causal over absolute positions: query at abs position p sees cache
        # slots 0..p (slots beyond the write frontier are zero AND masked)
        mask = kv_pos[None, None, None, :] <= positions[:, None, :, None]
        s = jnp.where(mask, s, -1e30)
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1),
                       vv.astype(jnp.float32)).astype(x.dtype)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, H * D)
    else:
        # prefill: the kernels read the Hk key/value heads as they are
        # (query head h on head h // (H / Hk)).  With Hk == H the
        # layout-native lane kernel runs (128-dim heads map 1:1 onto lane
        # blocks: no (B,S,H,D) <-> (B,H,S,D) transposes); grouped queries
        # take the head-major kernels
        o = flash_attention_bshd(q, k, v, True)
        o = o.reshape(B, S, H * D)
    return o @ p["o_proj"]["kernel"].astype(x.dtype), new_cache


def _mlp_block(x, p):
    g = jax.nn.silu(x @ p["gate_proj"]["kernel"].astype(x.dtype))
    u = x @ p["up_proj"]["kernel"].astype(x.dtype)
    return (g * u) @ p["down_proj"]["kernel"].astype(x.dtype)


def forward(params, tokens, cfg: LlamaConfig, caches=None, cache_index=None,
            positions=None):
    """tokens (B, S) -> (logits, new_caches)."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    x = params["embed_tokens"]["embedding"][tokens].astype(cfg.compute_dtype)
    new_caches = []
    for i in range(cfg.n_layer):
        p = params[f"layer_{i}"]
        h, nc = _attn_block(rms_norm(x, p["input_norm"]), p["attn"], cfg,
                            positions,
                            None if caches is None else caches[i],
                            cache_index)
        x = x + h
        x = x + _mlp_block(rms_norm(x, p["post_norm"]), p["mlp"])
        new_caches.append(nc)
    x = rms_norm(x, params["norm_f"]).astype(jnp.float32)
    logits = x @ params["lm_head"]["kernel"]
    return logits, (new_caches if caches is not None else None)


def init_cache(cfg: LlamaConfig, batch_size: int, dtype=None):
    dtype = dtype or cfg.compute_dtype
    D = cfg.head_dim
    return [
        (jnp.zeros((batch_size, cfg.max_seq, cfg.n_kv_head, D), dtype),
         jnp.zeros((batch_size, cfg.max_seq, cfg.n_kv_head, D), dtype))
        for _ in range(cfg.n_layer)
    ]


def generate(params, prompt_tokens, cfg: LlamaConfig, max_new_tokens: int,
             temperature: float = 0.0, rng=None):
    """Greedy/temperature sampling with a static-shape KV cache.

    prompt_tokens: (B, S_prompt) int32.  Returns (B, S_prompt+max_new).
    """
    B, S0 = prompt_tokens.shape
    caches = init_cache(cfg, B)
    positions = jnp.broadcast_to(jnp.arange(S0), (B, S0))
    logits, caches = forward(params, prompt_tokens, cfg, caches, 0, positions)
    last = logits[:, -1]
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def sample(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits, -1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature).astype(jnp.int32)

    def step(carry, _):
        caches, last_logits, pos, key = carry
        key, sub = jax.random.split(key)
        tok = sample(last_logits, sub)  # (B,)
        positions = jnp.full((B, 1), pos, jnp.int32)
        logits, caches = forward(params, tok[:, None], cfg, caches, pos,
                                 positions)
        return (caches, logits[:, -1], pos + 1, key), tok

    (_, _, _, _), toks = jax.lax.scan(
        step, (caches, last, jnp.int32(S0), rng), None, length=max_new_tokens
    )
    return jnp.concatenate([prompt_tokens, toks.T], axis=1)

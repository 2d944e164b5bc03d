"""The plain reference for the `deepseek_v3` family: what `correct` is
judged against.

DeepSeek-AI, "DeepSeek-V3 Technical Report" (arXiv:2412.19437) and the
public `modeling_deepseek_v3.py`, for a config with `q_lora_rank` null and
`n_group` = `topk_group` = 1 (kanana-2-30b-a3b's).  x is (tokens, E); no
projection has a bias; RMSNorm has a learned gain.

    h = x + Attn(RMSNorm(x));  y = h + F(RMSNorm(h));  final RMSNorm; an
      untied head; mean next-token cross-entropy, and nothing beside it.
    F: a SwiGLU `down(silu(gate(u)) * up(u))` in the leading dense layers,
      the mixture after.
    Attn: q = u Wq, per head [q_nope | q_rope];  [c | k_r] = u Wkv_a;
      c = RMSNorm(c);  per head [k_nope | v] = c Wkv_b;  RoPE, adjacent
      pairs (2i, 2i+1) turning by frequency i (`rope_interleave`), on
      q_rope of each head and on the ONE k_r every head shares;
      k = [k_nope | k_r];  causal softmax of q k' / sqrt(nope + rope);
      o = P v;  concat heads;  Wo.
    Mixture: s = sigmoid(u Wg) over ALL experts;  the top k of s + b;
      weights s (without b) at the chosen / (their sum + 1e-20) x
      routed_scale;  F(u) = sum_i w_i E_i(u) + Shared(u).
    b (`noaux_tc`): no gradient, no weight decay, no AdamW moments; after
      each step b_e += speed * sign(mean_e'(n_e') - n_e), n the rows each
      expert was sent by this batch's tokens in that layer.

Everything is `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, no sort, no grouped
matmul, nothing of `ray_tpu`.  Attention is a masked softmax over the
scores of a block of queries against every key; the experts are a loop
over those HELD (the share of an expert-parallel layer this reference is
given: stacks of `count` experts, the first of them expert `held_first` of
the router's columns), each applied to every token with the token's
weight, zero where it did not choose the expert.  What the absent experts
would add is left out, as in the system.

Departures, summation order only: queries are taken `query_block` at a
time; the held experts are `lax.scan`ned, each one's body
`jax.checkpoint`ed; each layer is `jax.checkpoint`ed, and the routed
layers, which are alike, are one body `lax.scan`ned over their stacked
parameters (a fifth of the code to compile); the trunk and the head run
one sequence at a time (`lax.map`), so that one sequence's activations
are alive beside the float32 state.  No statistic crosses sequences but
the bias rule's counts, which are summed over them.

Parameters: {"embed" (V, E), "head" (E, V), "norm_f" (E,), "dense": a list
of the leading dense layers, "routed": the routed layers' leaves stacked
(routed layers, ...)}.  A layer: {"norm1", "norm2" (E,), "wq" (E, H (nope
+ rope)), "wkv_a" (E, R + rope), "kv_norm" (R,), "wkv_b" (R, H (nope + v)),
"wo" (H v, E)} and, dense, "gate", "up" (E, F), "down" (F, E); routed,
"router" (E, N), "e_gate", "e_up" (count, E, W), "e_down" (count, W, E),
"s_gate", "s_up" (E, Ws), "s_down" (Ws, E).  The routing biases are no
parameters: (routed layers, N), beside them.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax


class Sizes(NamedTuple):
    n_head: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    top_k: int
    routed_scale: float
    held_first: int = 0
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    bias_update_speed: float = 0.001
    query_block: int = 512


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def rope_pairs(x, theta):
    """x (seq, ..., d): position m turns the adjacent pair (x_2i, x_2i+1)
    by the angle m * theta^(-2i/d), in place."""
    s, d = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]
    angle = angle.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                      even * jnp.sin(angle) + odd * jnp.cos(angle)],
                     axis=-1).reshape(x.shape)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def attention(x, p, sizes: Sizes):
    """x (seq, E), one sequence."""
    s = x.shape[0]
    h, nope, rope = sizes.n_head, sizes.qk_nope_dim, sizes.qk_rope_dim
    q = (x @ p["wq"]).reshape(s, h, nope + rope)
    latent = x @ p["wkv_a"]
    c = rms_norm(latent[:, :sizes.kv_lora_rank], p["kv_norm"], sizes.rms_eps)
    k_r = rope_pairs(latent[:, sizes.kv_lora_rank:], sizes.rope_theta)
    kv = (c @ p["wkv_b"]).reshape(s, h, nope + sizes.v_head_dim)
    q = jnp.concatenate(
        [q[..., :nope], rope_pairs(q[..., nope:], sizes.rope_theta)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, None], (s, h, rope))], -1)
    v = kv[..., nope:]
    q, k, v = (t.transpose(1, 0, 2) for t in (q, k, v))    # (h, s, .)
    block = min(sizes.query_block, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = qb @ k.transpose(0, 2, 1) / jnp.sqrt(
            jnp.float32(nope + rope))
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(s)[None]
        scores = jnp.where(seen, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v          # (h, block, v)

    out = jax.lax.map(rows, jnp.arange(0, s, block))        # (blocks, h, ., v)
    out = out.transpose(0, 2, 1, 3).reshape(s, h * sizes.v_head_dim)
    return out @ p["wo"]


def route(x, p, bias, sizes: Sizes):
    """x (tokens, E) -> (tokens, N): each token's weight for every expert,
    zero where the expert is not among its top k of s + bias."""
    s = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(s + bias, sizes.top_k)
    chosen = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1]), axis=1)
    picked = s * chosen
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return weights * sizes.routed_scale, chosen


def moe(x, p, bias, sizes: Sizes):
    """x (tokens, E) -> (y, rows sent to each of all the experts)."""
    weights, chosen = route(x, p, bias, sizes)
    count = p["e_gate"].shape[0]
    held = jax.lax.dynamic_slice_in_dim(
        weights, sizes.held_first, count, axis=1)           # (tokens, count)

    @jax.checkpoint
    def expert(x, gate, up, down, w):
        return swiglu(x, gate, up, down) * w[:, None]

    def add(total, e):
        return total + expert(x, *e), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(x),
                        (p["e_gate"], p["e_up"], p["e_down"], held.T))
    y = y + swiglu(x, p["s_gate"], p["s_up"], p["s_down"])
    return y, jnp.sum(chosen, axis=0)


def layer(x, p, bias, sizes: Sizes):
    """One layer on one sequence -> (y, rows sent to each expert; None
    from a dense layer)."""
    h = x + attention(rms_norm(x, p["norm1"], sizes.rms_eps), p, sizes)
    u = rms_norm(h, p["norm2"], sizes.rms_eps)
    if "router" not in p:
        return h + swiglu(u, p["gate"], p["up"], p["down"]), None
    y, rows = moe(u, p, bias, sizes)
    return h + y, rows


def trunk(params, biases, inputs, sizes: Sizes):
    """inputs (seq,), one sequence -> (x after the final norm, the rows
    sent to every expert in every routed layer (routed layers, N))."""
    x = params["embed"][inputs]
    one = jax.checkpoint(lambda x, p, bias: layer(x, p, bias, sizes))
    for p in params["dense"]:
        x, _ = one(x, p, None)
    # the routed layers are alike: one body, walked over their stack
    x, rows = jax.lax.scan(lambda x, pb: one(x, *pb), x,
                           (params["routed"], biases))
    return rms_norm(x, params["norm_f"], sizes.rms_eps), rows


def logits(params, biases, inputs, sizes: Sizes):
    """inputs (batch, seq) -> (batch, seq, V)."""
    return jax.lax.map(
        lambda row: trunk(params, biases, row, sizes)[0] @ params["head"],
        inputs)


def losses(params, biases, tokens, sizes: Sizes):
    """tokens (batch, seq + 1) -> (mean cross-entropy, rows sent to every
    expert by the whole batch (routed layers, N)); a sequence at a
    time."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    @jax.checkpoint
    def summed_xent(x, t):
        logp = jax.nn.log_softmax(x @ params["head"], axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, t[:, None], axis=-1))

    def sequence(xt):
        x, rows = trunk(params, biases, xt[0], sizes)
        return summed_xent(x, xt[1]), rows

    xent, rows = jax.lax.map(sequence, (inputs, targets))
    return jnp.sum(xent) / targets.size, jnp.sum(rows, axis=0)


def update_biases(biases, rows, sizes: Sizes):
    n = rows.astype(jnp.float32)
    return biases + sizes.bias_update_speed * jnp.sign(
        jnp.mean(n, axis=-1, keepdims=True) - n)


def adamw(settings):
    """The configuration's optimizer settings, as `optax.adamw` takes
    them."""
    return optax.adamw(settings["learning_rate"], b1=settings["b1"],
                       b2=settings["b2"], eps=settings["eps"],
                       weight_decay=settings["weight_decay"])


def make_train_step(sizes: Sizes, optimizer):
    """step(params, biases, opt_state, tokens) -> (params, biases,
    opt_state, loss, rows): one AdamW step on the cross-entropy's
    gradient, then the bias rule; loss and rows as before the step."""

    def step(params, biases, opt_state, tokens):
        (loss, rows), grads = jax.value_and_grad(losses, has_aux=True)(
            params, jax.lax.stop_gradient(biases), tokens, sizes)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates),
                update_biases(biases, rows, sizes), opt_state, loss, rows)

    return step


def first_losses(params, biases, batches, sizes: Sizes, optimizer_settings):
    """The cross-entropies of the first len(batches) steps from `params`
    and `biases`, one call of the jitted step a batch: the state is
    donated from call to call, so one copy of it lives (a program of all
    the steps unrolled is three times the code, 220 MB compiled, and is
    compiled anew at every run).  `batches` is (steps, batch, seq + 1)."""
    optimizer = adamw(optimizer_settings)
    step = jax.jit(make_train_step(sizes, optimizer),
                   donate_argnums=(0, 1, 2))
    opt_state = jax.jit(optimizer.init)(params)
    out = []
    for tokens in batches:
        params, biases, opt_state, loss, _ = step(
            params, biases, opt_state, tokens)
        out.append(float(loss))
    # freed now, not when the collector gets to it: the system's state is
    # born next and the chip does not hold both
    for leaf in jax.tree.leaves((params, biases, opt_state)):
        leaf.delete()
    return out

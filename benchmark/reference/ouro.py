"""The plain reference for Ouro, a looped language model: what `correct` is
judged against.

The published model (Zhu et al., "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741; the layer equations of
`ByteDance/Ouro-2.6B`'s public `modeling_ouro.py`), as ISSUE 50 wrote them
down (no network here: what the catalog's `config.json` does not carry is
listed under `assumed` in the configuration's file):

    a layer, a norm before and one AFTER each operator (four gains):
      h = x + N2(Attn(N1(x)));  y = h + N4(FFN(N3(h)))
      Attn: q, k, v = xWq, xWk, xWv (no bias, no norm on q or k); split
        into heads; rotate-half RoPE on every dimension of a head; causal
        softmax at head_dim^-1/2; Wo.
      FFN(u) = Wdown(silu(Wgate u) * Wup u).
    the loop: x_0 = the embedding; for t = 1..T:
      x_t = N_f(layers(x_{t-1})): the same layers and the same final norm
      every time; the normed x_t goes on to walk t + 1 and to head and gate.
    the gate: lambda_t = sigmoid(w_g . x_t + b_g), a token;
      p_1 = lambda_1, p_t = lambda_t prod_{j<t} (1 - lambda_j) for t < T,
      p_T = prod_{j<T} (1 - lambda_j).
    the loss, a token: sum_t p_t CE_t - beta H(p), CE_t the next-token
      cross-entropy of Whead x_t, H(p) = -sum_t p_t log p_t; the mean over
      the tokens.

Everything is `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, nothing of `ray_tpu`.
Attention is a masked softmax over the scores of a block of queries against
every key, one sequence at a time; the walks are a Python loop, each one a
loop over the layers; the four cross-entropies and the gate are written out.

Departures, of summation order and of what is held only: queries are taken
`query_block` at a time, each layer's call is `jax.checkpoint`ed, each
walk's cross-entropy is taken `query_block` rows at a time with its logits
made again by the backward pass, and the sequences go through one after
another (`lax.map`), each one's whole pass `jax.checkpoint`ed so that one
sequence's states are held at a time.  The loop over a walk's layers is a
`lax.scan` over their stacked parameters and not a Python loop: unrolled,
the chip's compiler starts many layers' recomputation at once and the
float32 step at the published widths does not fit one v5e (compiled for it
without the chip, six layers: 20.85 G of 15.75 G; scanned, it fits).

Parameters, a flat dict: "embed" (V, E), "head" (E, V), "norm_f" (E,),
"gate_w" (E,), "gate_b" (), "layers": {"norm1", "norm2", "norm3", "norm4"
(n, E); "wq", "wk", "wv" (n, E, heads x D), "wo" (n, heads x D, E); "gate",
"up" (n, E, W); "down" (n, W, E)}, every layer's stacked on a leading axis.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax


class Sizes(NamedTuple):
    n_head: int
    n_kv_head: int
    n_walk: int
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    entropy_weight: float = 0.05
    query_block: int = 512


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def rope(x, theta):
    """x (seq, heads, d): position m rotates the pair (x_i, x_{i + d/2}) by
    the angle m * theta^(-2i/d) (the rotate-half pairing)."""
    s, d = x.shape[0], x.shape[2]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rotated * sin


def attention(x, p, sizes: Sizes):
    """x (seq, E), one sequence -> (seq, E)."""
    s = x.shape[0]
    h, h_kv = sizes.n_head, sizes.n_kv_head
    d = p["wq"].shape[1] // h
    q = rope((x @ p["wq"]).reshape(s, h, d), sizes.rope_theta)
    k = rope((x @ p["wk"]).reshape(s, h_kv, d), sizes.rope_theta)
    v = (x @ p["wv"]).reshape(s, h_kv, d)
    # query head i reads key/value head i // (h / h_kv)
    k = jnp.repeat(k, h // h_kv, axis=1).transpose(1, 0, 2)   # (h, s, d)
    v = jnp.repeat(v, h // h_kv, axis=1).transpose(1, 0, 2)
    q = q.transpose(1, 0, 2)
    block = min(sizes.query_block, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = qb @ k.transpose(0, 2, 1) / jnp.sqrt(jnp.float32(d))
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(s)[None]
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1) @ v

    out = jax.lax.map(rows, jnp.arange(0, s, block))    # (blocks, h, ., d)
    return out.transpose(0, 2, 1, 3).reshape(s, h * d) @ p["wo"]


def layer(x, p, sizes: Sizes):
    eps = sizes.rms_eps
    a = attention(rms_norm(x, p["norm1"], eps), p, sizes)
    h = x + rms_norm(a, p["norm2"], eps)
    u = rms_norm(h, p["norm3"], eps)
    f = (jax.nn.silu(u @ p["gate"]) * (u @ p["up"])) @ p["down"]
    return h + rms_norm(f, p["norm4"], eps)


def walks(params, inputs, sizes: Sizes):
    """inputs (seq,), one sequence -> [x_1 .. x_T], every walk's normed
    state (seq, E)."""
    one = jax.checkpoint(lambda x, p: (layer(x, p, sizes), None))
    x = params["embed"][inputs]
    states = []
    for _ in range(sizes.n_walk):
        x, _ = jax.lax.scan(one, x, params["layers"])
        x = rms_norm(x, params["norm_f"], sizes.rms_eps)
        states.append(x)
    return states


def exit_distribution(params, states):
    """[x_1 .. x_T] -> [p_1 .. p_T], each (seq,)."""
    stayed = 1.0                    # prod_{j<t} (1 - lambda_j)
    p = []
    for x in states[:-1]:
        gate = jax.nn.sigmoid(x @ params["gate_w"] + params["gate_b"])
        p.append(gate * stayed)
        stayed = stayed * (1.0 - gate)
    return p + [stayed]


def cross_entropy(params, x, targets, sizes: Sizes):
    """x (seq, E), targets (seq,) -> each position's next-token
    cross-entropy (seq,)."""
    s = x.shape[0]
    block = min(sizes.query_block, s)

    @jax.checkpoint
    def rows(xt):
        x, t = xt
        logp = jax.nn.log_softmax(x @ params["head"], axis=-1)
        return -jnp.take_along_axis(logp, t[:, None], axis=-1)[:, 0]

    return jax.lax.map(rows, (x.reshape(s // block, block, -1),
                              targets.reshape(s // block, block))).reshape(s)


def sequence_losses(params, tokens, sizes: Sizes):
    """tokens (seq + 1,) -> the sequence's sums over its positions of (the
    loss, each walk's cross-entropy (T,), each p_t (T,), H(p))."""
    states = walks(params, tokens[:-1], sizes)
    p = exit_distribution(params, states)
    xent = [cross_entropy(params, x, tokens[1:], sizes) for x in states]
    entropy = -sum(jax.scipy.special.xlogy(p_t, p_t) for p_t in p)
    loss = sum(p_t * ce_t for p_t, ce_t in zip(p, xent)) \
        - sizes.entropy_weight * entropy
    return (jnp.sum(loss), jnp.stack([jnp.sum(c) for c in xent]),
            jnp.stack([jnp.sum(p_t) for p_t in p]), jnp.sum(entropy))


def losses(params, tokens, sizes: Sizes):
    """tokens (batch, seq + 1) -> (the objective, {"loss": the objective,
    "xent" (T,), "exit" (T,), "entropy"}), means over the batch's
    positions."""
    sums = jax.lax.map(
        jax.checkpoint(lambda row: sequence_losses(params, row, sizes)),
        tokens)
    n = tokens.shape[0] * (tokens.shape[1] - 1)
    loss, xent, p, entropy = (jnp.sum(part, axis=0) / n for part in sums)
    return loss, {"loss": loss, "xent": xent, "exit": p, "entropy": entropy}


def logits(params, inputs, sizes: Sizes):
    """inputs (batch, seq) -> (T, batch, seq, V): every walk's logits."""
    return jnp.stack([
        jnp.stack([x @ params["head"] for x in walks(params, row, sizes)])
        for row in inputs], axis=1)


def adamw(settings):
    """The configuration's optimizer settings, as `optax.adamw` takes
    them."""
    return optax.adamw(settings["learning_rate"], b1=settings["b1"],
                       b2=settings["b2"], eps=settings["eps"],
                       weight_decay=settings["weight_decay"])


def make_train_step(sizes: Sizes, optimizer):
    """step(params, opt_state, tokens) -> (params, opt_state, parts): one
    AdamW step on the objective's gradient; `parts` as `losses` gives them,
    before the step."""

    def step(params, opt_state, tokens):
        (_, parts), grads = jax.value_and_grad(losses, has_aux=True)(
            params, tokens, sizes)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, parts

    return step


def first_losses(params, batches, sizes: Sizes, optimizer_settings):
    """[the objective] of the first len(batches) steps from `params`, one
    call of the jitted step a batch: the state is donated from call to
    call, so one copy of it lives.  `batches` is (steps, batch, seq + 1)."""
    optimizer = adamw(optimizer_settings)
    step = jax.jit(make_train_step(sizes, optimizer), donate_argnums=(0, 1))
    opt_state = jax.jit(optimizer.init)(params)
    out = []
    for tokens in batches:
        params, opt_state, parts = step(params, opt_state, tokens)
        out.append(float(parts["loss"]))
    # freed now, not when the collector gets to it: the system's state is
    # born next and the chip does not hold both
    for leaf in jax.tree.leaves((params, opt_state)):
        leaf.delete()
    return out

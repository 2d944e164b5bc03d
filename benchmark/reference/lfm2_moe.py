"""The plain reference for the `lfm2_moe` family: what `correct` is judged
against.

LiquidAI's LFM2-MoE (`model_type` `lfm2_moe`), from its published
`config.json` and the public `modeling_lfm2_moe.py`.  x is (tokens, E); no
projection has a bias; RMSNorm has a learned gain.

    h = x + Op(RMSNorm(x));  y = h + F(RMSNorm(h));  final RMSNorm; the
      head is the embedding, transposed; mean next-token cross-entropy,
      and nothing beside it.
    Op, a `conv` layer: [b | c | z] = u W_in (thirds in that order);
      g = b * z;  v_t = sum_{j=0..L-1} w_j * g_{t-(L-1)+j} (w (E, L), one
      filter a channel, g zero before the sequence starts: position t sees
      t-L+1 .. t);  (c * v) W_out.  No activation function.
    Op, a `full_attention` layer: q = u W_q as H heads, k = u W_k and
      v = u W_v as H_kv heads;  RMSNorm over each q head and each k head
      (one gain vector each);  RoPE on the whole head, rotate-half: dim i
      turns with dim i + D/2 by the angle m * theta^(-2i/D);  causal
      softmax of q k' / sqrt(D), query head h against key/value head
      h // (H / H_kv);  concat heads;  W_o.
    F: a SwiGLU `down(silu(gate(u)) * up(u))` in the leading dense layers;
      after them s = sigmoid(u W_g) over ALL experts;  the top k of s + b;
      weights s (without b) at the chosen / (their sum + `renorm_eps`) x
      `routed_scale`;  F(u) = sum_i w_i E_i(u), each E_i a SwiGLU.
    b: no gradient, no weight decay, no AdamW moments; after each step
      b_e += speed * sign(mean_e'(n_e') - n_e), n the rows each expert was
      sent by this batch's tokens in that layer.

Everything is `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, no sort, no grouped
matmul, no convolution primitive, nothing of `ray_tpu`.  The convolution is
an explicit sum over taps of shifted copies; attention is a masked softmax
over the scores of a block of queries against every key, the key/value
heads repeated by `jnp.repeat`; the experts are a loop over those HELD (the
share of an expert-parallel layer this reference is given: stacks of
`count` experts, the first of them expert `held_first` of the router's
columns), each applied to every token with the token's weight, zero where
it did not choose the expert.  What the absent experts would add is left
out, as in the system.

Departures, summation order only: queries are taken `query_block` at a
time; the held experts are `lax.scan`ned, each one's body
`jax.checkpoint`ed; each layer is `jax.checkpoint`ed, and layers that
follow one another and are alike are one body `lax.scan`ned over their
stacked parameters (less code to compile); the trunk and the head run one
sequence at a time (`lax.map`).  No statistic crosses sequences but the
bias rule's counts, which are summed over them.

Parameters: {"embed" (V, E), "norm_f" (E,), "groups": a list of runs of
alike layers, each a layer's leaves stacked (layers in the run, ...)}.  A
layer: {"norm1", "norm2" (E,)}; a conv operator "w_in" (E, 3E), "taps"
(E, L), "w_out" (E, E); an attention operator "wq" (E, H D), "wk", "wv"
(E, H_kv D), "wo" (H D, E), "q_norm", "k_norm" (D,); dense, "gate", "up"
(E, F), "down" (F, E); routed, "router" (E, N), "e_gate", "e_up" (count,
E, W), "e_down" (count, W, E).  The routing biases are no parameters: a
list beside the groups, (layers in the run, N) for a routed run and None
for a dense one.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax


class Sizes(NamedTuple):
    n_head: int
    n_kv_head: int
    top_k: int
    routed_scale: float = 1.0
    renorm_eps: float = 1e-6
    held_first: int = 0
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    bias_update_speed: float = 0.001
    query_block: int = 512


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def rope_halves(x, theta):
    """x (seq, heads, d): position m turns the pair (x_i, x_{i+d/2}) by the
    angle m * theta^(-2i/d)."""
    s, d = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = (jnp.arange(s, dtype=jnp.float32)[:, None]
             * inv_freq[None])[:, None, :]                  # (s, 1, d/2)
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [first * jnp.cos(angle) - second * jnp.sin(angle),
         first * jnp.sin(angle) + second * jnp.cos(angle)], axis=-1)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def short_conv(x, p):
    """x (seq, E), one sequence."""
    s, e = x.shape
    bcz = x @ p["w_in"]
    b, c, z = bcz[:, :e], bcz[:, e:2 * e], bcz[:, 2 * e:]
    g = b * z
    taps = p["taps"].shape[1]
    v = jnp.zeros_like(g)
    for j in range(taps):
        back = taps - 1 - j                 # tap j reads position t - back
        shifted = jnp.concatenate(
            [jnp.zeros((back, e), g.dtype), g[:s - back]], axis=0)
        v = v + p["taps"][:, j] * shifted
    return (c * v) @ p["w_out"]


def attention(x, p, sizes: Sizes):
    """x (seq, E), one sequence."""
    s = x.shape[0]
    h, h_kv = sizes.n_head, sizes.n_kv_head
    d = p["wq"].shape[1] // h
    q = rms_norm((x @ p["wq"]).reshape(s, h, d), p["q_norm"], sizes.rms_eps)
    k = rms_norm((x @ p["wk"]).reshape(s, h_kv, d), p["k_norm"],
                 sizes.rms_eps)
    v = (x @ p["wv"]).reshape(s, h_kv, d)
    q, k = rope_halves(q, sizes.rope_theta), rope_halves(k, sizes.rope_theta)
    # query head i reads key/value head i // (h / h_kv)
    k, v = (jnp.repeat(t, h // h_kv, axis=1) for t in (k, v))
    q, k, v = (t.transpose(1, 0, 2) for t in (q, k, v))    # (h, s, d)
    block = min(sizes.query_block, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = qb @ k.transpose(0, 2, 1) / jnp.sqrt(jnp.float32(d))
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(s)[None]
        scores = jnp.where(seen, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v          # (h, block, d)

    out = jax.lax.map(rows, jnp.arange(0, s, block))        # (blocks, h, ., d)
    out = out.transpose(0, 2, 1, 3).reshape(s, h * d)
    return out @ p["wo"]


def route(x, p, bias, sizes: Sizes):
    """x (tokens, E) -> (tokens, N): each token's weight for every expert,
    zero where the expert is not among its top k of s + bias."""
    s = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(s + bias, sizes.top_k)
    chosen = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1]), axis=1)
    picked = s * chosen
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                        + sizes.renorm_eps)
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
    return y, jnp.sum(chosen, axis=0)


def layer(x, p, bias, sizes: Sizes):
    """One layer on one sequence -> (y, rows sent to each expert; None
    from a dense layer)."""
    u = rms_norm(x, p["norm1"], sizes.rms_eps)
    h = x + (short_conv(u, p) if "w_in" in p else attention(u, p, sizes))
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
    rows = []
    for group, bias in zip(params["groups"], biases):
        # the layers of a run are alike: one body, walked over their stack
        if bias is None:
            x, _ = jax.lax.scan(lambda x, p: (one(x, p, None)[0], None), x,
                                group)
        else:
            x, sent = jax.lax.scan(lambda x, pb: one(x, *pb), x,
                                   (group, bias))
            rows.append(sent)
    return rms_norm(x, params["norm_f"], sizes.rms_eps), \
        jnp.concatenate(rows)


def logits(params, biases, inputs, sizes: Sizes):
    """inputs (batch, seq) -> (batch, seq, V)."""
    return jax.lax.map(
        lambda row: trunk(params, biases, row, sizes)[0]
        @ params["embed"].T, inputs)


def losses(params, biases, tokens, sizes: Sizes):
    """tokens (batch, seq + 1) -> (mean cross-entropy, rows sent to every
    expert by the whole batch (routed layers, N)); a sequence at a
    time."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    @jax.checkpoint
    def summed_xent(x, t):
        logp = jax.nn.log_softmax(x @ params["embed"].T, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, t[:, None], axis=-1))

    def sequence(xt):
        x, rows = trunk(params, biases, xt[0], sizes)
        return summed_xent(x, xt[1]), rows

    xent, rows = jax.lax.map(sequence, (inputs, targets))
    return jnp.sum(xent) / targets.size, jnp.sum(rows, axis=0)


def update_biases(biases, rows, sizes: Sizes):
    """The rule, on the list of the runs' biases; `rows` is (routed
    layers, N) in the layers' order."""
    out, at = [], 0
    for bias in biases:
        if bias is None:
            out.append(None)
            continue
        n = rows[at:at + bias.shape[0]].astype(jnp.float32)
        at += bias.shape[0]
        out.append(bias + sizes.bias_update_speed * jnp.sign(
            jnp.mean(n, axis=-1, keepdims=True) - n))
    return out


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
    donated from call to call, so one copy of it lives.  `batches` is
    (steps, batch, seq + 1)."""
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

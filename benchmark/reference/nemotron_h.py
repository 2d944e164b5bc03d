"""The plain reference for the `nemotron_h` family: what `correct` is
judged against.

NVIDIA's Nemotron-H (`model_type` `nemotron_h`), from its published
`config.json` and the public `modeling_nemotron_h.py`.  x is (tokens, E); no
projection has a bias; RMSNorm has a learned gain.

    x <- x + Mixer(RMSNorm(x)), one mixer a layer; a final RMSNorm; a head
      of its own; mean next-token cross-entropy, and nothing beside it.
    Mamba-2 mixer, H heads of P, G groups, state N, K taps:
      [z | xBC | dt] = u W_in (widths HP | HP + 2GN | H);
      xBC <- silu(conv(xBC) + b), conv(v)_t = sum_j w_j * v_{t-(K-1)+j}, one
      filter a channel, zeros before the sequence starts;
      [x | B | C] = xBC (HP | GN | GN);
      D_t = softplus(dt_t + dt_bias), A = -exp(A_log);
      for head h with group g = h // (H / G), h_{-1} = 0:
        h_t = exp(D_t A) h_{t-1} + D_t x_t (x) B_t;  y_t = h_t C_t + D_h x_t;
      y <- y * silu(z), then RMSNorm over each group's HP / G channels,
      times a gain;  y W_out.
    Attention mixer: q = u W_q as H_a heads, k = u W_k and v = u W_v as
      H_kv heads; no rotary embedding, no norm over a head; causal softmax
      of q k' / sqrt(D), query head h against key/value head
      h // (H_a / H_kv); concat heads; W_o.
    Mixture: s = sigmoid(u W_g) over ALL experts; the top k of s + b;
      weights s (without b) at the chosen / (their sum + `renorm_eps`) x
      `routed_scale`; sum_i w_i E_i(u) + Shared(u), every expert
      W_down relu(W_up u)^2 (two matrices, not gated).
    b: no gradient, no weight decay, no AdamW moments; after each step
      b_e += speed * sign(mean_e'(n_e') - n_e), n the rows each expert was
      sent by this batch's tokens in that layer.

Everything is `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, no sort, no grouped
matmul, no convolution primitive, no chunked algebra, nothing of
`ray_tpu`.  The state-space recurrence is run POSITION BY POSITION (a
`lax.scan` over time that carries the (H, P, N) state); the convolution is
an explicit sum over taps of shifted copies; attention is a masked softmax
over the scores of a block of queries against every key, the key/value
heads repeated by `jnp.repeat`; the experts are a loop over those HELD (the
share of an expert-parallel layer this reference is given: stacks of
`count` experts, the first of them expert `held_first` of the router's
columns), each applied to every token with the token's weight, zero where
it did not choose the expert.  What the absent experts would add is left
out, as in the system.

Departures, summation order only: the recurrence walks `scan_block`
positions inside a `jax.checkpoint`, block after block, so that its
backward holds the states of one block and of the blocks' edges and not all
S of them; queries are taken `query_block` at a time; each held expert's body is
`jax.checkpoint`ed, and inside a Mamba-2 mixer the convolution with its SiLU
and the gated norm; each layer is `jax.checkpoint`ed; the trunk and the head run one sequence at a time
(`lax.map`).  No statistic crosses sequences but the bias rule's counts,
which are summed over them.

Parameters: {"embed" (V, E), "head" (E, V), "norm_f" (E,), "layers": a
list, one dict a layer}.  Every layer has "norm" (E,); a Mamba-2 mixer
"w_in" (E, 2HP + 2GN + H), "taps" (HP + 2GN, K), "conv_bias", "a_log",
"d", "dt_bias" (H,), "gate_norm" (HP,), "w_out" (HP, E); an attention mixer
"wq" (E, H_a D), "wk", "wv" (E, H_kv D), "wo" (H_a D, E); a mixture
"router" (E, N), "e_up" (count, E, W), "e_down" (count, W, E), "s_up"
(E, W_s), "s_down" (W_s, E).  The routing biases are no parameters: a list
beside the layers, (N,) for a mixture and None for the others.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax


class Sizes(NamedTuple):
    mamba_heads: int
    mamba_head_dim: int
    n_groups: int
    state_size: int
    n_head: int
    n_kv_head: int
    top_k: int
    routed_scale: float = 2.5
    renorm_eps: float = 1e-20
    held_first: int = 0
    rms_eps: float = 1e-5
    bias_update_speed: float = 0.001
    query_block: int = 512
    scan_block: int = 64


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def relu2(x, up, down):
    return jnp.square(jax.nn.relu(x @ up)) @ down


def conv(v, taps, bias):
    """v (seq, C): out_t = sum_j taps[:, j] * v_{t-(K-1)+j} + bias."""
    s, c = v.shape
    k = taps.shape[1]
    out = jnp.zeros_like(v) + bias
    for j in range(k):
        back = k - 1 - j                    # tap j reads position t - back
        shifted = jnp.concatenate(
            [jnp.zeros((back, c), v.dtype), v[:s - back]], axis=0)
        out = out + taps[:, j] * shifted
    return out


def heads_of_groups(v, heads):
    """v (..., G, N) -> (..., heads, N): head i reads group
    i // (heads / G)."""
    return jnp.repeat(v, heads // v.shape[-2], axis=-2)


def recurrence(x, dt, a, b, c, d, block):
    """x (seq, H, P); dt (seq, H); a (H,); b, c (seq, G, N), a group's heads
    share them; d (H,) -> y (seq, H, P), one position after another.  The
    walk takes x and gives y with a position's heads flat (H P lanes)."""
    s, h, p = x.shape
    block = min(block, s)
    assert s % block == 0, (s, block)

    def position(state, t):
        xt, dtt, bt, ct = t
        xt = xt.reshape(h, p)
        bt, ct = heads_of_groups(bt, h), heads_of_groups(ct, h)
        state = jnp.exp(dtt * a)[:, None, None] * state \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        y = jnp.sum(state * ct[:, None, :], axis=-1) + d[:, None] * xt
        return state, y.reshape(h * p)

    @jax.checkpoint
    def positions(state, ts):
        return jax.lax.scan(position, state, ts)

    state = jnp.zeros((h, p, b.shape[-1]), x.dtype)
    _, y = jax.lax.scan(positions, state, tuple(
        v.reshape(s // block, block, *v.shape[1:])
        for v in (x.reshape(s, h * p), dt, b, c)))
    return y.reshape(x.shape)


def gated_norm(y, z, gain, groups, eps):
    """y, z (seq, HP): the gate first, then an RMSNorm over each of the
    `groups` runs of HP / groups channels, then the gain."""
    s, hp = y.shape
    y = y * jax.nn.silu(z)
    y = rms_norm(y.reshape(s, groups, hp // groups), 1.0, eps)
    return y.reshape(s, hp) * gain


def mamba(u, p, sizes: Sizes):
    """u (seq, E), one sequence."""
    s = u.shape[0]
    h, hd, g, n = (sizes.mamba_heads, sizes.mamba_head_dim, sizes.n_groups,
                   sizes.state_size)
    hp, gn = h * hd, g * n
    zxbcdt = u @ p["w_in"]
    z, xbc, dt = (zxbcdt[:, :hp], zxbcdt[:, hp:2 * hp + 2 * gn],
                  zxbcdt[:, 2 * hp + 2 * gn:])
    xbc = jax.checkpoint(lambda v, taps, bias: jax.nn.silu(
        conv(v, taps, bias)))(xbc, p["taps"], p["conv_bias"])
    x = xbc[:, :hp].reshape(s, h, hd)
    b, c = (xbc[:, lo:lo + gn].reshape(s, g, n) for lo in (hp, hp + gn))
    y = recurrence(x, jax.nn.softplus(dt + p["dt_bias"]),
                   -jnp.exp(p["a_log"]), b, c, p["d"], sizes.scan_block)
    y = jax.checkpoint(lambda y, z, gain: gated_norm(
        y, z, gain, g, sizes.rms_eps))(y.reshape(s, hp), z, p["gate_norm"])
    return y @ p["w_out"]


def heads(u, p, sizes: Sizes):
    """u (seq, E) -> q (seq, H_a, D), k and v (seq, H_kv, D): the
    projections as they are, no position turned into them."""
    s = u.shape[0]
    h, h_kv = sizes.n_head, sizes.n_kv_head
    d = p["wq"].shape[1] // h
    return ((u @ p["wq"]).reshape(s, h, d), (u @ p["wk"]).reshape(s, h_kv, d),
            (u @ p["wv"]).reshape(s, h_kv, d))


def attention(u, p, sizes: Sizes):
    """u (seq, E), one sequence."""
    q, k, v = heads(u, p, sizes)
    s, h, d = q.shape
    h_kv = k.shape[1]
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


def route(u, p, bias, sizes: Sizes):
    """u (tokens, E) -> (tokens, N): each token's weight for every expert,
    zero where the expert is not among its top k of s + bias."""
    s = jax.nn.sigmoid(u @ p["router"])
    _, chosen = jax.lax.top_k(s + bias, sizes.top_k)
    chosen = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1]), axis=1)
    picked = s * chosen
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                        + sizes.renorm_eps)
    return weights * sizes.routed_scale, chosen


def moe(u, p, bias, sizes: Sizes):
    """u (tokens, E) -> (y, rows sent to each of all the experts)."""
    weights, chosen = route(u, p, bias, sizes)
    count = p["e_up"].shape[0]
    held = jax.lax.dynamic_slice_in_dim(
        weights, sizes.held_first, count, axis=1)           # (tokens, count)

    @jax.checkpoint
    def expert(u, up, down, w):
        return relu2(u, up, down) * w[:, None]

    y = relu2(u, p["s_up"], p["s_down"])
    for i in range(count):
        y = y + expert(u, p["e_up"][i], p["e_down"][i], held[:, i])
    return y, jnp.sum(chosen, axis=0)


def layer(x, p, bias, sizes: Sizes):
    """One layer on one sequence -> (y, rows sent to each expert; None from
    a layer that is no mixture)."""
    u = rms_norm(x, p["norm"], sizes.rms_eps)
    if "w_in" in p:
        return x + mamba(u, p, sizes), None
    if "wq" in p:
        return x + attention(u, p, sizes), None
    y, rows = moe(u, p, bias, sizes)
    return x + y, rows


def trunk(params, biases, inputs, sizes: Sizes):
    """inputs (seq,), one sequence -> (x after the final norm, the rows
    sent to every expert in every mixture layer (mixture layers, N))."""
    x = params["embed"][inputs]
    one = jax.checkpoint(lambda x, p, bias: layer(x, p, bias, sizes))
    rows = []
    for p, bias in zip(params["layers"], biases):
        x, sent = one(x, p, bias)
        if sent is not None:
            rows.append(sent)
    return rms_norm(x, params["norm_f"], sizes.rms_eps), jnp.stack(rows)


def logits(params, biases, inputs, sizes: Sizes):
    """inputs (batch, seq) -> (batch, seq, V)."""
    return jax.lax.map(
        lambda row: trunk(params, biases, row, sizes)[0] @ params["head"],
        inputs)


def losses(params, biases, tokens, sizes: Sizes):
    """tokens (batch, seq + 1) -> (mean cross-entropy, rows sent to every
    expert by the whole batch (mixture layers, N)); a sequence at a
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
    """The rule, on the list of the layers' biases; `rows` is (mixture
    layers, N) in the layers' order."""
    out, at = [], 0
    for bias in biases:
        if bias is None:
            out.append(None)
            continue
        n = rows[at].astype(jnp.float32)
        at += 1
        out.append(bias + sizes.bias_update_speed * jnp.sign(
            jnp.mean(n) - n))
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

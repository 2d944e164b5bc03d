"""The plain reference for the `phi4flash` family: what `correct` is judged
against.

Microsoft's Phi-4-mini-flash-reasoning (`model_type` `phi4flash`; Ren et
al., arXiv:2507.06607: SambaY with differential attention), from its
published `config.json` and the equations ISSUE 63 wrote down.  x is
(tokens, E); LN is a LayerNorm with gain and bias; no position embedding.

    every layer: x <- x + mixer(LN1(x)); x <- x + W_down(up * silu(gate)),
      gate = LN2(x) W_gate, up = LN2(x) W_up, no bias.
    Mamba-1 mixer, C channels, state N, rank R, K taps:
      [u | z] = h W_in;  u <- silu(conv(u) + b), conv(v)_t = sum_j w_j *
      v_{t-(K-1)+j}, one filter a channel, zeros before the sequence;
      [r | B | C] = u W_x (R | N | N);  dt = softplus(r W_dt + b_dt);
      A = -exp(A_log) (C, N);  s_t = exp(dt_t (x) A) s_{t-1} + (dt_t u_t)
      (x) B_t, s_{-1} = 0;  y_t = s_t C_t + D u_t;  (y * silu(z)) W_out.
      The layer named by `Sizes.memory_from` hands on m = y.
    differential attention, H query heads and H_kv key/value heads of d:
      q = h W_q + b_q, k = h W_k + b_k, v = h W_v + b_v; q1, k1, v1 the even
      heads and q2, k2, v2 the odd; pair j of the H / 2 reads key pair
      j // (H / H_kv);  a_i = softmax(q_i k_i' / sqrt(d) + mask) [v1 | v2];
      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda0 (the layer's
      `Sizes.lambdas`);  o = RMSNorm(a1 - lambda a2) * gain * (1 - lambda0)
      over each pair's 2 d;  o W_o + b_o.  Mask: key j for query i iff
      j <= i, and under a window i - j < W.  The layer named by
      `Sizes.keys_from` hands on its k and v.
    cross layer: q = h W_q + b_q alone, the same attention without a
      window over the handed-on k and v.
    gated memory unit: (m * silu(h W_1)) W_2.
    the final LN; logits by the embedding's rows; mean next-token
      cross-entropy.

Everything is `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, no convolution primitive,
nothing of `ray_tpu`.  The recurrence is run POSITION BY POSITION (a
`lax.scan` over time that carries the (C, N) state); the convolution is an
explicit sum over taps of shifted copies; each softmax is a masked softmax
over the scores of a block of queries against every key, the key heads
repeated by `jnp.repeat`.

Departures, summation order only: the recurrence walks `scan_block`
positions inside a `jax.checkpoint`, block after block; queries are taken
`query_block` at a time; the feed-forward and the head take `row_block`
rows at a time; each such part and each layer is `jax.checkpoint`ed; trunk
and head run one sequence at a time (`lax.map`).  No statistic crosses
sequences.

Parameters: {"embed" (V, E), "norm_f": {"g", "b"}, "layers": a list, one
dict a layer}.  Every layer has "ln1", "ln2" ({"g", "b"}), "w_gate",
"w_up" (E, W), "w_down" (W, E); a Mamba-1 layer "w_in" (E, 2C), "taps"
(C, K), "conv_bias", "w_x" (C, R + 2N), "w_dt" (R, C), "dt_bias", "a_log"
(C, N), "d", "w_out" (C, E); an attention layer "wq", "bq", "wk", "bk",
"wv", "bv", "lq1", "lk1", "lq2", "lk2" (d,), "gain" (2d,), "wo", "bo"; a
cross layer those without k and v; a memory unit "w_1" (E, C), "w_2"
(C, E).  A layer's kind is `Sizes.kinds`'.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"


class Sizes(NamedTuple):
    kinds: Tuple[str, ...]        # a layer's kind, in order
    lambdas: Tuple[float, ...]    # a layer's lambda0 (unused by the others)
    n_head: int
    n_kv_head: int
    window: int
    d_state: int
    dt_rank: int
    norm_eps: float
    rms_eps: float
    query_block: int
    scan_block: int
    row_block: int

    @property
    def memory_from(self) -> Optional[int]:
        """The layer whose scan the memory units gate: the last Mamba-1
        layer before the first of them."""
        if GMU not in self.kinds:
            return None
        first = self.kinds.index(GMU)
        return max(i for i in range(first) if self.kinds[i] == MAMBA)

    @property
    def keys_from(self) -> Optional[int]:
        return self.kinds.index(FULL) if CROSS in self.kinds else None


def layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def by_rows(fn, x, block):
    """fn over ``block`` rows of x (rows, .) at a time, each recomputed."""
    rows = x.shape[0]
    block = min(block, rows)
    assert rows % block == 0, (rows, block)
    out = jax.lax.map(jax.checkpoint(fn),
                      x.reshape(rows // block, block, *x.shape[1:]))
    return out.reshape(rows, *out.shape[2:])


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


def recurrence(u, dt, a, b, c, d, block):
    """u, dt (seq, C); a (C, N); b, c (seq, N); d (C,) -> y (seq, C), one
    position after another."""
    s = u.shape[0]
    block = min(block, s)
    assert s % block == 0, (s, block)

    def position(state, t):
        ut, dtt, bt, ct = t
        state = jnp.exp(dtt[:, None] * a) * state \
            + (dtt * ut)[:, None] * bt[None, :]
        return state, jnp.sum(state * ct[None, :], axis=-1) + d * ut

    @jax.checkpoint
    def positions(state, ts):
        return jax.lax.scan(position, state, ts)

    _, y = jax.lax.scan(positions, jnp.zeros(a.shape, u.dtype), tuple(
        v.reshape(s // block, block, v.shape[1]) for v in (u, dt, b, c)))
    return y.reshape(u.shape)


def mamba(h, p, sizes: Sizes):
    """h (seq, E), one sequence -> (the mixer's result, the scan's y)."""
    n, r = sizes.d_state, sizes.dt_rank
    uz = h @ p["w_in"]
    c = uz.shape[1] // 2
    u = jax.checkpoint(lambda v, taps, bias: jax.nn.silu(
        conv(v, taps, bias)))(uz[:, :c], p["taps"], p["conv_bias"])
    rbc = u @ p["w_x"]
    dt = jax.nn.softplus(rbc[:, :r] @ p["w_dt"] + p["dt_bias"])
    y = recurrence(u, dt, -jnp.exp(p["a_log"]), rbc[:, r:r + n],
                   rbc[:, r + n:], p["d"], sizes.scan_block)
    return (y * jax.nn.silu(uz[:, c:])) @ p["w_out"], y


def keys_values(h, p, sizes: Sizes):
    """-> k, v (seq, H_kv, d)."""
    s = h.shape[0]
    return ((h @ p["wk"] + p["bk"]).reshape(s, sizes.n_kv_head, -1),
            (h @ p["wv"] + p["bv"]).reshape(s, sizes.n_kv_head, -1))


def softmaxes(q, k, v, window, block):
    """q, k (heads, seq, d), v (heads, seq, dv) -> (heads, seq, dv): causal,
    under a window where one is given, ``block`` queries at a time."""
    s, d = q.shape[1:]
    block = min(block, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = qb @ k.transpose(0, 2, 1) / jnp.sqrt(jnp.float32(d))
        i = (start + jnp.arange(block))[:, None]
        j = jnp.arange(s)[None]
        seen = j <= i
        if window is not None:
            seen = seen & (i - j < window)
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1) @ v

    out = jax.lax.map(rows, jnp.arange(0, s, block))    # (blocks, heads, ., dv)
    return out.transpose(1, 0, 2, 3).reshape(q.shape[0], s, -1)


def differential(h, k, v, p, sizes: Sizes, window, lambda0):
    """h (seq, E); k, v (seq, H_kv, d)."""
    s = h.shape[0]
    heads = sizes.n_head
    q = (h @ p["wq"] + p["bq"]).reshape(s, heads, -1)
    d = q.shape[-1]
    q1, q2 = q[:, 0::2], q[:, 1::2]
    k1, k2 = k[:, 0::2], k[:, 1::2]
    vv = jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1)
    # pair j of the queries reads pair j // (H / H_kv) of the keys
    group = heads // sizes.n_kv_head
    k1, k2, vv = (jnp.repeat(t, group, axis=1) for t in (k1, k2, vv))
    major = lambda t: t.transpose(1, 0, 2)
    a1 = softmaxes(major(q1), major(k1), major(vv), window,
                   sizes.query_block)
    a2 = softmaxes(major(q2), major(k2), major(vv), window,
                   sizes.query_block)
    lam = jnp.exp(jnp.sum(p["lq1"] * p["lk1"])) \
        - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + lambda0
    o = a1 - lam * a2                                   # (pairs, seq, 2d)
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                     + sizes.rms_eps)
    o = o * p["gain"] * (1.0 - lambda0)
    return o.transpose(1, 0, 2).reshape(s, heads * d) @ p["wo"] + p["bo"]


def feed_forward(h, p, sizes: Sizes):
    return by_rows(lambda r: (jax.nn.silu(r @ p["w_gate"]) * (r @ p["w_up"]))
                   @ p["w_down"], h, sizes.row_block)


def layer(x, p, shared, sizes: Sizes, i: int):
    """x (seq, E) -> (x, what is handed on: {"memory", "k", "v"})."""
    kind = sizes.kinds[i]
    shared = dict(shared)
    h = layer_norm(x, p["ln1"], sizes.norm_eps)
    if kind == MAMBA:
        y, memory = mamba(h, p, sizes)
        if i == sizes.memory_from:
            shared["memory"] = memory
    elif kind == GMU:
        y = (shared["memory"] * jax.nn.silu(h @ p["w_1"])) @ p["w_2"]
    elif kind == CROSS:
        y = differential(h, shared["k"], shared["v"], p, sizes, None,
                         sizes.lambdas[i])
    else:
        k, v = keys_values(h, p, sizes)
        if i == sizes.keys_from:
            shared.update(k=k, v=v)
        y = differential(h, k, v, p, sizes,
                         sizes.window if kind == WINDOW else None,
                         sizes.lambdas[i])
    x = x + y
    return x + feed_forward(layer_norm(x, p["ln2"], sizes.norm_eps), p,
                            sizes), shared


def streams(params, inputs, sizes: Sizes):
    """inputs (seq,), one sequence -> [the stream after each layer]."""
    x, shared, out = params["embed"][inputs], {}, []
    for i, p in enumerate(params["layers"]):
        x, shared = jax.checkpoint(layer, static_argnums=(3, 4))(
            x, p, shared, sizes, i)
        out.append(x)
    return out


def hidden(params, inputs, sizes: Sizes):
    """-> (seq, E) after the final norm."""
    return layer_norm(streams(params, inputs, sizes)[-1], params["norm_f"],
                      sizes.norm_eps)


def logits(params, inputs, sizes: Sizes):
    """inputs (batch, seq) -> (batch, seq, V)."""
    return jnp.stack([hidden(params, row, sizes) @ params["embed"].T
                      for row in inputs])


def sequence_loss(params, tokens, sizes: Sizes):
    """tokens (seq + 1,) -> the sum of the sequence's cross-entropies, the
    logits `row_block` rows at a time."""
    x = hidden(params, tokens[:-1], sizes)
    s = x.shape[0]
    block = min(sizes.row_block, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def rows(xt):
        x, t = xt
        logp = jax.nn.log_softmax(x @ params["embed"].T, axis=-1)
        return -jnp.take_along_axis(logp, t[:, None], axis=-1)[:, 0]

    return jnp.sum(jax.lax.map(rows, (
        x.reshape(s // block, block, -1),
        tokens[1:].reshape(s // block, block))))


def losses(params, tokens, sizes: Sizes):
    """tokens (batch, seq + 1) -> the mean next-token cross-entropy."""
    sums = jax.lax.map(
        jax.checkpoint(lambda row: sequence_loss(params, row, sizes)), tokens)
    return jnp.sum(sums) / (tokens.shape[0] * (tokens.shape[1] - 1))


def adamw(settings):
    """The configuration's optimizer settings, as `optax.adamw` takes
    them."""
    return optax.adamw(settings["learning_rate"], b1=settings["b1"],
                       b2=settings["b2"], eps=settings["eps"],
                       weight_decay=settings["weight_decay"])


def make_train_step(sizes: Sizes, optimizer):
    """step(params, opt_state, tokens) -> (params, opt_state, the loss
    before the step): one AdamW step on the cross-entropy's gradient."""

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(losses)(params, tokens, sizes)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return step


def first_losses(params, batches, sizes: Sizes, optimizer_settings):
    """The cross-entropies of the first len(batches) steps from `params`,
    one call of the jitted step a batch: the state is donated from call to
    call, so one copy of it lives.  `batches` is (steps, batch, seq + 1)."""
    optimizer = adamw(optimizer_settings)
    step = jax.jit(make_train_step(sizes, optimizer), donate_argnums=(0, 1))
    opt_state = jax.jit(optimizer.init)(params)
    out = []
    for tokens in batches:
        params, opt_state, loss = step(params, opt_state, tokens)
        out.append(float(loss))
    # freed now, not when the collector gets to it: the system's state is
    # born next and the chip does not hold both
    for leaf in jax.tree.leaves((params, opt_state)):
        leaf.delete()
    return out

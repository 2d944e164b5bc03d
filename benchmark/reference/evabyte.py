"""The plain reference for the `evabyte` family: what `correct` is judged
against.

EvaByte (`model_type` `evabyte`, `attention_class` `eva`), from its
published `config.json`; the mixer is EVA, "Efficient Attention via Control
Variates" (Zheng, Yuan, Wang, Kong; ICLR 2023, arXiv:2302.04542), with one
learned vector phi and one learned vector mu a head.  x is (rows, E); no
projection has a bias; an RMSNorm's gain is 1 + w.  S positions a sequence,
in chunks of c and aligned windows of w.

    h = x + EVA(RMSNorm(x)) W_o;  y = h + SwiGLU(RMSNorm(h));  final
      RMSNorm; P heads as one matrix (E, P V).
    RMSNorm(x) = x rsqrt(mean x^2 + eps) (1 + w).
    q = RoPE(u W_q), k = RoPE(u W_k), v = u W_v as H heads of D;
      rotate-half RoPE over the whole head: dim i turns with dim i + D/2 by
      the angle m theta^(-2i/D) at position m.
    summaries, chunk n (positions c n .. c n + c - 1), head h:
      a_t = softmax over the chunk's positions of (k_t . phi_h), the ROTATED
      keys, no further scale;  ks_n = sum_t a_t k_t + mu_h;
      vs_n = sum_t a_t v_t.
    query i attends the keys A_i = {j : j // w = i // w and j <= i} and the
      summaries B_i = {n : (c n) // w < i // w} under ONE softmax:
      o_i = [sum_A e^(q_i.k_j / sqrt D) v_j + sum_B e^(q_i.ks_n / sqrt D) vs_n]
            / [sum_A e^(q_i.k_j / sqrt D) + sum_B e^(q_i.ks_n / sqrt D)].
    head p at position i predicts the byte at i + 1 + p.  L = the mean over
      the P heads of head p's mean over the sequences and the S - p
      positions that have a target of CE(head p's row i, x_{i+1+p}).

Everything is `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, no tile, nothing of
`ray_tpu`.  Attention is one masked softmax over the scores of a block of
query rows against ALL S token keys and ALL S / c summaries side by side,
the two sets written as boolean masks from the definitions of A_i and B_i;
the summaries are a reshape to (S / c, c) and a softmax.  The reference is
given the same heads the system holds (a share of the published ones).

Departures, summation order only: query rows are taken `query_block` at a
time, each block's body `jax.checkpoint`ed; the layers have the same leaves
and are one `jax.checkpoint`ed body `lax.scan`ned over their stacked
parameters; the feed-forward takes `row_block` rows at a time, each block's
body `jax.checkpoint`ed; the trunk and the heads run one sequence at a time
(`lax.map`).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax


class Sizes(NamedTuple):
    n_head: int
    chunk: int
    window: int
    n_pred_heads: int
    rope_theta: float
    rms_eps: float
    query_block: int
    row_block: int


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * (1.0 + w)


def rope_halves(x, theta):
    """x (rows, heads, d): position m's dim i turned with dim i + d/2 by
    m theta^(-2i/d)."""
    rows, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    angles = jnp.arange(rows, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def by_rows(fn, x, block):
    """``fn`` over x's rows ``block`` at a time, each block's body
    `jax.checkpoint`ed: a function of a row alone, so only the order of
    sums in a gradient differs."""
    block = min(block, x.shape[0])
    assert x.shape[0] % block == 0, (x.shape, block)
    out = jax.lax.map(jax.checkpoint(fn), x.reshape(-1, block, x.shape[1]))
    return out.reshape(x.shape[0], -1)


def summaries(k, v, phi, mu, chunk):
    """k, v (rows, h, d), phi, mu (h, d) -> ks, vs (rows / c, h, d)."""
    rows, h, d = k.shape
    kc = k.reshape(rows // chunk, chunk, h, d)
    vc = v.reshape(rows // chunk, chunk, h, d)
    a = jax.nn.softmax(jnp.sum(kc * phi, axis=-1), axis=1)[..., None]
    return jnp.sum(a * kc, axis=1) + mu, jnp.sum(a * vc, axis=1)


def attended(query_rows, seq, sizes: Sizes):
    """(rows, seq) and (rows, seq / c): the token keys A_i and the summaries
    B_i of each of ``query_rows``."""
    i = query_rows[:, None]
    j = jnp.arange(seq)[None]
    n = jnp.arange(seq // sizes.chunk)[None]
    own = (j // sizes.window == i // sizes.window) & (j <= i)
    earlier = (n * sizes.chunk) // sizes.window < i // sizes.window
    return own, earlier


def eva(x, p, sizes: Sizes):
    """x (seq, E) -> the operator's result (seq, E)."""
    rows, h = x.shape[0], sizes.n_head
    d = p["wq"].shape[1] // h
    q = rope_halves((x @ p["wq"]).reshape(rows, h, d), sizes.rope_theta)
    k = rope_halves((x @ p["wk"]).reshape(rows, h, d), sizes.rope_theta)
    v = (x @ p["wv"]).reshape(rows, h, d)
    ks, vs = summaries(k, v, p["phi"], p["mu"], sizes.chunk)
    # (h, rows, d); both sources side by side: S + S / c keys
    q = q.transpose(1, 0, 2)
    keys = jnp.concatenate([k, ks], axis=0).transpose(1, 0, 2)
    values = jnp.concatenate([v, vs], axis=0).transpose(1, 0, 2)
    block = min(sizes.query_block, rows)
    assert rows % block == 0, (rows, block)

    @jax.checkpoint
    def some(start):
        seen = jnp.concatenate(
            attended(start + jnp.arange(block), rows, sizes), axis=1)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = qb @ keys.transpose(0, 2, 1) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return probs @ values                               # (h, block, d)

    out = jax.lax.map(some, jnp.arange(0, rows, block))
    out = out.transpose(0, 2, 1, 3).reshape(rows, h * d)
    return out @ p["wo"]


def layer(x, p, sizes: Sizes):
    h = x + eva(rms_norm(x, p["norm1"], sizes.rms_eps), p, sizes)
    return h + by_rows(
        lambda rows: swiglu(rms_norm(rows, p["norm2"], sizes.rms_eps),
                            p["gate"], p["up"], p["down"]),
        h, sizes.row_block)


def streams(params, inputs, sizes: Sizes):
    """inputs (seq,) -> the stream after each layer, (layers, seq, E)."""
    one = jax.checkpoint(lambda x, p: (layer(x, p, sizes),) * 2)
    return jax.lax.scan(one, params["embed"][inputs], params["layers"])[1]


def trunk(params, inputs, sizes: Sizes):
    """inputs (seq,) -> the rows after the final norm (seq, E)."""
    one = jax.checkpoint(lambda x, p: (layer(x, p, sizes), None))
    x, _ = jax.lax.scan(one, params["embed"][inputs], params["layers"])
    return rms_norm(x, params["norm_f"], sizes.rms_eps)


def logits(params, inputs, sizes: Sizes):
    """inputs (seq,) -> (seq, P, V): head p's row i is of position
    i + 1 + p."""
    x = trunk(params, inputs, sizes)
    return (x @ params["head"]).reshape(x.shape[0], sizes.n_pred_heads, -1)


def losses(params, tokens, sizes: Sizes):
    """tokens (batch, seq + 1) -> L."""
    seq = tokens.shape[1] - 1

    def sequence(row):
        logp = jax.nn.log_softmax(logits(params, row[:-1], sizes), axis=-1)
        sums = []
        for p in range(sizes.n_pred_heads):
            targets = row[1 + p:]                          # (seq - p,)
            sums.append(-jnp.sum(jnp.take_along_axis(
                logp[:seq - p, p], targets[:, None], axis=-1)))
        return jnp.stack(sums)

    sums = jnp.sum(jax.lax.map(sequence, tokens), axis=0)   # (P,)
    counts = tokens.shape[0] * (seq - jnp.arange(sizes.n_pred_heads))
    return jnp.mean(sums / counts)


def adamw(settings):
    """The configuration's optimizer settings, as `optax.adamw` takes
    them."""
    return optax.adamw(settings["learning_rate"], b1=settings["b1"],
                       b2=settings["b2"], eps=settings["eps"],
                       weight_decay=settings["weight_decay"])


def make_train_step(sizes: Sizes, optimizer):
    """step(params, opt_state, tokens) -> (params, opt_state, L): one AdamW
    step on L's gradient; L as before the step."""

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(losses)(params, tokens, sizes)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return step


def first_losses(params, batches, sizes: Sizes, optimizer_settings):
    """[L] of the first len(batches) steps from `params`, one call of the
    jitted step a batch: the state is donated from call to call, so one copy
    of it lives.  `batches` is (steps, batch, seq + 1)."""
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

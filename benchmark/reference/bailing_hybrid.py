"""The plain reference for the `bailing_hybrid` family: what `correct` is
judged against.

inclusionAI's Ling-3.0-flash (`model_type` `bailing_hybrid`), from its
published `config.json` and the equations ISSUE 65 wrote down (Kimi Delta
Attention: Kimi Linear, arXiv:2510.26692; latent attention and the routing:
DeepSeek-V3, arXiv:2412.19437).  x is (tokens, E); no projection has a bias;
RMSNorm has a learned gain; H heads of K = V = 128.

    every layer: h = x + Mix(RMSNorm(x)); y = h + F(RMSNorm(h)); a final
      RMSNorm; an untied head; mean next-token cross-entropy alone.
    KDA mixer (`Sizes.kinds[i] == "kda"`), no position embedding:
      q, k, v = silu(conv(u W_q)), silu(conv(u W_k)), silu(conv(u W_v)),
      conv(x)_t = sum_j taps[:, j] * x_{t-3+j}, 4 taps a channel, zeros
      before the sequence, no bias;  q and k L2-normalised over a head's 128,
      x / sqrt(sum x^2 + 1e-6), and q times 128^-1/2;
      g_t = bound * sigmoid(exp(A_log_h) (u W_f + dt_bias)), bound = -5, a
      (H, 128) vector a position;  alpha_t = exp(g_t);
      beta_t = sigmoid(u W_b), a scalar a head;
      S_t = (I - beta_t k_t k_t') Diag(alpha_t) S_{t-1} + beta_t k_t v_t',
      S (128, 128) a head, zero before the sequence;  o_t = S_t' q_t;
      out = RMSNorm_128(o_t) * gain * sigmoid(u W_g);  then W_o.
    MLA mixer ("attn"): q = u Wq, per head [q_nope | q_rope];  [c | k_r] =
      u Wkv_a;  c = RMSNorm(c);  per head [k_nope | v] = c Wkv_b;  RoPE,
      adjacent pairs (2i, 2i+1) turning by frequency i, on q_rope of each
      head and on the ONE k_r every head shares;  k = [k_nope | k_r];
      causal softmax of q k' / sqrt(nope + rope);  o = P v;  each head's o
      times sigmoid(u W_gate), a scalar a head;  concat heads;  Wo.
    F: a SwiGLU `down(silu(gate(u)) * up(u))` in a dense layer, else the
      mixture: s = sigmoid(u Wr) over ALL N experts;  c = s + b;  the
      experts stand in `n_group` groups of N / n_group consecutive ones, a
      group scores the sum of its two largest c, the `topk_group` best
      groups stay;  the top k of c among theirs;  weights s (without b) at
      the chosen / (their sum + 1e-20) x routed_scale;
      F(u) = sum_i w_i E_i(u) + Shared(u).
    b (`noaux_tc`): no gradient, no weight decay, no AdamW moments; after
      each step b_e += speed * sign(mean_e'(n_e') - n_e), n the rows each
      expert was sent by this batch's tokens in that layer.

Everything is `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, no rows sorted by expert,
no grouped matmul, no chunked algebra, nothing of `ray_tpu`.  The delta rule is run
POSITION BY POSITION exactly as its equation reads (a `lax.scan` over time
that carries the (H, 128, 128) state); the convolution is a sum over taps of
shifted copies; MLA's softmax is a masked softmax over the scores of a block
of queries against every key; the experts are a loop over those HELD (the
share of an expert-parallel layer this reference is given: stacks of `count`
experts, the first of them expert `held_first` of the router's columns),
each applied to every token with the token's weight, zero where it did not
choose the expert.  The heads are those held likewise: the matrices it is
given are as wide as they are, and what the absent heads and experts would
add is left out, as in the system.

Departures, summation order only: the rule walks `scan_block` positions
inside a `jax.checkpoint`, block after block; queries are taken
`query_block` at a time; a dense feed-forward and the head take `row_block`
rows at a time; the held experts are `lax.scan`ned, each one's body
`jax.checkpoint`ed; each layer is `jax.checkpoint`ed; trunk and head run one
sequence at a time (`lax.map`).  No statistic crosses sequences but the bias
rule's counts, which are summed over them.

Parameters: {"embed" (V, E), "head" (E, V), "norm_f" (E,), "layers": a list,
one dict a layer}.  Every layer has "norm1", "norm2" (E,); a KDA layer "wq",
"wk", "wv", "wf", "wg" (E, H 128), "taps_q", "taps_k", "taps_v" (H 128, 4),
"a_log" (H,), "dt_bias" (H 128,), "wb" (E, H), "gain" (128,), "wo"
(H 128, E); an MLA layer "wq" (E, H (nope + rope)), "wkv_a" (E, R + rope),
"kv_norm" (R,), "wkv_b" (R, H (nope + v)), "wgate" (E, H), "wo" (H v, E); a
dense layer "gate", "up" (E, F), "down" (F, E); a routed one "router" (E, N),
"e_gate", "e_up" (count, E, W), "e_down" (count, W, E), "s_gate", "s_up"
(E, Ws), "s_down" (Ws, E).  The routing biases are no parameters: a list, one
(N,) a routed layer in order, beside them.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax

KDA, MLA = "kda", "attn"


class Sizes(NamedTuple):
    kinds: Tuple[str, ...]        # a layer's mixer, in order
    n_head: int                   # heads held
    head_dim: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    top_k: int
    n_group: int
    topk_group: int
    routed_scale: float
    held_first: int = 0
    gate_bound: float = -5.0
    rope_theta: float = 6e6
    rms_eps: float = 1e-6
    l2_eps: float = 1e-6
    bias_update_speed: float = 0.001
    query_block: int = 256
    scan_block: int = 64
    row_block: int = 2048


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def by_rows(fn, x, block):
    """fn over ``block`` rows of x (rows, .) at a time, each recomputed."""
    rows = x.shape[0]
    block = min(block, rows)
    assert rows % block == 0, (rows, block)
    out = jax.lax.map(jax.checkpoint(fn),
                      x.reshape(rows // block, block, *x.shape[1:]))
    return out.reshape(rows, *out.shape[2:])


# -- Kimi Delta Attention ------------------------------------------------------

def conv(x, taps):
    """x (seq, C): out_t = sum_j taps[:, j] * x_{t-(K-1)+j}."""
    s, c = x.shape
    k = taps.shape[1]
    out = jnp.zeros_like(x)
    for j in range(k):
        back = k - 1 - j                    # tap j reads position t - back
        shifted = jnp.concatenate(
            [jnp.zeros((back, c), x.dtype), x[:s - back]], axis=0)
        out = out + taps[:, j] * shifted
    return out


def l2_norm(x, eps):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def delta_rule(q, k, v, g, beta, block):
    """q, k, g (seq, H, K); v (seq, H, V); beta (seq, H) -> o (seq, H, V), one
    position after another."""
    s = q.shape[0]
    block = min(block, s)
    assert s % block == 0, (s, block)

    def position(state, t):
        qt, kt, vt, gt, bt = t
        decayed = jnp.exp(gt)[:, :, None] * state           # Diag(alpha) S
        seen = jnp.einsum("hk,hkv->hv", kt, decayed)        # k' Diag(alpha) S
        state = decayed - (bt[:, None] * kt)[:, :, None] * seen[:, None, :] \
            + (bt[:, None] * kt)[:, :, None] * vt[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt)

    @jax.checkpoint
    def positions(state, ts):
        return jax.lax.scan(position, state, ts)

    heads, width = q.shape[1], q.shape[2]
    _, o = jax.lax.scan(
        positions, jnp.zeros((heads, width, v.shape[2]), q.dtype),
        tuple(x.reshape(s // block, block, *x.shape[1:])
              for x in (q, k, v, g, beta)))
    return o.reshape(v.shape)


def kda(u, p, sizes: Sizes):
    """u (seq, E), one sequence."""
    s = u.shape[0]
    h, d = sizes.n_head, sizes.head_dim
    heads = lambda x: x.reshape(s, h, d)
    taken = jax.checkpoint(lambda x, taps: jax.nn.silu(conv(x, taps)))
    q = l2_norm(heads(taken(u @ p["wq"], p["taps_q"])), sizes.l2_eps) \
        / jnp.sqrt(jnp.float32(d))
    k = l2_norm(heads(taken(u @ p["wk"], p["taps_k"])), sizes.l2_eps)
    v = heads(taken(u @ p["wv"], p["taps_v"]))
    g = sizes.gate_bound * jax.nn.sigmoid(
        jnp.exp(p["a_log"])[None, :, None] * heads(u @ p["wf"] + p["dt_bias"]))
    beta = jax.nn.sigmoid(u @ p["wb"])
    o = delta_rule(q, k, v, g, beta, sizes.scan_block)
    o = rms_norm(o, p["gain"], sizes.rms_eps) \
        * jax.nn.sigmoid(heads(u @ p["wg"]))
    return o.reshape(s, h * d) @ p["wo"]


# -- latent attention ----------------------------------------------------------

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
    out = out.transpose(0, 2, 1, 3).reshape(s, h, sizes.v_head_dim)
    out = out * jax.nn.sigmoid(x @ p["wgate"])[:, :, None]
    return out.reshape(s, h * sizes.v_head_dim) @ p["wo"]


# -- the mixture ---------------------------------------------------------------

def route(x, p, bias, sizes: Sizes):
    """x (tokens, E) -> ((tokens, N): each token's weight for every expert,
    zero where it did not choose it; (tokens, N) of 0 and 1: its choice)."""
    s = jax.nn.sigmoid(x @ p["router"])
    c = s + bias
    tokens, n = c.shape
    per = n // sizes.n_group
    grouped = c.reshape(tokens, sizes.n_group, per)
    two = jnp.sort(grouped, axis=-1)[..., -2:]
    score = jnp.sum(two, axis=-1)                           # (tokens, groups)
    _, best = jax.lax.top_k(score, sizes.topk_group)
    stays = jnp.sum(jax.nn.one_hot(best, sizes.n_group), axis=1) > 0
    among = jnp.where(jnp.repeat(stays, per, axis=1), c, -jnp.inf)
    _, chosen = jax.lax.top_k(among, sizes.top_k)
    chosen = jnp.sum(jax.nn.one_hot(chosen, n), axis=1)
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


# -- the decoder ---------------------------------------------------------------

def layer(x, p, bias, sizes: Sizes, kind: str):
    """One layer on one sequence -> (y, rows sent to each expert; None from
    a dense layer)."""
    u = rms_norm(x, p["norm1"], sizes.rms_eps)
    h = x + (kda(u, p, sizes) if kind == KDA else attention(u, p, sizes))
    u = rms_norm(h, p["norm2"], sizes.rms_eps)
    if "router" not in p:
        return h + by_rows(
            lambda r: swiglu(r, p["gate"], p["up"], p["down"]), u,
            sizes.row_block), None
    y, rows = moe(u, p, bias, sizes)
    return h + y, rows


def streams(params, biases, inputs, sizes: Sizes):
    """inputs (seq,), one sequence -> ([the stream after each layer], the
    rows sent to every expert in every routed layer (routed layers, N))."""
    x, out, rows, biases = params["embed"][inputs], [], [], list(biases)
    for p, kind in zip(params["layers"], sizes.kinds):
        bias = biases.pop(0) if "router" in p else None
        x, sent = jax.checkpoint(layer, static_argnums=(3, 4))(
            x, p, bias, sizes, kind)
        out.append(x)
        if sent is not None:
            rows.append(sent)
    return out, jnp.stack(rows)


def trunk(params, biases, inputs, sizes: Sizes):
    """-> ((seq, E) after the final norm, the rows (routed layers, N))."""
    out, rows = streams(params, biases, inputs, sizes)
    return rms_norm(out[-1], params["norm_f"], sizes.rms_eps), rows


def logits(params, biases, inputs, sizes: Sizes):
    """inputs (batch, seq) -> (batch, seq, V)."""
    return jnp.stack([trunk(params, biases, row, sizes)[0] @ params["head"]
                      for row in inputs])


def losses(params, biases, tokens, sizes: Sizes):
    """tokens (batch, seq + 1) -> (mean cross-entropy, rows sent to every
    expert by the whole batch (routed layers, N)); a sequence at a time, the
    logits `row_block` rows at a time."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    def summed_xent(xt):
        x, t = xt
        logp = jax.nn.log_softmax(x @ params["head"], axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, t[:, None], axis=-1))

    def sequence(xt):
        x, rows = trunk(params, biases, xt[0], sizes)
        s = x.shape[0]
        block = min(sizes.row_block, s)
        assert s % block == 0, (s, block)
        return jnp.sum(jax.lax.map(jax.checkpoint(summed_xent), (
            x.reshape(s // block, block, -1),
            xt[1].reshape(s // block, block)))), rows

    xent, rows = jax.lax.map(sequence, (inputs, targets))
    return jnp.sum(xent) / targets.size, jnp.sum(rows, axis=0)


def update_biases(biases, rows, sizes: Sizes):
    n = rows.astype(jnp.float32)
    moved = jnp.stack(biases) + sizes.bias_update_speed * jnp.sign(
        jnp.mean(n, axis=-1, keepdims=True) - n)
    return list(moved)


def adamw(settings):
    """The configuration's optimizer settings, as `optax.adamw` takes
    them."""
    return optax.adamw(settings["learning_rate"], b1=settings["b1"],
                       b2=settings["b2"], eps=settings["eps"],
                       weight_decay=settings["weight_decay"])


def make_train_step(sizes: Sizes, optimizer):
    """step(params, biases, opt_state, tokens) -> (params, biases,
    opt_state, loss, rows): one AdamW step on the cross-entropy's gradient,
    then the bias rule; loss and rows as before the step."""

    def step(params, biases, opt_state, tokens):
        (loss, rows), grads = jax.value_and_grad(losses, has_aux=True)(
            params, jax.lax.stop_gradient(biases), tokens, sizes)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates),
                update_biases(biases, rows, sizes), opt_state, loss, rows)

    return step


def first_losses(params, biases, batches, sizes: Sizes, optimizer_settings):
    """The cross-entropies of the first len(batches) steps from `params`
    and `biases`, one call of the jitted step a batch: the state is donated
    from call to call, so one copy of it lives.  `batches` is (steps, batch,
    seq + 1)."""
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

"""The plain reference for the `sdar` family: what `correct` is judged
against.

SDAR-30B-A3B-Chat (`model_type` `sdar_moe`), from its published
`config.json`: the block is Qwen3-MoE's, whose keys the config carries; the
training form is block diffusion's (Arriola et al., ICLR 2025, BD3-LM's
vectorised objective), which SDAR's adaptation keeps.  x is (rows, E); no
projection has a bias; RMSNorm has a learned gain.  L tokens a sequence,
block length b, block(i) = i // b.

    noise, step n: key = fold_in(fold_in(PRNGKey(seed), 0x5DA2), n), split
      in two; t = 1 - uniform(first, (batch, L / b)), one level a block;
      m = uniform(second, (batch, L)) < t of the token's block;
      xn_i = MASK where m_i, else x_i; a row's weight is m_i / t.
    rows: a sequence's L clean rows (tokens x) and then its L noised rows
      (tokens xn), 2 L in all; row r is of kind r // L (0 clean, 1 noised)
      at position r % L, which is what RoPE turns it by.
    h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h));  final RMSNorm; an
      untied head.
    Attn: q = u W_q as H heads, k = u W_k and v = u W_v as H_kv heads;
      RMSNorm over each q head and each k head (one gain vector each);
      RoPE on the whole head, rotate-half: dim i turns with dim i + D/2 by
      the angle m * theta^(-2i/D);  o_r = sum_s softmax_s(q_r . k_s /
      sqrt(D)) v_s over the keys s row r attends, query head h against
      key/value head h // (H / H_kv);  concat heads;  W_o.
    the rule, `attended`: a clean row attends the clean rows of no later
      block than its own; a noised row attends the clean rows of strictly
      earlier blocks and the noised rows of its own block; nothing else.
    MoE: g = softmax(u W_g) over ALL experts in float32; the top k of g;
      their weights g over their sum (`norm_topk_prob`);  sum_i w_i E_i(u),
      each E_i a SwiGLU; no shared expert, no bias.
    L_D = 1 / (batch L) sum over the NOISED rows of (m_i / t) CE(row L + i,
      x_i): no shift, the clean rows reach no head.
    L_B = sum over the layers of N sum_e f_e P_e over all 2 L rows: N the
      experts, f_e the share of the batch's (rows x k) assignments that
      went to expert e (a count, no gradient), P_e the mean of g_e.
    The objective is L_D + aux_weight * L_B.

Everything is `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, no tile, no grouped
matmul, nothing of `ray_tpu`.  Attention is one masked softmax over the
scores of a block of query rows against all 2 L keys, the rule written out
as a comparison of block indices and kinds, the key/value heads repeated by
`jnp.repeat`; the experts are a loop over those HELD (the share of an
expert-parallel layer this reference is given: stacks of `count` experts,
the first of them expert `held_first` of the router's columns), each
applied to every row with the row's weight, zero where it did not choose
the expert.  What the absent experts would add is left out, as in the
system.

Departures, summation order only: query rows are taken `query_block` at a
time, each block's body `jax.checkpoint`ed; the held experts are
`lax.scan`ned, each one's body `jax.checkpoint`ed; the layers are alike and
are one `jax.checkpoint`ed body `lax.scan`ned over their stacked parameters;
the trunk and the head run one sequence at a time (`lax.map`).  No statistic
crosses sequences but L_B's counts and probabilities, which are summed over
them first.

Parameters: {"embed" (V, E), "norm_f" (E,), "head" (E, V), "layers": a
layer's leaves stacked (layers, ...)}.  A layer: "norm1", "norm2" (E,);
"wq" (E, H D), "wk", "wv" (E, H_kv D), "wo" (H D, E), "q_norm", "k_norm"
(D,); "router" (E, N), "e_gate", "e_up" (count, E, W), "e_down"
(count, W, E).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax

NOISE_STREAM = 0x5DA2


class Sizes(NamedTuple):
    n_head: int
    n_kv_head: int
    top_k: int                      # experts a token
    mask_token: int
    block_length: int = 4
    norm_topk_prob: bool = True
    held_first: int = 0
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    aux_weight: float = 0.001
    query_block: int = 512


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def rope_halves(x, positions, theta):
    """x (rows, heads, d), positions (rows,): position m turns the pair
    (x_i, x_{i+d/2}) by the angle m * theta^(-2i/d)."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = (positions.astype(jnp.float32)[:, None]
             * inv_freq[None])[:, None, :]                  # (rows, 1, d/2)
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [first * jnp.cos(angle) - second * jnp.sin(angle),
         first * jnp.sin(angle) + second * jnp.cos(angle)], axis=-1)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def noise(seed, step, batch, seq, block):
    """Step ``step``'s noise -> (m (batch, seq) bool, t (batch, seq): the
    level of each token's block)."""
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(seed), NOISE_STREAM), step)
    levels, tokens = jax.random.split(key)
    t = 1.0 - jax.random.uniform(levels, (batch, seq // block))
    t = jnp.repeat(t, block, axis=1)
    return jax.random.uniform(tokens, (batch, seq)) < t, t


def attended(query_rows, seq, block):
    """(len(query_rows), 2 seq) bool: which of a sequence's 2 seq rows each
    query row attends."""
    kind = lambda r: r // seq               # 0 clean, 1 noised
    at = lambda r: r % seq // block         # the row's block
    q, k = query_rows[:, None], jnp.arange(2 * seq)[None]
    clean_to_clean = (kind(q) == 0) & (kind(k) == 0) & (at(k) <= at(q))
    noised_to_clean = (kind(q) == 1) & (kind(k) == 0) & (at(k) < at(q))
    noised_to_own = (kind(q) == 1) & (kind(k) == 1) & (at(k) == at(q))
    return clean_to_clean | noised_to_clean | noised_to_own


def attention(x, p, sizes: Sizes):
    """x (2 seq, E), one sequence's clean and then its noised rows -> the
    operator's result (2 seq, E)."""
    rows = x.shape[0]
    seq = rows // 2
    h, h_kv = sizes.n_head, sizes.n_kv_head
    d = p["wq"].shape[1] // h
    positions = jnp.arange(rows) % seq
    q = rms_norm((x @ p["wq"]).reshape(rows, h, d), p["q_norm"],
                 sizes.rms_eps)
    k = rms_norm((x @ p["wk"]).reshape(rows, h_kv, d), p["k_norm"],
                 sizes.rms_eps)
    v = (x @ p["wv"]).reshape(rows, h_kv, d)
    q = rope_halves(q, positions, sizes.rope_theta)
    k = rope_halves(k, positions, sizes.rope_theta)
    # query head i reads key/value head i // (h / h_kv)
    k, v = (jnp.repeat(t, h // h_kv, axis=1) for t in (k, v))
    q, k, v = (t.transpose(1, 0, 2) for t in (q, k, v))    # (h, rows, d)
    block = min(sizes.query_block, rows)
    assert rows % block == 0, (rows, block)

    @jax.checkpoint
    def some(start):
        seen = attended(start + jnp.arange(block), seq, sizes.block_length)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = qb @ k.transpose(0, 2, 1) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return probs @ v                                    # (h, block, d)

    out = jax.lax.map(some, jnp.arange(0, rows, block))
    out = out.transpose(0, 2, 1, 3).reshape(rows, h * d)
    return out @ p["wo"]


def route(x, p, sizes: Sizes):
    """x (rows, E) -> (rows, N): each row's weight for every expert, zero
    where the expert is not among its top k."""
    g = jax.nn.softmax(x @ p["router"], axis=-1)
    _, chosen = jax.lax.top_k(g, sizes.top_k)
    chosen = jnp.sum(jax.nn.one_hot(chosen, g.shape[-1]), axis=1)
    picked = g * chosen
    if sizes.norm_topk_prob:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return picked, chosen


def moe(x, p, sizes: Sizes):
    """x (rows, E) -> (y, rows sent to each of all the experts, the
    router's probabilities summed over the rows)."""
    weights, chosen = route(x, p, sizes)
    count = p["e_gate"].shape[0]
    held = jax.lax.dynamic_slice_in_dim(
        weights, sizes.held_first, count, axis=1)           # (rows, count)

    @jax.checkpoint
    def expert(x, gate, up, down, w):
        return swiglu(x, gate, up, down) * w[:, None]

    def add(total, e):
        return total + expert(x, *e), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(x),
                        (p["e_gate"], p["e_up"], p["e_down"], held.T))
    return y, jnp.sum(chosen, axis=0), jnp.sum(
        jax.nn.softmax(x @ p["router"], axis=-1), axis=0)


def layer(x, p, sizes: Sizes):
    """One layer on one sequence's 2 seq rows -> (y, (rows sent to each
    expert, the router's probabilities summed over the rows))."""
    h = x + attention(rms_norm(x, p["norm1"], sizes.rms_eps), p, sizes)
    y, rows, probs = moe(rms_norm(h, p["norm2"], sizes.rms_eps), p, sizes)
    return h + y, (rows, probs)


def trunk(params, clean, noised, sizes: Sizes):
    """clean, noised (seq,): one sequence's tokens and their noised copy ->
    (the NOISED rows after the final norm (seq, E), the rows sent to every
    expert in every layer and the routers' probabilities summed over the
    2 seq rows, each (layers, N))."""
    one = jax.checkpoint(lambda x, p: layer(x, p, sizes))
    x, (rows, probs) = jax.lax.scan(
        one, params["embed"][jnp.concatenate([clean, noised])],
        params["layers"])
    x = x[clean.shape[0]:]
    return rms_norm(x, params["norm_f"], sizes.rms_eps), rows, probs


def row_losses(params, tokens, masked, sizes: Sizes):
    """tokens (batch, seq), masked (batch, seq) bool -> (every noised
    row's cross-entropy against its own clean token (batch, seq), the rows
    sent to every expert by the whole batch (layers, N), the routers'
    probabilities summed over the batch's rows (layers, N))."""
    noised = jnp.where(masked, sizes.mask_token, tokens)

    @jax.checkpoint
    def xent(x, t):
        logp = jax.nn.log_softmax(x @ params["head"], axis=-1)
        return -jnp.take_along_axis(logp, t[:, None], axis=-1)[:, 0]

    def sequence(pair):
        x, rows, probs = trunk(params, *pair, sizes)
        return xent(x, pair[0]), rows, probs

    ce, rows, probs = jax.lax.map(sequence, (tokens, noised))
    return ce, jnp.sum(rows, axis=0), jnp.sum(probs, axis=0)


def losses(params, tokens, seed, step, sizes: Sizes):
    """tokens (batch, seq + 1), whose last column is not read -> (the
    objective L_D + aux_weight L_B, (L_D, L_B, rows sent to every expert by
    the whole batch (layers, N), the noised rows' cross-entropies
    (batch, seq), m, t)); a sequence at a time."""
    tokens = tokens[:, :-1]
    batch, seq = tokens.shape
    m, t = noise(seed, step, batch, seq, sizes.block_length)
    ce, rows, probs = row_losses(params, tokens, m, sizes)
    diffusion = jnp.sum(jnp.where(m, ce / t, 0.0)) / tokens.size
    routed = 2 * tokens.size                 # rows a layer routes
    share = jax.lax.stop_gradient(rows) / (routed * sizes.top_k)
    balance = rows.shape[-1] * jnp.sum(share * probs / routed)
    return diffusion + sizes.aux_weight * balance, \
        (diffusion, balance, rows, ce, m, t)


def adamw(settings):
    """The configuration's optimizer settings, as `optax.adamw` takes
    them."""
    return optax.adamw(settings["learning_rate"], b1=settings["b1"],
                       b2=settings["b2"], eps=settings["eps"],
                       weight_decay=settings["weight_decay"])


def make_train_step(sizes: Sizes, optimizer, seed):
    """step(params, opt_state, tokens, n) -> (params, opt_state, (L_D,
    L_B)): AdamW step number n on the objective's gradient under that
    step's noise; the losses as before the step."""

    def step(params, opt_state, tokens, n):
        (_, (diffusion, balance, *_)), grads = jax.value_and_grad(
            losses, has_aux=True)(params, tokens, seed, n, sizes)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, \
            (diffusion, balance)

    return step


def first_losses(params, batches, seed, sizes: Sizes, optimizer_settings):
    """[(L_D, L_B)] of the first len(batches) steps from `params`, one call
    of the jitted step a batch: the state is donated from call to call, so
    one copy of it lives.  `batches` is (steps, batch, seq + 1)."""
    optimizer = adamw(optimizer_settings)
    step = jax.jit(make_train_step(sizes, optimizer, seed),
                   donate_argnums=(0, 1))
    opt_state = jax.jit(optimizer.init)(params)
    out = []
    for n, tokens in enumerate(batches):
        params, opt_state, parts = step(params, opt_state, tokens,
                                        jnp.int32(n))
        out.append(tuple(float(part) for part in parts))
    # freed now, not when the collector gets to it: the system's state is
    # born next and the chip does not hold both
    for leaf in jax.tree.leaves((params, opt_state)):
        leaf.delete()
    return out

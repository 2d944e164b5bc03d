"""The plain reference for the `keye_vl` family: what `correct` is judged
against.

Keye-VL-2.0's language model (`model_type` `KeyeVL2`), from its published
`config.json`: the block is Qwen3-MoE's, whose keys the config carries;
`sa_config` is DeepSeek-V3.2-Exp's lightning indexer with its sparse
training stage's loss.  x is (tokens, E); no projection has a bias; RMSNorm
has a learned gain.

    h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h));  final RMSNorm; an
      untied head; the objective is L_LM + L_I + aux_weight * L_B.
    Attn: q = u W_q as H heads, k = u W_k and v = u W_v as H_kv heads;
      RMSNorm over each q head and each k head (one gain vector each);
      RoPE on the whole head, rotate-half: dim i turns with dim i + D/2 by
      the angle m * theta^(-2i/D);  o_t = sum_{s in S_t} softmax_{s in
      S_t}(q_t . k_s / sqrt(D)) v_s, query head h against key/value head
      h // (H / H_kv), every head over the same S_t;  concat heads;  W_o.
    The indexer reads ub = stop_gradient(u):  qI = ub W_Iq as J heads of
      D_I;  kI = LayerNorm(ub W_Ik) with gain and bias, one head;  RoPE as
      above on the first D_I / 2 dims of each, the rest as they are;
      w = ub W_Iw / sqrt(J) / sqrt(D_I);
      I_{t,s} = sum_j w_{t,j} relu(qI_{t,j} . kI_s)   for s <= t;
      S_t = the min(topk, t + 1) keys s <= t of largest I_{t,s}, of equal
      scores the lower s: a STABLE descending sort of the row, its first
      min(topk, t + 1) entries.
    L_I = mean over the queries of KL(p_t || softmax_{s in S_t} I_{t,s}),
      p_t = stop_gradient(the mean over the H heads of the attention
      probabilities over S_t), summed over the layers.
    MoE: g = softmax(u W_g) over ALL experts in float32; the top k of g;
      their weights g over their sum (`norm_topk_prob`);  sum_i w_i E_i(u),
      each E_i a SwiGLU; no shared expert, no bias.
    L_B = sum over the layers of N sum_e f_e P_e: N the experts, f_e the
      share of the batch's (tokens x k) assignments that went to expert e
      (a count, no gradient), P_e the mean of g_e over the batch's tokens.

Everything is `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, no mask operand, no
threshold search, no grouped matmul, nothing of `ray_tpu`.  Attention and
the indexer are one masked softmax over the scores of a block of queries
against every key, the key/value heads repeated by `jnp.repeat`; the
experts are a loop over those HELD (the share of an expert-parallel layer
this reference is given: stacks of `count` experts, the first of them
expert `held_first` of the router's columns), each applied to every token
with the token's weight, zero where it did not choose the expert.  What the
absent experts would add is left out, as in the system.

Departures, summation order only: queries are taken `query_block` at a
time, each block's body `jax.checkpoint`ed; the held experts are
`lax.scan`ned, each one's body `jax.checkpoint`ed; the layers are alike and
are one `jax.checkpoint`ed body `lax.scan`ned over their stacked parameters;
the trunk and the head run one sequence at a time (`lax.map`).  No statistic
crosses sequences but L_B's counts and probabilities, which are summed over
them first.

Parameters: {"embed" (V, E), "norm_f" (E,), "head" (E, V), "layers": a
layer's leaves stacked (layers, ...)}.  A layer: "norm1", "norm2" (E,);
"wq" (E, H D), "wk", "wv" (E, H_kv D), "wo" (H D, E), "q_norm", "k_norm"
(D,); the indexer's "iq" (E, J D_I), "ik" (E, D_I), "iw" (E, J), "ik_gain",
"ik_bias" (D_I,); "router" (E, N), "e_gate", "e_up" (count, E, W), "e_down"
(count, W, E).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax


class Sizes(NamedTuple):
    n_head: int
    n_kv_head: int
    top_k: int                      # experts a token
    index_heads: int
    index_top_k: int                # keys a query
    norm_topk_prob: bool = True
    held_first: int = 0
    rope_theta: float = 1e7
    rms_eps: float = 1e-6
    aux_weight: float = 0.001
    query_block: int = 512


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def layer_norm(x, gain, bias, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred / jnp.sqrt(jnp.mean(jnp.square(centred), axis=-1,
                                       keepdims=True) + eps) * gain + bias


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


def rope_first_half(x, theta):
    """RoPE on the first half of x's last dim, the rest as it is."""
    half = x.shape[-1] // 2
    return jnp.concatenate(
        [rope_halves(x[..., :half], theta), x[..., half:]], axis=-1)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def detached(x):
    """x as a constant: what the indexer reads of the model."""
    return jax.lax.stop_gradient(x)


def indexer(x, p, sizes: Sizes):
    """x (seq, E), read as a constant -> (qI (J, seq, D_I), kI (seq, D_I),
    w (seq, J))."""
    x = detached(x)
    s, j = x.shape[0], sizes.index_heads
    d = p["iq"].shape[1] // j
    q = rope_first_half((x @ p["iq"]).reshape(s, j, d), sizes.rope_theta)
    k = layer_norm(x @ p["ik"], p["ik_gain"], p["ik_bias"], sizes.rms_eps)
    k = rope_first_half(k[:, None, :], sizes.rope_theta)[:, 0]
    w = x @ p["iw"] / jnp.sqrt(jnp.float32(j)) / jnp.sqrt(jnp.float32(d))
    return q.transpose(1, 0, 2), k, w


def index_scores(q, k, w):
    """q (J, rows, D_I), k (seq, D_I), w (rows, J) -> (rows, seq)."""
    return jnp.sum(w.T[:, :, None]
                   * jax.nn.relu(q @ k.T), axis=0)


def select(scores, seen, top_k):
    """scores (rows, seq), seen (rows, seq) bool: the keys a row's query
    may attend -> (rows, seq) bool: its min(top_k, keys seen) keys of
    largest score, of equal scores the lower key: the first entries of a
    stable descending sort."""
    scores = jnp.where(seen, scores, -jnp.inf)
    order = jnp.argsort(-scores, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)          # each key's place in it
    count = jnp.minimum(top_k, jnp.sum(seen, axis=-1, keepdims=True))
    return (rank < count) & seen


def attention(x, p, sizes: Sizes, selection=None):
    """x (seq, E), one sequence -> (the operator's result (seq, E), the sum
    over its queries of KL(p_t || softmax of the selected index
    scores)).  ``selection`` (seq, seq) bool: the keys each query attends,
    in place of the indexer's own choice (the benchmark's family holds the
    system's kernels to this softmax under the system's own selection)."""
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
    iq, ik, iw = indexer(x, p, sizes)
    block = min(sizes.query_block, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def rows(start):
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(s)[None]
        index = index_scores(
            jax.lax.dynamic_slice_in_dim(iq, start, block, axis=1), ik,
            jax.lax.dynamic_slice_in_dim(iw, start, block, axis=0))
        chosen = select(jax.lax.stop_gradient(index), seen,
                        sizes.index_top_k) if selection is None else \
            jax.lax.dynamic_slice_in_dim(selection, start, block, axis=0)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = qb @ k.transpose(0, 2, 1) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(chosen, scores, -jnp.inf), axis=-1)
        target = jax.lax.stop_gradient(jnp.mean(probs, axis=0))
        log_q = jax.nn.log_softmax(jnp.where(chosen, index, -jnp.inf),
                                   axis=-1)
        kl = jnp.sum(jnp.where(
            chosen, jax.scipy.special.xlogy(target, target) - target * log_q,
            0.0))
        return probs @ v, kl                                # (h, block, d)

    out, kl = jax.lax.map(rows, jnp.arange(0, s, block))
    out = out.transpose(0, 2, 1, 3).reshape(s, h * d)
    return out @ p["wo"], jnp.sum(kl)


def route(x, p, sizes: Sizes):
    """x (tokens, E) -> (tokens, N): each token's weight for every expert,
    zero where the expert is not among its top k."""
    g = jax.nn.softmax(x @ p["router"], axis=-1)
    _, chosen = jax.lax.top_k(g, sizes.top_k)
    chosen = jnp.sum(jax.nn.one_hot(chosen, g.shape[-1]), axis=1)
    picked = g * chosen
    if sizes.norm_topk_prob:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return picked, chosen


def moe(x, p, sizes: Sizes):
    """x (tokens, E) -> (y, rows sent to each of all the experts, the
    router's probabilities summed over the tokens)."""
    weights, chosen = route(x, p, sizes)
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
    return y, jnp.sum(chosen, axis=0), jnp.sum(
        jax.nn.softmax(x @ p["router"], axis=-1), axis=0)


def layer(x, p, sizes: Sizes):
    """One layer on one sequence -> (y, (rows sent to each expert, the
    router's probabilities summed over the sequence, the indexer's KL
    summed over the sequence's queries))."""
    a, kl = attention(rms_norm(x, p["norm1"], sizes.rms_eps), p, sizes)
    h = x + a
    y, rows, probs = moe(rms_norm(h, p["norm2"], sizes.rms_eps), p, sizes)
    return h + y, (rows, probs, kl)


def trunk(params, inputs, sizes: Sizes):
    """inputs (seq,), one sequence -> (x after the final norm, the rows
    sent to every expert in every layer and the routers' probabilities
    summed over the sequence, each (layers, N), the indexers' KL summed
    over layers and queries)."""
    one = jax.checkpoint(lambda x, p: layer(x, p, sizes))
    x, (rows, probs, kl) = jax.lax.scan(one, params["embed"][inputs],
                                        params["layers"])
    return rms_norm(x, params["norm_f"], sizes.rms_eps), rows, probs, \
        jnp.sum(kl)


def logits(params, inputs, sizes: Sizes):
    """inputs (batch, seq) -> (batch, seq, V)."""
    return jax.lax.map(
        lambda row: trunk(params, row, sizes)[0] @ params["head"], inputs)


def losses(params, tokens, sizes: Sizes):
    """tokens (batch, seq + 1) -> (the objective L_LM + L_I + aux_weight
    L_B, (L_LM the mean cross-entropy, L_I the indexers' loss, L_B the
    routers' load-balancing loss, rows sent to every expert by the whole
    batch (layers, N))); a sequence at a time."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    @jax.checkpoint
    def summed_xent(x, t):
        logp = jax.nn.log_softmax(x @ params["head"], axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, t[:, None], axis=-1))

    def sequence(xt):
        x, rows, probs, kl = trunk(params, xt[0], sizes)
        return summed_xent(x, xt[1]), kl, rows, probs

    xent, kl, rows, probs = jax.lax.map(sequence, (inputs, targets))
    xent, kl = jnp.sum(xent) / targets.size, jnp.sum(kl) / targets.size
    rows, probs = jnp.sum(rows, axis=0), jnp.sum(probs, axis=0)
    share = jax.lax.stop_gradient(rows) / (targets.size * sizes.top_k)
    balance = rows.shape[-1] * jnp.sum(share * probs / targets.size)
    return xent + kl + sizes.aux_weight * balance, (xent, kl, balance, rows)


def adamw(settings):
    """The configuration's optimizer settings, as `optax.adamw` takes
    them."""
    return optax.adamw(settings["learning_rate"], b1=settings["b1"],
                       b2=settings["b2"], eps=settings["eps"],
                       weight_decay=settings["weight_decay"])


def make_train_step(sizes: Sizes, optimizer):
    """step(params, opt_state, tokens) -> (params, opt_state, (L_LM, L_I,
    L_B)): one AdamW step on the objective's gradient; the losses as
    before the step."""

    def step(params, opt_state, tokens):
        (_, (xent, kl, balance, _)), grads = jax.value_and_grad(
            losses, has_aux=True)(params, tokens, sizes)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, \
            (xent, kl, balance)

    return step


def first_losses(params, batches, sizes: Sizes, optimizer_settings):
    """[(L_LM, L_I, L_B)] of the first len(batches) steps from `params`, one
    call of the jitted step a batch: the state is donated from call to
    call, so one copy of it lives.  `batches` is (steps, batch, seq + 1)."""
    optimizer = adamw(optimizer_settings)
    step = jax.jit(make_train_step(sizes, optimizer), donate_argnums=(0, 1))
    opt_state = jax.jit(optimizer.init)(params)
    out = []
    for tokens in batches:
        params, opt_state, parts = step(params, opt_state, tokens)
        out.append(tuple(float(part) for part in parts))
    # freed now, not when the collector gets to it: the system's state is
    # born next and the chip does not hold both
    for leaf in jax.tree.leaves((params, opt_state)):
        leaf.delete()
    return out

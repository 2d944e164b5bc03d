"""The plain reference for the `mellum` family: what `correct` is judged
against.

Mellum2-12B-A2.5B-Instruct (`model_type` `mellum`), from its published
`config.json`; the block is the Qwen3-MoE lineage's, whose keys the config
carries.  x is (rows, E); no projection has a bias; RMSNorm has a learned
gain.  S tokens a sequence; layer l is of kind `kinds[l]`, 0 a
`sliding_attention` layer and 1 a `full_attention` one.

    h = x + Attn_kind(RMSNorm(x));  y = h + MoE(RMSNorm(h));  final
      RMSNorm; an untied head.
    Attn: q = u W_q as H heads, k = u W_k and v = u W_v as H_kv heads;
      RMSNorm over each q head and each k head (one gain vector each);
      RoPE on the whole head, rotate-half: dim i turns with dim i + D/2 by
      the angle m f_i, cos and sin times c, by the layer's kind;
      o_r = sum_s softmax_s(q_r . k_s / sqrt(D)) v_s over the keys s row r
      attends, query head h against key/value head h // (H / H_kv);
      concat heads;  W_o.
    which keys, `attended`: a full layer, s <= r; a sliding layer,
      r - W < s <= r (the W latest, the row's own among them).
    the rotary table, `frequencies`: a sliding layer, f_i = theta^(-2i/D),
      c = 1.  A full layer, YaRN (Peng et al., arXiv:2309.00071, as
      `transformers`' `_compute_yarn_parameters` reads these keys):
      b_i = theta^(2i/D); d(n) = D ln(P / (2 pi n)) / (2 ln theta), P the
      original positions; low = floor(d(beta_fast)), high =
      ceil(d(beta_slow)); r_i = clip((i - low) / (high - low), 0, 1);
      f_i = (1 - r_i) / b_i + r_i / (factor b_i); c = `attention_factor`
      (0.1 ln(factor) + 1 where none is given), on q and on k.
    MoE: g = softmax(u W_g) over ALL experts in float32; the top k of g;
      their weights g over their sum (`norm_topk_prob`);  sum_i w_i E_i(u),
      each E_i a SwiGLU; no shared expert, no bias.
    L = the mean over the batch's tokens of CE(row i, x_{i+1}).
    L_B = sum over the layers of N sum_e f_e P_e: N the experts, f_e the
      share of the batch's (rows x k) assignments that went to expert e (a
      count, no gradient), P_e the mean of g_e.
    The objective is L + aux_weight * L_B.

Everything is `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, no tile, no grouped
matmul, nothing of `ray_tpu`.  Attention is one masked softmax over the
scores of a block of query rows against all S keys, each kind's rule
written out as a comparison of positions, the key/value heads repeated by
`jnp.repeat`; the experts are a loop over those HELD (the share of an
expert-parallel layer this reference is given: stacks of `count` experts,
the first of them expert `held_first` of the router's columns), each
applied to every row with the row's weight, zero where it did not choose
the expert.  What the absent experts would add is left out, as in the
system.

Departures, summation order only: query rows are taken `query_block` at a
time, each block's body `jax.checkpoint`ed; the held experts are
`lax.scan`ned, each one's body `jax.checkpoint`ed; the layers have the
same leaves and are one `jax.checkpoint`ed body `lax.scan`ned over their
stacked parameters with each layer's kind beside them (both kinds'
tables are made, and a layer selects its own by its kind); the trunk and
the head run one sequence at a time (`lax.map`), the head's logits
`head_block` rows at a time, each block's body `jax.checkpoint`ed.  No
statistic crosses sequences but L_B's counts and probabilities, which are
summed over them first.

Parameters: {"embed" (V, E), "norm_f" (E,), "head" (E, V), "layers": a
layer's leaves stacked (layers, ...)}.  A layer: "norm1", "norm2" (E,);
"wq" (E, H D), "wk", "wv" (E, H_kv D), "wo" (H D, E), "q_norm", "k_norm"
(D,); "router" (E, N), "e_gate", "e_up" (count, E, W), "e_down"
(count, W, E).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

SLIDING, FULL = 0, 1


class Sizes(NamedTuple):
    n_head: int
    n_kv_head: int
    top_k: int                      # experts a token
    kinds: Tuple[int, ...]          # a layer's kind, SLIDING or FULL
    window: int = 1024
    norm_topk_prob: bool = True
    held_first: int = 0
    rope_theta: float = 5e5
    yarn_factor: float = 16.0
    yarn_original: int = 8192       # `original_max_position_embeddings`
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: Optional[float] = None
    rms_eps: float = 1e-6
    aux_weight: float = 0.001
    query_block: int = 512
    head_block: int = 2048          # rows of logits alive at once


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def frequencies(d, kind, sizes: Sizes):
    """-> ((d / 2,) the angle a position turns pair i by, the factor c on
    cos and sin) of a kind of layer."""
    inv_freq = 1.0 / sizes.rope_theta ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if kind == SLIDING:
        return inv_freq, 1.0

    def dim_of(turns):
        return d * jnp.log(sizes.yarn_original / (turns * 2 * jnp.pi)) \
            / (2 * jnp.log(sizes.rope_theta))

    low = jnp.maximum(jnp.floor(dim_of(sizes.yarn_beta_fast)), 0)
    high = jnp.minimum(jnp.ceil(dim_of(sizes.yarn_beta_slow)), d - 1)
    high = jnp.where(high == low, high + 0.001, high)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    scaled = inv_freq / sizes.yarn_factor * ramp + inv_freq * (1 - ramp)
    c = sizes.yarn_attention_factor
    if c is None:
        c = 0.1 * jnp.log(sizes.yarn_factor) + 1.0
    return scaled, c


def rope_halves(x, positions, inv_freq, c):
    """x (rows, heads, d), positions (rows,): position m turns the pair
    (x_i, x_{i+d/2}) by the angle m * inv_freq_i; cos and sin times c."""
    d = x.shape[-1]
    angle = (positions.astype(jnp.float32)[:, None]
             * inv_freq[None])[:, None, :]                  # (rows, 1, d/2)
    cos, sin = jnp.cos(angle) * c, jnp.sin(angle) * c
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [first * cos - second * sin, first * sin + second * cos], axis=-1)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def attended(query_rows, seq, kind, window):
    """(len(query_rows), seq) bool: which keys each query row attends, by
    the layer's kind (a traced 0 or 1 where the layers are scanned)."""
    q, k = query_rows[:, None], jnp.arange(seq)[None]
    return (k <= q) & ((kind == FULL) | (k > q - window))


def attention(x, p, kind, sizes: Sizes):
    """x (seq, E) -> the operator's result (seq, E); ``kind`` a Python int
    or a traced one."""
    rows = x.shape[0]
    h, h_kv = sizes.n_head, sizes.n_kv_head
    d = p["wq"].shape[1] // h
    positions = jnp.arange(rows)
    q = rms_norm((x @ p["wq"]).reshape(rows, h, d), p["q_norm"],
                 sizes.rms_eps)
    k = rms_norm((x @ p["wk"]).reshape(rows, h_kv, d), p["k_norm"],
                 sizes.rms_eps)
    v = (x @ p["wv"]).reshape(rows, h_kv, d)
    plain, one = frequencies(d, SLIDING, sizes)
    yarn, c = frequencies(d, FULL, sizes)
    inv_freq = jnp.where(kind == FULL, yarn, plain)
    c = jnp.where(kind == FULL, c, one)
    q = rope_halves(q, positions, inv_freq, c)
    k = rope_halves(k, positions, inv_freq, c)
    # query head i reads key/value head i // (h / h_kv)
    k, v = (jnp.repeat(t, h // h_kv, axis=1) for t in (k, v))
    q, k, v = (t.transpose(1, 0, 2) for t in (q, k, v))    # (h, rows, d)
    block = min(sizes.query_block, rows)
    assert rows % block == 0, (rows, block)

    @jax.checkpoint
    def some(start):
        seen = attended(start + jnp.arange(block), rows, kind, sizes.window)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = qb @ k.transpose(0, 2, 1) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return probs @ v                                    # (h, block, d)

    out = jax.lax.map(some, jnp.arange(0, rows, block))
    out = out.transpose(0, 2, 1, 3).reshape(rows, h * d)
    return out @ p["wo"]


def route(x, p, sizes: Sizes):
    """x (rows, E) -> ((rows, N): each row's weight for every expert, zero
    where the expert is not among its top k; (rows, N) 1 where it is; the
    router's probabilities (rows, N))."""
    g = jax.nn.softmax(x @ p["router"], axis=-1)
    _, chosen = jax.lax.top_k(g, sizes.top_k)
    chosen = jnp.sum(jax.nn.one_hot(chosen, g.shape[-1]), axis=1)
    picked = g * chosen
    if sizes.norm_topk_prob:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return picked, chosen, g


def moe(x, p, sizes: Sizes):
    """x (rows, E) -> (y, rows sent to each of all the experts, the
    router's probabilities summed over the rows)."""
    weights, chosen, g = route(x, p, sizes)
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
    return y, jnp.sum(chosen, axis=0), jnp.sum(g, axis=0)


def layer(x, p, kind, sizes: Sizes):
    """One layer on one sequence's rows -> (y, (rows sent to each expert,
    the router's probabilities summed over the rows))."""
    h = x + attention(rms_norm(x, p["norm1"], sizes.rms_eps), p, kind, sizes)
    y, rows, probs = moe(rms_norm(h, p["norm2"], sizes.rms_eps), p, sizes)
    return h + y, (rows, probs)


def trunk(params, tokens, sizes: Sizes):
    """tokens (seq,) -> (the rows after the final norm (seq, E), the rows
    sent to every expert in every layer and the routers' probabilities
    summed over the rows, each (layers, N))."""
    one = jax.checkpoint(lambda x, pk: layer(x, pk[0], pk[1], sizes))
    x, (rows, probs) = jax.lax.scan(
        one, params["embed"][tokens],
        (params["layers"], jnp.asarray(sizes.kinds, jnp.int32)))
    return rms_norm(x, params["norm_f"], sizes.rms_eps), rows, probs


def row_losses(params, tokens, sizes: Sizes):
    """tokens (batch, seq + 1) -> (every row's cross-entropy against the
    next token (batch, seq), the rows sent to every expert by the whole
    batch (layers, N), the routers' probabilities summed over the batch's
    rows (layers, N))."""

    @jax.checkpoint
    def some(xt):
        x, t = xt
        logp = jax.nn.log_softmax(x @ params["head"], axis=-1)
        return -jnp.take_along_axis(logp, t[:, None], axis=-1)[:, 0]

    def xent(x, t):
        block = min(sizes.head_block, x.shape[0])
        assert x.shape[0] % block == 0, (x.shape, block)
        return jax.lax.map(some, (x.reshape(-1, block, x.shape[1]),
                                  t.reshape(-1, block))).reshape(-1)

    def sequence(row):
        x, rows, probs = trunk(params, row[:-1], sizes)
        return xent(x, row[1:]), rows, probs

    ce, rows, probs = jax.lax.map(sequence, tokens)
    return ce, jnp.sum(rows, axis=0), jnp.sum(probs, axis=0)


def losses(params, tokens, sizes: Sizes):
    """tokens (batch, seq + 1) -> (the objective L + aux_weight L_B, (L,
    L_B, rows sent to every expert by the whole batch (layers, N), the
    rows' cross-entropies (batch, seq))); a sequence at a time."""
    ce, rows, probs = row_losses(params, tokens, sizes)
    routed = ce.size                          # rows a layer routes
    share = jax.lax.stop_gradient(rows) / (routed * sizes.top_k)
    balance = rows.shape[-1] * jnp.sum(share * probs / routed)
    xent = jnp.mean(ce)
    return xent + sizes.aux_weight * balance, (xent, balance, rows, ce)


def adamw(settings):
    """The configuration's optimizer settings, as `optax.adamw` takes
    them."""
    return optax.adamw(settings["learning_rate"], b1=settings["b1"],
                       b2=settings["b2"], eps=settings["eps"],
                       weight_decay=settings["weight_decay"])


def make_train_step(sizes: Sizes, optimizer):
    """step(params, opt_state, tokens) -> (params, opt_state, (L, L_B)):
    one AdamW step on the objective's gradient; the losses as before the
    step."""

    def step(params, opt_state, tokens):
        (_, (xent, balance, *_)), grads = jax.value_and_grad(
            losses, has_aux=True)(params, tokens, sizes)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, \
            (xent, balance)

    return step


def first_losses(params, batches, sizes: Sizes, optimizer_settings):
    """[(L, L_B)] of the first len(batches) steps from `params`, one call
    of the jitted step a batch: the state is donated from call to call, so
    one copy of it lives.  `batches` is (steps, batch, seq + 1)."""
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

"""The plain reference for the `laguna` family: what `correct` is judged
against.

Laguna-XS.2 (`model_type` `laguna`), from its published `config.json`.  x is
(rows, E); no projection has a bias; RMSNorm has a learned gain.  S tokens a
sequence; layer l is of kind `layer_types[l]`, 0 a `full_attention` layer
and 1 a `sliding_attention` one, with H_l = `num_attention_heads_per_layer[l]`
query heads on H_kv key/value heads of D; its feed-forward is dense (it has
"gate", "up", "down") or sparse (it has a "router").

    h = x + Attn_kind(RMSNorm(x));  y = h + F(RMSNorm(h));  final RMSNorm;
      an untied head.
    Attn, u its input: q = u W_q as H_l heads, k = u W_k and v = u W_v as
      H_kv heads; RoPE on the FIRST R dims of each q and k head,
      rotate-half within them: dim i turns with dim i + R/2 by the angle
      m f_i, cos and sin times c; the last D - R dims pass as they are; R,
      f and c by the layer's kind;
      o_r = sum_s softmax_s(q_r . k_s / sqrt(D)) v_s over the keys s row r
      attends, query head h against key/value head h // (H_l / H_kv);
      g = sigmoid(u W_g), one scalar a head and row (W_g is (E, H_l));
      o_h <- g_h o_h;  concat heads;  W_o.
    which keys, `attended`: a full layer, s <= r; a sliding layer,
      r - W < s <= r (the W latest, the row's own among them).
    the rotary table, `frequencies`: a sliding layer, R = D
      (`partial_rotary_factor` 1), f_i = theta_s^(-2i/R), c = 1.  A full
      layer, R = D / 2 (`partial_rotary_factor` 0.5) and YaRN at dim R (Peng
      et al., arXiv:2309.00071, as `transformers`' `_compute_yarn_parameters`
      reads these keys, dim = head_dim x partial_rotary_factor):
      b_i = theta_f^(2i/R); d(n) = R ln(P / (2 pi n)) / (2 ln theta_f), P
      the original positions; low = floor(d(beta_fast)), high =
      ceil(d(beta_slow)); r_i = clip((i - low) / (high - low), 0, 1);
      f_i = (1 - r_i) / b_i + r_i / (factor b_i); c = `attention_factor`
      (0.1 ln(factor) + 1 where none is given), on q and on k.
    F dense: a SwiGLU.  F sparse: s = sigmoid(u W_r) over ALL experts; the
      top k of s + b (b the routing bias); weights s at the chosen over
      their sum (+ 1e-20), times `routed_scale`;
      F(u) = Shared(u) + sum_i w_i E_i(u), every one a SwiGLU, no token
      dropped, no auxiliary loss.
    L = the mean over the batch's tokens of CE(row i, x_{i+1}).
    After a step, b_e += speed * sign(mean(n) - n_e), n the rows each expert
      of the layer was sent by the whole batch; b is no optimizer leaf.

Everything is `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, no tile, no grouped
matmul, nothing of `ray_tpu`.  Attention is one masked softmax over the
scores of a block of query rows against all S keys, each kind's rule
written out as a comparison of positions, a group's query heads against
their key/value head by an `einsum` over the group; the experts are a loop
over those HELD (the share of an expert-parallel layer this reference is
given: stacks of `count` experts, the first of them expert `held_first` of
the router's columns), each applied to every row with the row's weight,
zero where it did not choose the expert.  What the absent experts would add
is left out, as in the system.

Departures, summation order only: query rows are taken `query_block` at a
time, each block's body `jax.checkpoint`ed; the held experts are
`lax.scan`ned, each one's body `jax.checkpoint`ed; neighbouring layers with
the same leaves (a GROUP: the same kind and the same feed-forward) are one
`jax.checkpoint`ed body `lax.scan`ned over their stacked parameters, the
groups walked in order; the trunk and the head run one sequence at a time
(`lax.map`), the head's logits `head_block` rows at a time, each block's
body `jax.checkpoint`ed.  No statistic crosses sequences but the rows sent
to each expert, which are summed over them.

Parameters: {"embed" (V, E), "norm_f" (E,), "head" (E, V), "groups": a list,
a group's layers' leaves stacked (layers of the group, ...)}; `Sizes.groups`
says each group's kind.  A layer: "norm1", "norm2" (E,); "wq" (E, H_l D),
"wk", "wv" (E, H_kv D), "wg" (E, H_l), "wo" (H_l D, E); dense: "gate", "up"
(E, F), "down" (F, E); sparse: "router" (E, N), "e_gate", "e_up"
(count, E, W), "e_down" (count, W, E), "s_gate", "s_up" (E, W_s), "s_down"
(W_s, E).  The routing biases ride beside them: a list, a group's (layers of
the group, N), None for a dense group.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

FULL, SLIDING = 0, 1


class Sizes(NamedTuple):
    n_kv_head: int
    head_dim: int
    top_k: int                      # experts a token
    groups: Tuple[int, ...]         # each group's kind, FULL or SLIDING
    window: int = 512
    routed_scale: float = 2.5
    held_first: int = 0
    theta_full: float = 5e5
    theta_sliding: float = 1e4
    rotary_full: int = 64           # the first dims of a head that turn
    rotary_sliding: int = 128
    yarn_factor: float = 64.0
    yarn_original: int = 4096       # `original_max_position_embeddings`
    yarn_beta_fast: float = 64.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: Optional[float] = None
    rms_eps: float = 1e-6
    bias_update_speed: float = 0.001
    query_block: int = 256
    head_block: int = 2048          # rows of logits alive at once


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def frequencies(kind, sizes: Sizes):
    """-> (R, the first dims of a head that turn; (R / 2,) the angle a
    position turns pair i by; the factor c on cos and sin) of a kind of
    layer."""
    if kind == SLIDING:
        r = sizes.rotary_sliding
        return r, 1.0 / sizes.theta_sliding ** (
            jnp.arange(0, r, 2, dtype=jnp.float32) / r), 1.0
    r, theta = sizes.rotary_full, sizes.theta_full
    inv_freq = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)

    def dim_of(turns):
        return r * jnp.log(sizes.yarn_original / (turns * 2 * jnp.pi)) \
            / (2 * jnp.log(theta))

    low = jnp.maximum(jnp.floor(dim_of(sizes.yarn_beta_fast)), 0)
    high = jnp.minimum(jnp.ceil(dim_of(sizes.yarn_beta_slow)), r - 1)
    high = jnp.where(high == low, high + 0.001, high)
    ramp = jnp.clip((jnp.arange(r // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    scaled = inv_freq / sizes.yarn_factor * ramp + inv_freq * (1 - ramp)
    c = sizes.yarn_attention_factor
    if c is None:
        c = 0.1 * jnp.log(sizes.yarn_factor) + 1.0
    return r, scaled, c


def rope_first_dims(x, positions, r, inv_freq, c):
    """x (rows, heads, d), positions (rows,): position m turns the pair
    (x_i, x_{i+r/2}), i < r / 2, by the angle m * inv_freq_i, cos and sin
    times c; the dims from r on pass as they are."""
    turned, passed = x[..., :r], x[..., r:]
    angle = (positions.astype(jnp.float32)[:, None]
             * inv_freq[None])[:, None, :]                  # (rows, 1, r/2)
    cos, sin = jnp.cos(angle) * c, jnp.sin(angle) * c
    first, second = turned[..., :r // 2], turned[..., r // 2:]
    return jnp.concatenate(
        [first * cos - second * sin, first * sin + second * cos, passed],
        axis=-1)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def attended(query_rows, seq, kind, window):
    """(len(query_rows), seq) bool: which keys each query row attends, by
    the layer's kind."""
    q, k = query_rows[:, None], jnp.arange(seq)[None]
    if kind == FULL:
        return k <= q
    return (k <= q) & (k > q - window)


def attention(x, p, kind, sizes: Sizes):
    """x (seq, E), the layer's normed input -> the operator's result
    (seq, E)."""
    rows = x.shape[0]
    h_kv, d = sizes.n_kv_head, sizes.head_dim
    h = p["wq"].shape[1] // d                       # this layer's heads
    group = h // h_kv
    positions = jnp.arange(rows)
    q = (x @ p["wq"]).reshape(rows, h, d)
    k = (x @ p["wk"]).reshape(rows, h_kv, d)
    v = (x @ p["wv"]).reshape(rows, h_kv, d)
    table = frequencies(kind, sizes)
    q = rope_first_dims(q, positions, *table)
    k = rope_first_dims(k, positions, *table)
    # query head i reads key/value head i // group
    q = q.reshape(rows, h_kv, group, d).transpose(1, 2, 0, 3)
    k, v = (t.transpose(1, 0, 2) for t in (k, v))           # (h_kv, rows, d)
    block = min(sizes.query_block, rows)
    assert rows % block == 0, (rows, block)

    @jax.checkpoint
    def some(start):
        seen = attended(start + jnp.arange(block), rows, kind, sizes.window)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
        scores = jnp.einsum("kgqd,ksd->kgqs", qb, k) / jnp.sqrt(
            jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,ksd->kgqd", probs, v)   # (h_kv, g, block, d)

    out = jax.lax.map(some, jnp.arange(0, rows, block))
    out = out.transpose(0, 3, 1, 2, 4).reshape(rows, h, d)
    gate = jax.nn.sigmoid(x @ p["wg"])                      # (rows, h)
    return (out * gate[:, :, None]).reshape(rows, h * d) @ p["wo"]


def route(x, p, bias, sizes: Sizes):
    """x (rows, E) -> ((rows, N): each row's weight for every expert, zero
    where the expert is not among its top k of s + bias; (rows, N) 1 where
    it is)."""
    s = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(s + bias, sizes.top_k)
    chosen = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1]), axis=1)
    picked = s * chosen
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return weights * sizes.routed_scale, chosen


def moe(x, p, bias, sizes: Sizes):
    """x (rows, E) -> (y, rows sent to each of all the experts)."""
    weights, chosen = route(x, p, bias, sizes)
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
    y = y + swiglu(x, p["s_gate"], p["s_up"], p["s_down"])
    return y, jnp.sum(chosen, axis=0)


def layer(x, p, bias, kind, sizes: Sizes):
    """One layer on one sequence's rows -> (y, rows sent to each expert;
    None from a dense layer)."""
    h = x + attention(rms_norm(x, p["norm1"], sizes.rms_eps), p, kind, sizes)
    u = rms_norm(h, p["norm2"], sizes.rms_eps)
    if "router" not in p:
        return h + swiglu(u, p["gate"], p["up"], p["down"]), None
    y, rows = moe(u, p, bias, sizes)
    return h + y, rows


def trunk(params, biases, tokens, sizes: Sizes):
    """tokens (seq,) -> (the rows after the final norm (seq, E), the rows
    sent to every expert in every sparse layer (sparse layers, N))."""
    x = params["embed"][tokens]
    sent = []
    for p, bias, kind in zip(params["groups"], biases, sizes.groups):
        one = jax.checkpoint(
            lambda x, pb, kind=kind: layer(x, *pb, kind, sizes))
        x, rows = jax.lax.scan(one, x, (p, bias))
        if rows is not None:
            sent.append(rows)
    return rms_norm(x, params["norm_f"], sizes.rms_eps), \
        jnp.concatenate(sent)


def row_losses(params, biases, tokens, sizes: Sizes):
    """tokens (batch, seq + 1) -> (every row's cross-entropy against the
    next token (batch, seq), the rows sent to every expert by the whole
    batch (sparse layers, N))."""

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
        x, rows = trunk(params, biases, row[:-1], sizes)
        return xent(x, row[1:]), rows

    ce, rows = jax.lax.map(sequence, tokens)
    return ce, jnp.sum(rows, axis=0)


def losses(params, biases, tokens, sizes: Sizes):
    """tokens (batch, seq + 1) -> (L, (rows sent to every expert by the
    whole batch (sparse layers, N), the rows' cross-entropies
    (batch, seq))); a sequence at a time."""
    ce, rows = row_losses(params, biases, tokens, sizes)
    return jnp.mean(ce), (rows, ce)


def update_biases(biases, rows, sizes: Sizes):
    """The bias rule over every sparse layer; ``rows`` (sparse layers, N)
    in the layers' order, ``biases`` a group's (layers, N), None for a
    dense group."""
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
    opt_state, L, rows): one AdamW step on the cross-entropy's gradient,
    then the bias rule; loss and rows as before the step."""

    def step(params, biases, opt_state, tokens):
        (loss, (rows, _)), grads = jax.value_and_grad(
            losses, has_aux=True)(
                params, jax.lax.stop_gradient(biases), tokens, sizes)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates),
                update_biases(biases, rows, sizes), opt_state, loss, rows)

    return step


def first_losses(params, biases, batches, sizes: Sizes, optimizer_settings):
    """The cross-entropies of the first len(batches) steps from `params`
    and `biases`, one call of the jitted step a batch: the state is donated
    from call to call, so one copy of it lives.  `batches` is (steps,
    batch, seq + 1)."""
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

"""The plain reference for OLMoE: what `correct` is judged against.

The published model (Muennighoff et al., "OLMoE: Open Mixture-of-Experts
Language Models", arXiv:2409.02060; the layer equations of
`allenai/OLMoE-1B-7B-0125`'s `modeling_olmoe.py`):

    h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h))
    Attn: q, k, v = xWq, xWk, xWv (no bias); RMSNorm over the whole q and
      the whole k projection; split into heads; rotate-half RoPE on every
      dimension of a head; causal softmax at head_dim^-1/2; Wo.
    MoE: p = softmax(xWr) over all experts; the top k of p, with those
      values of p as weights, not renormalised;
      sum over them of p_e * Wdown_e(silu(Wgate_e x) * Wup_e x).
    final RMSNorm, an untied head, mean next-token cross-entropy.
    objective = cross-entropy + aux_weight * load balancing
                + z_weight * router z-loss (the paper's 0.01 and 0.001).

Everything is `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, no sort, no grouped
matmul, nothing of `ray_tpu`.  Attention is a masked softmax over the
scores of a block of queries against every key; the experts are a loop
over ALL of them, each applied to every token, with a token's weight zero
where it did not choose the expert.

Departures from the published description:

- Load balancing is n_experts * sum_e f_e P_e with f_e expert e's share of
  the T*k assignments (it sums to 1) and P_e its mean router probability,
  as the paper writes it; `modeling_olmoe.py`'s `load_balancing_loss_func`
  sums over the k choices without dividing, k times this.  The routers'
  losses are averaged over the layers (that function pools the layers'
  tokens; with one layer they are the same).
- Summation order only: queries are taken `query_block` at a time, the
  experts are `lax.scan`ned with each one's body `jax.checkpoint`ed (so 64
  experts' activations for every token are never alive together), each
  layer is `jax.checkpoint`ed, and the head and its cross-entropy are
  taken `micro` sequences at a time.  The routers' statistics are over
  the whole batch, as the system's: they are not sums over sequences.

Parameters, a flat dict: "embed" (V, E), "head" (E, V), "norm_f" (E,),
"layers": a list of {"norm1", "norm2", "q_norm", "k_norm" (E,); "wq", "wk",
"wv", "wo" (E, E); "router" (E, N); "gate", "up" (N, E, W); "down"
(N, W, E)}.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax


class Sizes(NamedTuple):
    n_head: int
    top_k: int
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    aux_weight: float = 0.01
    z_weight: float = 0.001
    query_block: int = 512


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def rope(x, theta):
    """x (batch, seq, heads, d): position m rotates the pair (x_i,
    x_{i + d/2}) by the angle m * theta^(-2i/d) (the rotate-half
    pairing)."""
    s, d = x.shape[1], x.shape[3]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rotated * sin


def attention(x, p, sizes: Sizes):
    b, s, e = x.shape
    d = e // sizes.n_head
    heads = lambda t: t.reshape(b, s, sizes.n_head, d)
    q = rope(heads(rms_norm(x @ p["wq"], p["q_norm"], sizes.rms_eps)),
             sizes.rope_theta).transpose(0, 2, 1, 3)
    k = rope(heads(rms_norm(x @ p["wk"], p["k_norm"], sizes.rms_eps)),
             sizes.rope_theta).transpose(0, 2, 1, 3)
    v = heads(x @ p["wv"]).transpose(0, 2, 1, 3)
    block = min(sizes.query_block, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
        scores = qb @ k.transpose(0, 1, 3, 2) / jnp.sqrt(jnp.float32(d))
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(s)[None]
        scores = jnp.where(seen, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v      # (b, h, block, d)

    out = jax.lax.map(rows, jnp.arange(0, s, block))    # (blocks, b, h, ., d)
    out = out.transpose(1, 0, 3, 2, 4).reshape(b, s, e)
    return out @ p["wo"]


def route(x, p, sizes: Sizes):
    """x (tokens, E) -> (router logits, probabilities, for every token
    and expert 1 where the expert is among the token's top k, else 0)."""
    logits = x @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(probs, sizes.top_k)
    return logits, probs, jnp.sum(
        jax.nn.one_hot(chosen, probs.shape[-1]), axis=1)


def moe(x, p, sizes: Sizes):
    """x (tokens, E) -> (y, load-balancing loss, z-loss, rows of the
    fullest expert)."""
    logits, probs, chosen = route(x, p, sizes)
    n_experts = probs.shape[-1]
    weights = probs * chosen       # not renormalised

    @jax.checkpoint
    def expert(x, gate, up, down, w):
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down * w[:, None]

    def add(total, e):
        gate, up, down, w = e
        return total + expert(x, gate, up, down, w), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(x),
                        (p["gate"], p["up"], p["down"], weights.T))
    # f is a count: no gradient passes through it
    rows = jnp.sum(chosen, axis=0)
    share = rows / (x.shape[0] * sizes.top_k)
    balance = n_experts * jnp.sum(share * jnp.mean(probs, axis=0))
    z = jnp.mean(jnp.square(jax.scipy.special.logsumexp(logits, axis=-1)))
    return y, balance, z, jnp.max(rows)


def trunk(params, inputs, sizes: Sizes):
    """inputs (batch, seq) -> (x after the final norm, load-balancing
    loss and z-loss averaged over the layers, rows of the fullest
    expert)."""
    b, s = inputs.shape
    x = params["embed"][inputs]

    @jax.checkpoint
    def layer(x, p):
        h = x + attention(rms_norm(x, p["norm1"], sizes.rms_eps), p, sizes)
        y, balance, z, fullest = moe(
            rms_norm(h, p["norm2"], sizes.rms_eps).reshape(b * s, -1), p,
            sizes)
        return h + y.reshape(h.shape), balance, z, fullest

    balances, zs, fullest = [], [], []
    for p in params["layers"]:
        x, balance, z, most = layer(x, p)
        balances.append(balance)
        zs.append(z)
        fullest.append(most)
    n = len(params["layers"])
    return (rms_norm(x, params["norm_f"], sizes.rms_eps), sum(balances) / n,
            sum(zs) / n, jnp.max(jnp.stack(fullest)))


def logits(params, inputs, sizes: Sizes):
    return trunk(params, inputs, sizes)[0] @ params["head"]


def losses(params, tokens, sizes: Sizes, micro=None):
    """tokens (batch, seq + 1) -> (objective, {"loss": cross-entropy,
    "aux_loss", "z_loss", "max_expert_rows"}); the head is applied to
    `micro` sequences at a time (all at once if None)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x, balance, z, fullest = trunk(params, inputs, sizes)
    b, s, e = x.shape
    micro = micro or b

    @jax.checkpoint
    def summed_xent(xt):
        x, t = xt
        logp = jax.nn.log_softmax(x @ params["head"], axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, t[..., None], axis=-1))

    xent = jnp.sum(jax.lax.map(summed_xent, (
        x.reshape(b // micro, micro, s, e),
        targets.reshape(b // micro, micro, s)))) / (b * s)
    objective = xent + sizes.aux_weight * balance + sizes.z_weight * z
    return objective, {"loss": xent, "aux_loss": balance, "z_loss": z,
                       "max_expert_rows": fullest}


def adamw(settings):
    """The configuration's optimizer settings, as `optax.adamw` takes
    them."""
    return optax.adamw(settings["learning_rate"], b1=settings["b1"],
                       b2=settings["b2"], eps=settings["eps"],
                       weight_decay=settings["weight_decay"])


def make_train_step(sizes: Sizes, optimizer, micro=None):
    """step(params, opt_state, tokens) -> (params, opt_state, parts): one
    AdamW step on the objective's gradient; `parts` as `losses` gives
    them, before the step."""

    def step(params, opt_state, tokens):
        (_, parts), grads = jax.value_and_grad(losses, has_aux=True)(
            params, tokens, sizes, micro)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, parts

    return step


def losses_program(sizes: Sizes, optimizer_settings, micro=None):
    """run(params, batches) -> the cross-entropies of the first
    len(batches) steps from `params`, as one program: the optimizer's
    state is born inside it and freed with it.  `batches` is (steps,
    batch, seq + 1)."""
    optimizer = adamw(optimizer_settings)
    step = make_train_step(sizes, optimizer, micro)

    def run(params, batches):
        # unrolled, not scanned: a scan would hold a second copy of the
        # parameters and moments it carries
        opt_state = optimizer.init(params)
        out = []
        for tokens in batches:
            params, opt_state, parts = step(params, opt_state, tokens)
            out.append(parts["loss"])
        return jnp.stack(out)

    return run

"""The plain reference for GPT-2: what `correct` is judged against.

The published model (Radford et al. 2019; the layer equations of
`openai-community/gpt2*` on Hugging Face): token plus learned position
embeddings, pre-LayerNorm blocks of causal softmax attention and a
tanh-GELU MLP four times as wide, a final LayerNorm, the output head tied
to the token embedding, mean cross-entropy of the next token.  Everything
is `jax.numpy` in float32 under `default_matmul_precision("highest")`: no
Pallas, no `shard_map`, nothing of `ray_tpu`.  `jax.grad` of that loss and
`optax.adamw` make the training step.

Departures from a textbook loop, none of which changes the arithmetic
beyond summation order: the blocks are stacked and `lax.scan`ned so that
48 layers compile as one; each block is `jax.checkpoint`ed so that a whole
XL sequence's attention matrices are never all alive; a batch is taken a
few sequences at a time and the gradients are summed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import optax

LN_EPS = 1e-5


def layer_norm(x, gain, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * gain + bias


def gelu(x):
    """GPT-2's `gelu_new`: the tanh approximation."""
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, n_head):
    """One pre-LN block on x of shape (batch, seq, width)."""
    b, s, e = x.shape
    d = e // n_head
    h = layer_norm(x, p["ln1_g"], p["ln1_b"])
    qkv = h @ p["attn_w"] + p["attn_b"]
    q, k, v = (t.reshape(b, s, n_head, d).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1) @ v
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, e)
    x = x + attn @ p["proj_w"] + p["proj_b"]
    h = layer_norm(x, p["ln2_g"], p["ln2_b"])
    return x + gelu(h @ p["fc_w"] + p["fc_b"]) @ p["out_w"] + p["out_b"]


def loss(params, tokens, n_head):
    """Mean next-token cross-entropy of tokens (batch, seq + 1)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    s = inputs.shape[1]
    x = params["wte"][inputs] + params["wpe"][:s]

    def layer(x, p):
        return jax.checkpoint(lambda x, p: block(x, p, n_head))(x, p), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = layer_norm(x, params["lnf_g"], params["lnf_b"])
    logits = x @ params["wte"].T
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(picked)


def make_train_step(n_head, optimizer, micro):
    """step(params, opt_state, tokens) -> (params, opt_state, loss), the
    batch taken `micro` sequences at a time."""

    def step(params, opt_state, tokens):
        n_micro = tokens.shape[0] // micro
        chunks = tokens.reshape(n_micro, micro, tokens.shape[1])

        def accumulate(carry, chunk):
            total, grads = carry
            value, g = jax.value_and_grad(loss)(params, chunk, n_head)
            return (total + value, jax.tree.map(jnp.add, grads, g)), None

        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like,
                                                         params))
        (total, grads), _ = jax.lax.scan(accumulate, zero, chunks)
        grads = jax.tree.map(lambda g: g / n_micro, grads)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, \
            total / n_micro

    return step


def adamw(settings):
    """The configuration's optimizer settings, as `optax.adamw` takes
    them."""
    return optax.adamw(settings["learning_rate"], b1=settings["b1"],
                       b2=settings["b2"], eps=settings["eps"],
                       weight_decay=settings["weight_decay"])


def losses_program(n_head, optimizer_settings, micro):
    """run(params, batches) -> the losses of the first len(batches) steps
    from `params`, as one program: the optimizer's state is born inside it
    and freed with it.  `batches` is (steps, batch, seq + 1)."""
    optimizer = adamw(optimizer_settings)
    step = make_train_step(n_head, optimizer, micro)

    def run(params, batches):
        # the steps are unrolled, not scanned: a scan would hold a second
        # copy of the parameters and moments it carries
        opt_state = optimizer.init(params)
        losses = []
        for tokens in batches:
            params, opt_state, value = step(params, opt_state, tokens)
            losses.append(value)
        return jnp.stack(losses)

    return run

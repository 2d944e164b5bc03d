"""What the Train-path models share: the norms, rotary positions, the way
into and out of an attention operator (`attention_qkv`: the three
projections to heads, an RMSNorm a head where the parameters have a gain for
one, RoPE where the caller gives positions, over the whole head or its first
dims; `attention_out`: a gate a head where the caller gives its input, W_o), the
feed-forwards (gated and not), the routed layer over them, which hands on
what its route gives past the two it needs (`ops/moe.py` has the routes: the
sigmoid one with a routing bias, the softmax one with its balance loss), the
gated short convolution, the causal convolution of a state-space mixer, the
walk over a decoder's layers (once, or, for a looped model, several times
over the same parameters with the final norm after every walk), the head and
its chunked loss (one rule, `chunked_xent`: the rows weighted or not, their
losses handed back beside the sum, the gradient formed in the same walk that
makes the logits), the mixed-precision step, and the two things every
`init_params` draws (`normal_kernel`, `unit_scale`).  A model file imports
these, `ray_tpu.parallel.attention` and `ray_tpu.ops`; it imports no other
model file: it is its configuration, its table of parameters
(`init_params`), its mixers (an attention's is the kernels' call between
`attention_qkv` and `attention_out`, under the model's own name `attention`)
and a `_layer` that says which mixer and which feed-forward a layer has.

Imports jax, the names of the flash kernels' residuals and forms
(`ops/flash_attention.py`, which every model imports through
`parallel/attention.py` anyway) and, of the runtime, only the job
timeline's counters (`util/tracing.py`, which imports nothing heavy): a
worker pays nothing for it before its first step.

The scopes.  A scope is the device's span: a `jax.named_scope` puts its
name into the `op_name` of every operation traced under it, the chip's
profiler carries that into the trace (`tf_op`), and a reader
(`benchmark/harness/scope_trace.py`) sums device time by it.  `SCOPES` is
every path a Train-path model may use, and the one place a reader or a test
takes them from.  A model writes plain `with jax.named_scope("attention"):`
around the part; what the models share names itself (`layer_norm` and
`rms_norm`: `norm`; `attention_qkv`'s `qkv` and `attention_out`'s `gate`
and `out`, which rely on the caller standing in `attention` (the `kernel`
between them is the model's); `short_conv`'s three parts; `trunk`'s `embed`;
`routed_layer`'s `route` and `shared` and `ops/moe.py`'s `dispatch`,
`experts`, `combine`, `balance_loss`'s `route` and `routing_bias_update`,
which rely on the caller standing in `ffn/moe`; `head_and_loss`, also around
`head_and_weighted_loss`; the flash kernels' forms; a looped model's
`exit_gate` (the gates, the exit distribution and its entropy: the model
writes it; the weighting of the rows' losses is the head's, under
`head_and_loss`); a block-diffusion model's `diffusion` (the draw of the
step's noise, the masking of the tokens, the two kinds of row laid side by
side and taken apart, the rows' weights: everything that objective adds
outside the kernels, the trunk's products and the head);
`train_step`'s `optimizer_update`).  `norm` is a layer's
norm on the residual stream: one inside an operator (a norm over a head, the
latent's) stands in that operator's scope and counts there.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import hashlib
import itertools
import json
import logging
import math
import os
import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import causal_conv as conv_kernels
from ray_tpu.ops import grouped_matmul
from ray_tpu.ops.eva import SUMMARY_NAME
from ray_tpu.ops.flash_attention import KEPT_RESIDUALS, KERNEL_FORMS
from ray_tpu.ops.moe import ROUTE_NAME, moe_dispatch
from ray_tpu.parallel.attention import attention
from ray_tpu.parallel.context import get_mesh
from ray_tpu.parallel.sharding import (chip_bytes, logical_spec,
                                       param_logical_dims)
from ray_tpu.util import tracing
from ray_tpu.util.compile_cache import compile_cache_dir

logger = logging.getLogger(__name__)

SCOPES = (
    "embed",
    "norm",
    "attention",
    "attention/qkv",
    "attention/latent_down",
    "attention/latent_up",
    "attention/indexer",
    "attention/indexer/proj",
    "attention/indexer/scores",
    "attention/indexer/select",
    "attention/indexer/loss",
    "attention/kernel",
    *(f"attention/kernel/{form}" for form in KERNEL_FORMS),
    "attention/gate",
    "attention/out",
    "attention/cross",
    "attention/diff",
    "short_conv",
    "short_conv/in_proj",
    "short_conv/gate_taps",
    "short_conv/out_proj",
    "ssm",
    "ssm/in_proj",
    "ssm/conv",
    "ssm/scan",
    "ssm/gate_norm",
    "ssm/out_proj",
    "mamba",
    "mamba/in_proj",
    "mamba/conv",
    "mamba/x_proj",
    "mamba/scan",
    "mamba/out_proj",
    "gmu",
    "kda",
    "kda/proj",
    "kda/conv",
    "kda/gate",
    "kda/rule",
    "kda/gate_norm",
    "kda/out_proj",
    "eva",
    "eva/qkv",
    "eva/summary",
    "eva/local",
    # the flash kernels of the local half, head-major under a rule of
    # aligned windows (`ops/flash_attention.py:_form`)
    "eva/local/fwd_rows_blocks",
    "eva/local/bwd_fused_blocks",
    "eva/remote",
    "eva/merge",
    "eva/out",
    "ffn",
    "ffn/dense",
    "ffn/moe",
    "ffn/moe/route",
    "ffn/moe/dispatch",
    "ffn/moe/experts",
    "ffn/moe/combine",
    "ffn/moe/shared",
    "head_and_loss",
    "exit_gate",
    "diffusion",
    "optimizer_update",
    "routing_bias_update",
)

# What the compiler names itself: XLA:TPU replaces `jax.lax.ragged_dot`, the
# form a shape that `ops/grouped_matmul.py`'s kernels decline takes, by its
# own grouped-matmul kernel, whose `op_name` is the compiler's
# (`ragged-dot-none`, and `ragged-dot-metadata` for the kernel that lays out
# the groups) and no longer the caller's (the repo's kernels carry the
# caller's).  Every `ragged_dot` of the Train-path models is the routed
# experts' (`tests/test_scopes.py` holds them to it), so a reader puts an
# operation whose name starts so under that scope; whether it ran forward or
# backward the name does not say.
COMPILER_NAMED = (("ragged-dot", "ffn/moe/experts"),)


def layer_norm(x, p, eps=1e-5):
    """Stats in f32 for stability; output CAST BACK to the input dtype —
    the f32 scale/bias would otherwise silently promote the residual
    stream (and every downstream matmul) to the MXU's slow f32 path."""
    with jax.named_scope("norm"):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        return (y * p["scale"] + p["bias"]).astype(x.dtype)


def rms_norm(x, p, eps=1e-5, unit_offset=False):
    """``unit_offset``: the gain is 1 + w, w the parameter, which starts at
    0 (a release's `norm_add_unit_offset`); False: the gain is w and the
    program it always was."""
    with jax.named_scope("norm"):
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        gain = 1.0 + p["scale"] if unit_offset else p["scale"]
        return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) \
            * gain.astype(x.dtype)


def rope(x, positions, theta, interleaved=False, scale=None):
    """x: (B, S, H, D); positions: (B, S) or (S,).  Dim i turns with dim
    i + D/2 by frequency i (rotate-half).  A caller that rotates a part of
    a head passes that part; a key part all heads share comes with H = 1.

    ``theta``: the base, frequency i being theta^(-2i/D); or the D/2
    frequencies themselves, which a caller computed (`yarn_frequencies`),
    with ``scale`` the factor cos and sin are multiplied by (so q . k
    carries its square).  Such a call is counted on the job timeline as
    the step is traced (`rope.scaled`).

    ``interleaved``: the pairs that turn together are the adjacent (2i,
    2i+1).  They are taken apart first ([evens | odds]) and rotated as
    halves, so the result is the pairwise rotation in that order of the
    dims and not in x's: the same permutation on queries and keys, which
    leaves every q . k as it was."""
    D = x.shape[-1]
    if interleaved:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    if isinstance(theta, (int, float)):
        freqs = theta ** (-jnp.arange(0, D // 2, dtype=jnp.float32) / (D // 2))
    else:
        tracing.count("rope.scaled")
        freqs = jnp.asarray(theta, jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)

    def table(turn):
        t = turn(angles) if scale is None else turn(angles) * scale
        return t[..., None, :].astype(x.dtype)

    cos, sin = table(jnp.cos), table(jnp.sin)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def yarn_frequencies(dim, theta, factor, original_max_position, beta_fast=32,
                     beta_slow=1, attention_factor=None):
    """YaRN's rotary table (Peng et al., arXiv:2309.00071) as `rope` takes
    it -> ((dim / 2,) float32 frequencies, the scale of cos and sin): a
    pure function of a config's keys, numpy at the time the step is traced.

    b_i = theta^(2i/dim).  A dim that turns ``n`` times over the
    ``original_max_position`` positions the model was trained at sits at
    d(n) = dim ln(original_max_position / (2 pi n)) / (2 ln theta); low =
    floor(d(beta_fast)), high = ceil(d(beta_slow)), both within
    [0, dim - 1]; r_i = clip((i - low) / (high - low), 0, 1).  Frequency i
    is (1 - r_i) / b_i + r_i / (factor b_i): the fast dims as they were,
    the slow ones stretched ``factor`` times, a ramp between.  The scale is
    ``attention_factor``, 0.1 ln(factor) + 1 where the config gives none."""
    half = dim // 2
    base = theta ** (np.arange(half, dtype=np.float64) / half)

    def turns(n):
        return dim * math.log(original_max_position / (2 * math.pi * n)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 0.001), 0, 1)
    freqs = (1 - ramp) / base + ramp / (factor * base)
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return freqs.astype(np.float32), float(attention_factor)


def attention_qkv(x, p, head_dim, eps=None, positions=None, theta=None,
                  scale=None, rotary_dim=None):
    """The way into an attention operator, x (B, S, E) -> q (B, S, H, D), k
    and v (B, S, H_kv, D) with D = ``head_dim``, under `qkv`; the caller
    stands in `attention`, runs its kernels on the three under `kernel` and
    hands their result to `attention_out`.  q, k, v = x W_q, x W_k, x W_v
    (``p``'s "q_proj", "k_proj" and "v_proj", each {"kernel": ...}, no
    bias; a kernel's width over D is its heads), marked `attention/qkv`;
    where ``p`` has "q_norm" and "k_norm", q and k through an RMSNorm over
    each head's D at ``eps`` with that gain; where ``positions`` is given,
    q and k through `rope` at ``positions(S)``, a function of the rows'
    number (`jnp.arange`: row t stands at t) that is traced here, behind
    the products, with ``theta`` and ``scale`` as `rope` takes them.
    ``rotary_dim``: the FIRST dims of each head that turn (``theta``'s
    frequencies are then of that width), the rest passing as they are
    (`partial_rotary_factor`); None, or D: the whole head.  A call that
    rotates a part is counted on the job timeline as the step is traced
    (`rope.partial`)."""
    B, S, _ = x.shape
    part = rotary_dim is not None and rotary_dim != head_dim
    if part and positions is not None:
        tracing.count("rope.partial")
    with jax.named_scope("qkv"):
        # the products, before the norms: a norm's backward reads them
        q, k, v = named(tuple(
            (x @ p[name]["kernel"].astype(x.dtype)).reshape(
                B, S, -1, head_dim)
            for name in ("q_proj", "k_proj", "v_proj")), "attention/qkv")
        at = None if positions is None else positions(S)

        def turned(heads, norm):
            if norm in p:
                heads = rms_norm(heads, p[norm], eps)
            if at is None:
                return heads
            if not part:
                return rope(heads, at, theta, scale=scale)
            return jnp.concatenate(
                [rope(heads[..., :rotary_dim], at, theta, scale=scale),
                 heads[..., rotary_dim:]], axis=-1)
        return turned(q, "q_norm"), turned(k, "k_norm"), v


def attention_out(o, p, gate_input=None):
    """The way out of one: the kernels' result o (B, S, H, D) -> o W_o
    (B, S, E), ``p``'s "o_proj", under `out`, marked `attention/out`.
    ``gate_input``: u (B, S, E), the operator's own input; each head's
    result is first multiplied by g = sigmoid(u W_g) in float32, one scalar
    a head and token, W_g ``p``'s "g_proj" (E, H), no bias (the head-wise
    gate at the kernels' output of Qiu et al., arXiv:2505.06708), under
    `gate`, the product marked `attention/gate`; such a call is counted on
    the job timeline as the step is traced (`attention.gated`).  None: no
    gate, and the program it always was."""
    B, S = o.shape[:2]
    if gate_input is not None:
        tracing.count("attention.gated")
        with jax.named_scope("gate"):
            # the product and not its sigmoid, whose backward reads its own
            # result (as a router's, `ops/moe.py:sigmoid_route`)
            g = jax.nn.sigmoid(named(jnp.matmul(
                gate_input, p["g_proj"]["kernel"].astype(gate_input.dtype),
                preferred_element_type=jnp.float32), "attention/gate"))
            o = (o.astype(jnp.float32) * g[..., None]).astype(o.dtype)
    with jax.named_scope("out"):
        return named(o.reshape(B, S, -1)
                     @ p["o_proj"]["kernel"].astype(o.dtype), "attention/out")


def latent_attention(x, p, cfg, gated=False):
    """DeepSeek-V3's latent attention (MLA) as training multiplies it out,
    x (B, S, E) the normed stream -> (B, S, E); the caller stands in
    `attention`.  q = x W_q, a head's [q_nope | q_rope];  [c | k_r] =
    x W_kv_a;  c = RMSNorm(c);  a head's [k_nope | v] = c W_kv_b;  RoPE
    (interleaved pairs, `rope`) on q_rope of every head and on the ONE k_r
    all heads share;  k = [k_nope | k_r];  the flash kernels' causal softmax
    of q k' at (nope + rope)^-1/2 with v of another width;  W_o
    (`attention_out`; ``gated``: behind a gate a head from x, ``p``'s
    "g_proj").  ``p``: "q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj",
    "o_proj"; ``cfg``: `n_head` (the heads HELD here, which the matrices'
    widths agree with), `kv_lora_rank`, `qk_nope_dim`, `qk_rope_dim`,
    `v_head_dim`, `rope_theta`, `rms_eps`."""
    B, S, _ = x.shape
    H, R, nope = cfg.n_head, cfg.kv_lora_rank, cfg.qk_nope_dim
    kernel = lambda name: p[name]["kernel"].astype(x.dtype)
    positions = jnp.arange(S)
    turn = functools.partial(rope, positions=positions,
                             theta=cfg.rope_theta, interleaved=True)
    with jax.named_scope("latent_down"):
        q = (x @ kernel("q_proj")).reshape(B, S, H, nope + cfg.qk_rope_dim)
        latent = named(x @ kernel("kv_a_proj"), "attention/latent_down")
        c = rms_norm(latent[..., :R], p["kv_a_norm"], cfg.rms_eps)
        k_rope = turn(latent[..., None, R:])            # (B, S, 1, rope)
    with jax.named_scope("latent_up"):
        kv = (c @ kernel("kv_b_proj")).reshape(
            B, S, H, nope + cfg.v_head_dim)
        q = named(jnp.concatenate(
            [q[..., :nope], turn(q[..., nope:])], axis=-1), "attention/qkv")
        k, v = named((jnp.concatenate([
            kv[..., :nope],
            jnp.broadcast_to(k_rope, (B, S, H, cfg.qk_rope_dim))], axis=-1),
            kv[..., nope:]), "attention/latent_up")
    with jax.named_scope("kernel"):
        o = attention(q, k, v)                          # (B, S, H, v_head_dim)
    return attention_out(o, p, gate_input=x if gated else None)


def swiglu(x, gate, up, down, matmul=jnp.matmul):
    """down(silu(gate(x)) * up(x)), no bias: the gated feed-forward of a
    dense layer, a shared expert (``matmul`` a plain product) and routed
    experts (a grouped one over rows sorted by expert, the weights one
    stack an expert).  The gate's and up's results are marked for a
    recomputed layer (`KEPT_NAMES`)."""
    gate, up = named((matmul(x, gate), matmul(x, up)), "ffn/hidden")
    return matmul(jax.nn.silu(gate) * up, down)


def relu2(x, up, down, matmul=jnp.matmul):
    """down(relu(up(x))^2): a feed-forward that is not gated, two matrices
    (Nemotron-H's experts, routed and shared); ``matmul`` as `swiglu`'s.
    The up's result is marked for a recomputed layer."""
    hidden = named(matmul(x, up), "ffn/hidden")
    return matmul(jnp.square(jax.nn.relu(hidden)), down)


def dense_ffn(x, p, ffn):
    """``ffn`` (`swiglu` or `relu2`) over plain products with ``p``'s
    matrices in the compute type: {"gate_proj" where ``ffn`` is gated,
    "up_proj", "down_proj"}, each {"kernel": ...}: a dense layer's
    feed-forward, a shared expert."""
    return ffn(x, *(p[name]["kernel"].astype(x.dtype) for name in
                    ("gate_proj", "up_proj", "down_proj") if name in p))


def _widened(w, axis):
    """A stack of expert matrices with zeros along ``axis``, the experts'
    hidden width, up to whole lane tiles, which is all the grouped kernels
    ask of a width (`ops/grouped_matmul.py`; XLA:TPU's own kernel wanted
    whole 256s and ran 1,856 and 1,920 alike at under half its speed at
    2,048, PERF.md §6, PR 38): silu(0) * 0 and relu(0)^2 are 0, times rows
    of zeros they add nothing, and no gradient comes back to the zeros.  A
    width of whole lane tiles is left as it is."""
    extra = -w.shape[axis] % grouped_matmul.LANE
    if not extra:
        return w
    pad = [(0, 0)] * w.ndim
    pad[axis] = (0, extra)
    return jnp.pad(w, pad)


def grouped_ffn(p, ffn):
    """-> `run_experts(rows, group_sizes)` for `ops/moe.py:moe_dispatch`:
    ``ffn`` (`swiglu` or `relu2`) over rows sorted by expert, every product
    a grouped matmul (`ops/grouped_matmul.py`, one walk of the rows for
    all of them) with one of ``p``'s stacks, an expert a matrix: "wi_gate"
    where the experts are gated, "wi_up" (n, E, W) and "wo" (n, W, E),
    each widened to whole lane tiles (`_widened`) once, outside the
    run."""
    stacks = [_widened(p[name], axis) for name, axis in
              (("wi_gate", 2), ("wi_up", 2), ("wo", 1)) if name in p]

    def run(xs, group_sizes):
        return ffn(xs, *stacks, matmul=grouped_matmul.over(
            group_sizes, xs.shape[0]))
    return run


def routed_layer(x, p, route, n_experts, held, ffn):
    """One routed feed-forward, x (B, S, E) -> (y (B, S, E), the rows this
    chip's tokens sent to each of ALL the experts (n_experts,) int32, and
    whatever ``route`` gives past its two); the caller stands in the scope
    `ffn/moe`.  ``route(xt (T, E), p["router"]) -> (weights (T, k) f32,
    experts (T, k) int32, ...)`` is the model's router, run under `route`:
    `ops/moe.py:sigmoid_route` gives the two, `softmax_route` its mean for
    the balance loss besides; the experts are ``ffn`` over ``p``'s stacks
    (`grouped_ffn`), dropless over the ``held`` = (first, count) of them
    that live here, None: all (`ops/moe.py:moe_dispatch`); a shared expert
    is there if ``p`` has "shared", the same ``ffn`` over every token
    (`dense_ffn`) under `shared`."""
    B, S, E = x.shape
    xt = x.reshape(B * S, E)
    with jax.named_scope("route"):
        weights, experts, *more = route(xt, p["router"])
    y, rows = moe_dispatch(xt, weights, experts, n_experts,
                           grouped_ffn(p, ffn), held=held)
    if "shared" in p:
        with jax.named_scope("shared"):
            y = y + dense_ffn(xt, p["shared"], ffn)
    return (y.reshape(B, S, E), rows, *more)


def _back(x, k):
    """x (B, S, E) -> row t holds x_{t-k}, zeros before the sequence starts:
    one `pad` with a negative high edge, which XLA:TPU fuses into the pass
    that reads it (a slice and a concatenate it writes out first)."""
    return x if k == 0 else jax.lax.pad(
        x, jnp.zeros((), x.dtype), ((0, 0, 0), (k, -k, 0), (0, 0, 0)))


def _ahead(x, k):
    """row t holds x_{t+k}, zeros past the sequence's end."""
    return x if k == 0 else jax.lax.pad(
        x, jnp.zeros((), x.dtype), ((0, 0, 0), (-k, k, 0), (0, 0, 0)))


def _thirds(bcz):
    E = bcz.shape[-1] // 3
    return (bcz[..., i * E:(i + 1) * E] for i in range(3))


def _f32(x):
    return x.astype(jnp.float32)


def _taps(w, shifted):
    """sum_j w_j * shifted(L-1-j) in float32, w (E, L)."""
    L = w.shape[1]
    return sum(w[:, j].astype(jnp.float32) * shifted(L - 1 - j)
               for j in range(L))


@jax.custom_vjp
def _gate_taps(bcz, w):
    """[b | c | z] (B, S, 3E) and taps w (E, L) -> c * conv(b * z), (B, S,
    E): v_t = sum_j w_j * g_{t-(L-1)+j} over g = b * z, zero before the
    sequence starts, one filter a channel.  L shifted multiply-adds in
    float32 over the (B, S, E) layout, no transpose to channels-major, the
    gate b * z taken again for each tap from shifted b and z: ONE pass that
    reads 3E and writes E a token.  The backward is written out for the
    same reason (two passes: the gradient of [b | c | z], and the taps');
    autodiff of a first form that padded g made four with a float32
    (B, S, E) array between them (`tools/chip_kernels.py --cases
    shortconv_8k` has the forms tried; PERF.md §6, PR 34)."""
    b, c, z = _thirds(bcz)
    v = _taps(w, lambda k: _f32(_back(b, k)) * _f32(_back(z, k)))
    return (_f32(c) * v).astype(bcz.dtype)


def _gate_taps_bwd(res, dy):
    """dv = dy * c goes back through the taps looking AHEAD (dg_t = sum_j
    w_j dv_{t+(L-1)-j}); db = dg * z, dc = dy * v, dz = dg * b; the taps'
    gradient sums dv_t * g_{t-(L-1)+j} over batch and sequence."""
    bcz, w = res
    b, c, z = _thirds(bcz)
    L = w.shape[1]
    g = lambda k: _f32(_back(b, k)) * _f32(_back(z, k))
    dg = _taps(w, lambda k: _f32(_ahead(dy, k)) * _f32(_ahead(c, k)))
    dbcz = jnp.concatenate(
        [dg * _f32(z), _f32(dy) * _taps(w, g), dg * _f32(b)], axis=-1)
    dv = _f32(dy) * _f32(c)
    dw = jnp.stack([jnp.sum(dv * g(L - 1 - j), axis=(0, 1))
                    for j in range(L)], axis=1)
    return dbcz.astype(bcz.dtype), dw.astype(w.dtype)


# the forward rule names the function itself, not the module's global: a
# test that patches `_gate_taps` wraps the whole rule, forward and backward
_gate_taps.defvjp(lambda bcz, w, _primal=_gate_taps: (_primal(bcz, w),
                                                      (bcz, w)),
                  _gate_taps_bwd)


def short_conv(u, p):
    """The gated short convolution of LFM2 (`Lfm2ShortConv`), u (B, S, E):
    [b | c | z] = u W_in;  g = b * z;  v = the causal depthwise convolution
    of g with the layer's L taps (position t sees t-L+1 .. t);
    (c * v) W_out.  No bias, no activation function.  ``p``: {"in_proj":
    {"kernel": (E, 3E)}, "conv": {"kernel": (E, L)}, "out_proj":
    {"kernel": (E, E)}}.  Counts itself on the job timeline as the step is
    traced (`shortconv.layers`, `shortconv.taps`)."""
    taps = p["conv"]["kernel"]
    tracing.count("shortconv.layers")
    tracing.count("shortconv.taps", taps.shape[1])
    with jax.named_scope("in_proj"):
        bcz = named(u @ p["in_proj"]["kernel"].astype(u.dtype),
                    "short_conv/in_proj")
    with jax.named_scope("gate_taps"):
        y = named(_gate_taps(bcz, taps), "short_conv/gate_taps")
    with jax.named_scope("out_proj"):
        return named(y @ p["out_proj"]["kernel"].astype(u.dtype),
                     "short_conv/out_proj")


# `causal_conv`'s activations, by the name `ops/causal_conv.py` knows them
_CONV_ACTIVATIONS = {None: None, jax.nn.silu: "silu"}


def causal_conv(v, p, activation=None, start=0, widths=None):
    """A causal depthwise convolution with a bias in the (B, S, C) layout:
    out_t = activation(sum_j w_j * v_{t-(K-1)+j} + b), w ``p["kernel"]``
    (C, K), one filter a channel, b ``p["bias"]`` (C,), zeros before the
    sequence starts; ``activation`` None or `jax.nn.silu`.  v (B, S, C), or
    wider with the C channels its columns from ``start`` (a Mamba-2 mixer's
    xBC in W_in's result); ``widths`` cuts the result's columns into a tuple
    of arrays.  `ops/causal_conv.py` makes it: on a TPU, where ``start``
    and the widths are whole 128-lane blocks and the sequence divides into
    row tiles, a Mosaic kernel a pass, forward and backward, that reads the
    columns where they lie; elsewhere `short_conv`'s K shifted
    multiply-adds in float32 without its gates, which jax differentiates.
    Counts itself on the job timeline as the step is traced: `conv.layers`,
    and `conv.kernel_layers` the calls the kernels make."""
    w, b = p["kernel"], p["bias"]
    tracing.count("conv.layers")
    tracing.count("conv.kernel_layers",
                  int(conv_kernels.takes(v, w, start, widths)))
    return conv_kernels.causal_conv(
        v, w, b, _CONV_ACTIVATIONS[activation], start, widths)


# What a recomputed layer may keep besides its attention kernel's residuals:
# results of plain matmuls that the backward pass reads, each marked where it
# is made (`named`).  The mark goes on the product itself where an operation
# behind it reads its own result in the backward pass (a norm, a sigmoid, a
# softmax): kept, that result would still be made again.  The words are
# `SCOPES`' where one fits, so a trace and a kept name read alike.  The ORDER
# is the policy's and the same for every model: by the milliseconds a replay
# spends remaking a value per byte it takes to hold, as the chip's traces
# gave them (PERF.md §6, PR 37).
KEPT_NAMES = (
    ROUTE_NAME,                 # a router's product (T, N), the experts it
                                # chose (T, k) and the rows' order by expert
    "attention/indexer/select", # the mask of the keys each query attends,
                                # (B, S, S) int8: kept, a replay searches
                                # no threshold (16 passes over the scores)
    "attention/latent_down",    # DeepSeek-V3's [c | k_r], W_kv_a's result
    "attention/gate",           # a gated attention's u W_g, (B, S, H) float32:
                                # a product that reads the whole stream for
                                # a result a head wide, as a router's
    SUMMARY_NAME,               # an EVA mixer's chunk summaries of k and v,
                                # a sixteenth of either: kept, a replay drops
                                # the pooling kernel (first by the byte on
                                # evabyte, 57 ms a GiB: PERF.md section 5, PR 69)
    "attention/out",            # W_o's result, as wide as the stream
    "short_conv/out_proj",      # W_out's result, the same
    "kda/out_proj",             # a delta-rule mixer's W_o's result, the same
                                # (13 ms a GiB on ling)
    "short_conv/gate_taps",     # c * conv(b * z), the same
    "attention/qkv",            # GPT-2's fused qkv; W_q's, W_k's and W_v's
                                # results; DeepSeek-V3's q with its RoPE part
    "short_conv/in_proj",       # [b c z], W_in's result, 3E wide
    "ssm/in_proj",              # [z | xBC | dt], W_in's result, 3.8E wide
    "kda/proj",                 # a delta-rule mixer's [q | k | v], u W_f,
                                # u W_g and u W_b: 5 H K + H wide (21 ms a
                                # GiB on ling, a feed-forward's hidden 17)
    "ffn/hidden",               # c_fc's result (4E); a SwiGLU's gate and up;
                                # an ungated expert's up
    "ssm/scan",                 # the scan's y: kept, a replay drops the scan
                                # kernel altogether (its backward reads the
                                # scan's inputs alone, `ops/ssd.py`): 4.4 ms
                                # a step for 0.5 GiB on nemotron, 8.8 ms a
                                # GiB (PERF.md §6, PR 39)
    "attention/latent_up",      # k and v multiplied out of the latent: the
                                # widest and the cheapest to remake
    "kda/conv",                 # q, k and v behind their taps and SiLU: a
                                # pass over its bytes remakes them (4 ms a
                                # GiB on ling)
    "attention/indexer/scores", # an indexer's I, (B, S, S) float32: four
                                # times the mask; kept, it saves a replay
                                # its forward kernel, never the backward's
                                # products, which are made in VMEM either way
)

# Of the device's memory limit, the share the budget never spends: the
# program's own code, the batch, what the allocator loses between buffers.
_HEADROOM = 0.05
# The FIRST GUESS at what a step holds besides its state and what the stack
# keeps (`keep_plan`'s static plan; since PR 72 no longer the budget where
# the compiler gives its own account of the step, `_measured_plan`).  One
# layer's live backward pass, in units of that layer's input and marked
# values (the matmul results and kernel residuals, which are what a replay
# writes out whole; the glue between them the compiler fuses away): the
# replay's values, their head-major and float32 copies and the cotangents in
# flight beside them.  And what runs behind the stack with every kept value
# alive (the head's logits), in units of its bytes: the value and its
# gradient.  Sized with the no-chip compile (`tools/aot_collectives.py`;
# PERF.md §6, PR 37), to err towards keeping less: what a CPU, a device
# that states no limit and a measurement that fails fall back on.
_LIVE_LAYERS = 2.5
_LIVE_BEHIND = 2.0

_told = threading.local()


@contextlib.contextmanager
def _telling(**what):
    """Bind what a caller further out knows and a stack of layers cannot
    see: ``state_bytes`` (`train_step`), ``memory_limit`` and ``plans``
    (`assume_memory_limit`); and what `keep_plan` measures a step through:
    ``account`` (`_compiled_account`, or a test's), ``stacks`` (a count of
    the step's stacks as they are traced), ``decided`` ({a stack's number:
    the names it keeps}) and, in a trace made to be measured, ``forced``
    (the same, handed in)."""
    before = dict(vars(_told))
    vars(_told).update(what)
    try:
        yield
    finally:
        vars(_told).clear()
        vars(_told).update(before)


def assume_memory_limit(limit, plans=None, account=None):
    """Context manager: take ``limit`` bytes for the device's memory limit
    whatever the device states (a described device of the no-chip compile
    states none: `tools/aot_collectives.py`; the CPU of a test neither).
    ``plans``: a list that gets the `keep_plan` of each stack traced
    inside.  ``account``: stands for the compiler's account of the step
    (`_compiled_account`), ``account({a stack's number: names}) -> (the
    step's peak bytes under them, the instructions the compiler made again
    itself) or None``: a test's, where no compiler gives one."""
    return _telling(memory_limit=limit, plans=plans, account=account)


def _first_device():
    """The first device the step is traced for: the bound mesh's, or
    jax's."""
    mesh = get_mesh()
    return mesh.devices.flat[0] if mesh is not None else jax.devices()[0]


def _memory_limit():
    """`memory_stats()["bytes_limit"]` of the first device the step is
    traced for; None where the device states none."""
    told = getattr(_told, "memory_limit", None)
    if told is not None:
        return told
    try:
        return (_first_device().memory_stats() or {}).get("bytes_limit")
    except jax.errors.JaxRuntimeError:  # a described device has no runtime
        return None


def named(x, name):
    """``x`` (an array or a pytree of them) marked as a value a recomputed
    layer may keep, under ``name`` of `KEPT_NAMES`.  Outside a
    `checkpoint_layer` the mark is nothing: no instruction, no copy."""
    if name not in KEPT_NAMES:
        raise ValueError(f"{name!r} is no name of KEPT_NAMES")
    return jax.tree.map(lambda v: checkpoint_name(v, name), x)


def _activation_bytes(tree, tiled=False):
    """Bytes one chip holds of a layer's values (arrays or avals): their
    leading dim is the batch's (or batch x sequence, flat), cut as
    `parallel/sharding.py` cuts a batch, the rest whole; ``tiled`` as
    `chip_bytes` says.  A value inside a `shard_map` (a kernel's under a
    mesh: `parallel/attention.py`) is one chip's share already."""
    def one(leaf):
        mesh = getattr(getattr(leaf, "sharding", None), "mesh", None)
        dims = () if getattr(mesh, "manual_axes", ()) else ("batch",)
        return chip_bytes(leaf.shape, leaf.dtype, *dims, tiled=tiled)
    return sum(one(leaf) for leaf in jax.tree.leaves(tree)
               if hasattr(leaf, "shape"))


def state_bytes(params, opt_state, compute_dtype) -> int:
    """Bytes of the training state on one chip while a step runs: the
    parameters cut as `parallel/sharding.py` lays them out by their names,
    their gradients beside them, the optimizer's state cut as the
    parameters are on average, and the matrices' copy in the compute type
    (`cast_weights`)."""
    whole = held = cast = 0
    for leaf, dims in param_logical_dims(params)[1]:
        here = chip_bytes(leaf.shape, leaf.dtype, *dims)
        whole += chip_bytes(leaf.shape, leaf.dtype)
        held += here
        if leaf.ndim >= 2 and leaf.dtype == jnp.float32:
            cast += here * jnp.dtype(compute_dtype).itemsize // 4
    moments = sum(chip_bytes(leaf.shape, leaf.dtype)
                  for leaf in jax.tree.leaves(opt_state))
    return 2 * held + cast + moments * held // max(whole, 1)


# true of `checkpoint_name`'s primitive alone: the public way to tell it
_is_name = jax.checkpoint_policies.save_any_names_but_these()


def _layer_marks(fn, args, static_argnums):
    """{name: bytes one chip holds of the values ONE call `fn(*args)` marks
    with it}, `KEPT_RESIDUALS` among them, from an abstract trace of the
    layer under the gradient; nothing runs and nothing counts on the job
    timeline."""
    static = {i: args[i] for i in static_argnums}
    avals = [jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          a) for i, a in enumerate(args) if i not in static]

    def call(*dynamic):     # a function of its own: no trace cache shared
        dynamic = iter(dynamic)
        return fn(*(static[i] if i in static else next(dynamic)
                    for i in range(len(args))))

    marked = collections.Counter()

    def record(prim, *in_avals, **params):
        if _is_name(prim, *in_avals, **params):
            # a kernel's result keeps the kernel's layout
            marked[params["name"]] += _activation_bytes(
                in_avals[0], tiled=params["name"] in KEPT_RESIDUALS)
        return False

    with tracing.outside_job():
        jax.eval_shape(lambda *a: jax.vjp(
            jax.checkpoint(call, policy=record), *a)[0], *avals)
    return marked


def keep_plan(fn, calls, static_argnums=(), behind=(), room=None,
              shared=()):
    """What a stack of recomputed layers keeps.  ``calls``: the arguments
    of each call of ``fn``, the first argument the residual stream;
    ``behind``: what the caller makes right behind the stack; ``shared``:
    what layers hand on to later layers (`trunk`), alive from its maker's
    pass to its last reader's backward whatever the budget: "already".
    -> {"names": the names of `KEPT_NAMES` kept, in its order;
    "bytes_kept": what they hold on one chip over the whole stack;
    "declined": the marked names that did not fit; "room": the static
    budget; "already": what the stack keeps whatever the budget (each
    layer's input, `KEPT_RESIDUALS`); "state": the training state as
    `train_step` told it; "reserve"; "marked": {name: bytes over the
    stack}; "measured": whether the compiler's account of the step decided
    (then "measured_room", "peak": the step's compiled peak under the names
    kept, "admitted": the names it added to the static plan's, and
    "recorded": whether a record served all that and nothing was compiled;
    else no "measured_room", 0, () and False)}.

    The static plan first.  Greedy over `KEPT_NAMES`: a name is kept when
    all its values, every layer counted, fit what is left of the room; one
    that does not is skipped whole and the next is tried.  The room
    (``room`` given: that, and nothing is measured) is the device's memory
    limit less `_HEADROOM`, the training state on one chip, what the stack
    keeps already and a reserve: `_LIVE_LAYERS` times the heaviest layer's
    input and marked values, or `_LIVE_BEHIND` times ``behind`` if that is
    more.  A device that states no limit, or a step whose state nobody
    told (`train_step` does), has no room: the stack keeps
    `KEPT_RESIDUALS` alone.

    That sum is a guess, wrong by gigabytes either way (PERF.md section 6,
    PR 72).  Where it declines a name and the step can be compiled for its
    device (`_compiled_account`), the room is what the COMPILED step under
    the static plan leaves of the limit less `_HEADROOM`, and the declined
    names are offered that (`_measured_plan`)."""
    marked, already, heaviest = collections.Counter(), 0, 0
    marks = {}
    for args in calls:
        dynamic = [a for i, a in enumerate(args) if i not in static_argnums]
        key = (tuple(args[i] for i in static_argnums),
               jax.tree.structure(dynamic),
               tuple((x.shape, x.dtype) for x in jax.tree.leaves(dynamic)))
        if key not in marks:
            marks[key] = _layer_marks(fn, args, static_argnums)
        marked.update(marks[key])
        stream = _activation_bytes(args[0])
        already += stream
        heaviest = max(heaviest, stream + sum(marks[key].values()))
    already += sum(marked.pop(name, 0) for name in KEPT_RESIDUALS)
    already += _activation_bytes(shared)
    reserve = int(max(_LIVE_LAYERS * heaviest,
                      _LIVE_BEHIND * _activation_bytes(behind)))
    state = getattr(_told, "state_bytes", None)
    limit = None        # the device's, where the room is this function's
    if room is None:
        limit = _memory_limit()
        room = 0 if limit is None or state is None else \
            int(limit * (1 - _HEADROOM)) - state - already - reserve
    plan = {"room": room, "state": state, "already": already,
            "reserve": reserve, "marked": dict(marked), "measured": False,
            "recorded": False, "peak": 0, "admitted": ()}
    _keeping(plan, _admitted(marked, KEPT_NAMES, room))
    # which of the step's stacks this is, in the order they are traced
    at = next(getattr(_told, "stacks", None) or itertools.count())
    forced = getattr(_told, "forced", None)
    if forced is not None:      # a trace made to be measured: as it is told
        return _keeping(plan, forced.get(at, plan["names"]))
    account = getattr(_told, "account", None)
    if limit is not None and account is not None and plan["declined"]:
        decided = getattr(_told, "decided", None) or {}
        key = _plan_key(calls, static_argnums, behind, shared, plan, limit,
                        {**decided, at: None})
        _measured_plan(plan, int(limit * (1 - _HEADROOM)), key,
                       lambda names: account({**decided, at: names}))
    if getattr(_told, "decided", None) is not None:
        _told.decided[at] = plan["names"]
    return plan


def _admitted(marked, names, room):
    """Those of ``names`` that ``room`` bytes admit, offered in order: a
    name is admitted when all it marks fits what is left, one that does
    not is skipped whole and the next is tried."""
    admitted = []
    for name in names:
        if 0 < marked.get(name, 0) <= room:
            admitted.append(name)
            room -= marked[name]
    return admitted


def _keeping(plan, names):
    """``plan`` keeping ``names`` of its marked values, and declining the
    rest: in `KEPT_NAMES`' order both."""
    marked = plan["marked"]
    plan["names"] = tuple(n for n in KEPT_NAMES if n in marked and n in names)
    plan["declined"] = tuple(n for n in KEPT_NAMES
                             if n in marked and n not in names)
    plan["bytes_kept"] = sum(marked[n] for n in plan["names"])
    return plan


def _measured_plan(plan, budget, key, peak_under):
    """The static ``plan`` of a stack that declines a name, given the room
    the compiled step has: ``budget`` (the limit less `_HEADROOM`) less
    ``peak_under(names)``, the step's compiled peak with this stack keeping
    ``names`` (`_step_peak`'s pair; None: no account).  The declined names
    are offered that room greedily, in `KEPT_NAMES`' order and whole, as
    the static room was; if that admits any, the step keeping them too is
    compiled and has to read at or under the budget itself, with no more
    instructions made again by the compiler than under the static plan (a
    step XLA had to squeeze under its scheduler's limit reads under the
    budget and runs slower: nemotron's, PERF.md section 6, PR 72), else (a
    kept value costs more than its bytes, or the compiler refuses the
    step) the last name admitted is given back and the check repeated: the
    plan handed on was SEEN to fit.  A measurement that cannot be made
    leaves the static plan: never less than before there was one.

    Measured once a program and chip: the outcome is written under
    ``key`` (`_plan_key`) beside the compiled programs, and a later trace
    that finds it there takes the names from it and compiles nothing."""
    marked, static = plan["marked"], plan["names"]
    recorded = _read_record(key, marked)
    if recorded is not None:
        names, p0_peak, peak = recorded
    else:
        try:
            seen = peak_under(static)
        except Exception as e:      # whatever a compile raises: the guess
            logger.warning("keep_plan: the step's account failed (%s: %s); "
                           "the static plan stands", type(e).__name__, e)
            seen = None
        if seen is None:
            return plan
        p0_peak, p0_remade = seen
        admitted = _admitted(marked, plan["declined"], budget - p0_peak)
        names, peak = static, p0_peak
        while admitted:
            try:
                seen = peak_under(static + tuple(admitted))
            except Exception as e:
                logger.warning("keep_plan: the step keeping %s too does not "
                               "compile (%s: %s)", admitted[-1],
                               type(e).__name__, e)
                seen = None
            if seen is not None and seen[0] <= budget \
                    and seen[1] <= p0_remade:
                names, peak = static + tuple(admitted), seen[0]
                break
            admitted.pop()
        _write_record(key, names, p0_peak, peak)
    _keeping(plan, names)
    plan.update(measured=True, recorded=recorded is not None,
                measured_room=budget - p0_peak, peak=peak,
                admitted=tuple(n for n in plan["names"] if n not in static))
    return plan


# -- the step's account, from the compiler -----------------------------------

def placed_shapes(params, opt_state, batch):
    """((params, opt_state, batch) as shapes placed where a caller places
    the arrays, the shardings of the first two) under the bound mesh:
    parameters by `parallel/sharding.py`'s rules for their names, each
    optimizer leaf beside the parameter whose path ends its own, whatever
    else (the step count) everywhere, a batch's leading dim cut as a
    batch's.  No mesh bound: shapes alone, for jax's first device."""
    mesh = get_mesh()

    def struct(x, sharding=None):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    if mesh is None:
        return jax.tree.map(struct, (params, opt_state, batch)), None

    def at(*dims):
        return jax.sharding.NamedSharding(mesh, logical_spec(mesh, dims))

    paths = [path for path, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    by_path = {path: at(*dims) for path, (_, dims)
               in zip(paths, param_logical_dims(params)[1])}

    def beside(path):
        for start in range(len(path)):
            if path[start:] in by_path:
                return by_path[path[start:]]
        return at()

    placed = (
        jax.tree_util.tree_map_with_path(
            lambda path, x: struct(x, by_path[path]), params),
        jax.tree_util.tree_map_with_path(
            lambda path, x: struct(x, beside(path)), opt_state),
        jax.tree.map(lambda x: struct(
            x, at("batch", *(None,) * (x.ndim - 1)) if x.ndim else at()),
            batch))
    return placed, jax.tree.map(lambda x: x.sharding, placed[:2])


def _step_peak(step, args, forced):
    """The peak bytes of ``step`` compiled for the device ``args`` (its
    params, opt_state and batch, arrays or tracers) are being traced for,
    with its recomputed stacks keeping the names of ``forced`` ({a stack's
    number: names}; a stack left out: its static plan), as the compiler
    accounts for it (`memory_analysis()`: arguments + scratch + the outputs
    that alias no argument), and the instructions the compiler made again
    ITSELF to get there (XLA's own rematerialization names its copies
    `<name>.remat`: what it does to a step past its scheduler's limit, at a
    price in time no account of bytes shows) -> (bytes, instructions); None
    where the compiler gives no account.  The step
    is jitted as `train_step` says a caller does (the state donated and
    handed back placed as it came), on the arguments' shapes as
    `placed_shapes` places them, so the program measured under the plan a
    stack ends with is the one the caller compiles next, and jax's cache
    serves that.
    Nothing of it counts on the job timeline."""
    avals, kept = placed_shapes(*args)
    placing = {} if kept is None else {"out_shardings": (*kept, None)}

    # a function of its own under the step's name (the compiled module's):
    # jit keeps a function's trace by its arguments' shapes
    def train_step(*args):
        return step(*args)

    with tracing.outside_job(), \
            _telling(forced=forced, plans=None, account=None):
        compiled = jax.jit(train_step, donate_argnums=(0, 1), **placing
                           ).lower(*avals).compile()
    memory = compiled.memory_analysis()
    if memory is None:
        return None
    peak = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + max(0, memory.output_size_in_bytes
                  - memory.alias_size_in_bytes))
    return peak, len(_COMPILER_REMADE.findall(compiled.as_text()))


# an instruction XLA's rematerialization defined, in a compiled module's text
_COMPILER_REMADE = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]*\.remat[\w.\-]* = ",
                              re.M)


def _compiled_account(step, params, opt_state, batch):
    """-> ``account(forced) -> (peak bytes, instructions the compiler made
    again itself) or None``: `_step_peak` of ``step`` on these arguments;
    None on a CPU, whose compiler's buffers say nothing of a chip's
    memory."""
    if _first_device().platform == "cpu":
        return None
    return functools.partial(_step_peak, step, (params, opt_state, batch))


# -- what was measured, remembered -------------------------------------------

@functools.cache
def _sources_digest():
    """A digest of the sources a step's program is made from: this
    directory, `ops` and `parallel`."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    digest = hashlib.sha256()
    for part in ("models", "ops", "parallel"):
        for name in sorted(os.listdir(os.path.join(root, part))):
            if name.endswith(".py"):
                with open(os.path.join(root, part, name), "rb") as f:
                    digest.update(name.encode() + f.read())
    return digest.hexdigest()


def _versions():
    """jax's, jaxlib's and libtpu's versions: the compiler's."""
    from importlib import metadata

    found = []
    for package in ("jax", "jaxlib", "libtpu"):
        try:
            found.append(metadata.version(package))
        except metadata.PackageNotFoundError:
            found.append(None)
    return found


def _plan_key(calls, static_argnums, behind, shared, plan, limit, among):
    """The name a stack's measured plan is remembered under: a digest of
    what `keep_plan` has before any heavy trace and the measured outcome
    depends on.  The stack's calls by their arrays' shapes and dtypes and
    their static arguments as they print (an address left out); what is
    marked, by name; what is kept already; the static plan; the state; the
    limit; what lies behind; ``among``: which of the step's stacks this is
    and what those before it keep; the mesh's shape; the device's kind;
    the compiler's versions; the sources."""
    mesh = get_mesh()
    shapes = lambda tree: [[list(x.shape), str(x.dtype)]
                           for x in jax.tree.leaves(tree)
                           if hasattr(x, "shape")]
    what = {
        "calls": [[re.sub(r"0x[0-9a-f]+", "", repr(a)) if i in static_argnums
                   else shapes(a) for i, a in enumerate(args)]
                  for args in calls],
        "marked": plan["marked"], "already": plan["already"],
        "static": plan["names"], "state": plan["state"], "limit": limit,
        "behind": shapes(behind), "shared": shapes(shared),
        "among": sorted(among.items()),
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "device": _first_device().device_kind, "versions": _versions(),
        "sources": _sources_digest()}
    return hashlib.sha256(
        json.dumps(what, sort_keys=True).encode()).hexdigest()[:40]


def _record_path(key):
    """Where the plan measured under ``key`` is kept: below the directory
    of jax's compilation cache; None where this process keeps no cache."""
    cache = compile_cache_dir()
    return cache and os.path.join(cache, "keep_plans", key + ".json")


# the records whose plans this process handed out and no compile of the step
# has borne out yet
_on_trial = set()
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _step_compiled(event, _seconds, fun_name="", **_):
    """jax reports a compile's end (`jax.monitoring`), also of one that
    raised: the first of a step's after a record served its plan bears the
    record out, or, refused by the compiler, deletes it, and the next
    set-up measures anew.  Not the compiles `_compiled_account` makes."""
    if (event != _COMPILE_EVENT or not _on_trial
            or "train_step" not in str(fun_name)
            or getattr(_told, "forced", None) is not None):
        return
    refused = sys.exc_info()[0] is not None
    while _on_trial:
        path = _on_trial.pop()
        if refused:
            logger.warning("keep_plan: the compiler refused the step under "
                           "the plan recorded at %s: deleted", path)
            _forget(path)


@functools.cache
def _listen_for_compiles():
    """`_step_compiled` among jax.monitoring's listeners, once a process."""
    jax.monitoring.register_event_duration_secs_listener(_step_compiled)


def _forget(path):
    with contextlib.suppress(OSError):
        os.remove(path)


def _read_record(key, marked):
    """-> (names, the static plan's compiled peak, the names' own) as
    `_write_record` wrote them under ``key``, and the record on trial
    (`_step_compiled`); None where there is none.  One that cannot be read
    or names what the stack does not mark is deleted."""
    path = _record_path(key)
    if not path or not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            record = json.load(f)
        names = tuple(record["names"])
        peaks = int(record["static_peak_bytes"]), int(record["peak_bytes"])
        if not set(names) <= set(marked):
            raise ValueError(f"names {names} of no mark")
    except (OSError, ValueError, KeyError, TypeError) as e:
        logger.warning("keep_plan: the record at %s cannot be read (%s): "
                       "deleted", path, e)
        _forget(path)
        return None
    _listen_for_compiles()
    _on_trial.add(path)
    return (names, *peaks)


def _write_record(key, names, static_peak, peak):
    """The measured outcome under ``key``, written whole or not at all."""
    path = _record_path(key)
    if not path:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(f"{path}.{os.getpid()}", "w") as f:
            json.dump({"names": list(names), "static_peak_bytes": static_peak,
                       "peak_bytes": peak}, f)
        os.replace(f.name, path)
    except OSError as e:
        logger.warning("keep_plan: no record written at %s (%s)", path, e)


def _keep(names):
    """The policy of `checkpoint_layer`: keep a value marked with one of
    ``names``, recompute everything else.  Counts each value it keeps on
    the job timeline (`remat.residuals_kept`), once per traced layer."""
    save = jax.checkpoint_policies.save_only_these_names(*names)

    def policy(prim, *avals, **params):
        keep = save(prim, *avals, **params)
        if keep:
            tracing.count("remat.residuals_kept")
        return keep
    return policy


def checkpoint_layer(fn, stack=None, behind=(), shared=(), **kw):
    """`jax.checkpoint(fn, **kw)` for a model's layer, and the one owner of
    what a recomputed layer keeps.  Always its attention kernel's output
    and row statistics (`ops/flash_attention.py:KEPT_RESIDUALS`, what the
    kernel's backward reads besides q, k and v).  With ``stack``, the
    arguments of every call the caller is about to make (one tuple a
    layer), also the values the layers mark with `KEPT_NAMES`, most
    valuable first, as far as the chip has room for them over the whole
    stack (`keep_plan`): the backward pass then recomputes the cheap glue
    between kept matmul results and no more.  ``behind``: arrays or avals
    of what the caller makes right behind the stack, with every kept value
    alive (the head's logits, or a chunk of them); ``shared``: what the
    layers hand on to later layers, as `keep_plan` counts it.  The plan is
    made here, once per traced stack, and counted on the job timeline:
    `remat.bytes_kept` (one chip, all layers), `remat.names_declined`, and
    how the room was found: `remat.room_measured` (1: the compiler's
    account of the step decided, 0: the static sum), `remat.
    plan_recorded_hit` (1: a record of an earlier measurement served it and
    nothing was compiled for it), `remat.measured_peak_bytes` (the step's
    compiled peak under the names kept) and `remat.names_admitted` (the
    names the account added to the static plan's).
    A layer that marks nothing is a bare `jax.checkpoint`."""
    names = KEPT_RESIDUALS
    if stack is not None:
        plan = keep_plan(fn, stack, kw.get("static_argnums", ()), behind,
                         shared=shared)
        names += plan["names"]
        tracing.count("remat.bytes_kept", plan["bytes_kept"])
        tracing.count("remat.names_declined", len(plan["declined"]))
        tracing.count("remat.room_measured", int(plan["measured"]))
        tracing.count("remat.plan_recorded_hit", int(plan["recorded"]))
        tracing.count("remat.measured_peak_bytes", plan["peak"])
        tracing.count("remat.names_admitted", len(plan["admitted"]))
        if getattr(_told, "plans", None) is not None:
            _told.plans.append(plan)
    return jax.checkpoint(fn, policy=_keep(names), **kw)


def _final_norm(x, p, cfg):
    """The norm behind a decoder's last layer, by its leaves: a LayerNorm at
    `cfg.norm_eps` where ``p`` has a bias, else an RMSNorm at
    `cfg.rms_eps`."""
    if "bias" in p:
        return layer_norm(x, p, cfg.norm_eps)
    if getattr(cfg, "norm_unit_offset", False):
        return rms_norm(x, p, cfg.rms_eps, unit_offset=True)
    return rms_norm(x, p, cfg.rms_eps)


def _shared_walk(x, layers, layer, cfg, behind):
    """`trunk`'s walk for layers that hand tensors on: -> (x behind the last
    layer, the layers' second results, a None left out).  What each call is
    handed is known before any runs (an abstract walk, nothing counted), so
    that one budget reckons every layer (`checkpoint_layer`) with what is
    handed on counted as held whatever it decides."""
    calls, made, held = [], [], None
    with tracing.outside_job():
        for i, p in enumerate(layers):
            calls.append((x, p, cfg, held, i))
            _, _, after = jax.eval_shape(
                lambda x, p, held, i=i: layer(x, p, cfg, held, i), x, p, held)
            made += [leaf for name, leaf in (after or {}).items()
                     if name not in (held or {})]
            held = after
    tracing.count("shared.bytes_kept", _activation_bytes(made))
    if cfg.remat:
        layer = checkpoint_layer(layer, stack=calls, static_argnums=(2, 4),
                                 behind=behind, shared=made)
    seconds, held = [], None
    for i, p in enumerate(layers):
        x, second, held = layer(x, p, cfg, held, i)
        if second is not None:
            seconds.append(second)
    return x, seconds


def trunk(params, tokens, layer, cfg, walks=None, shared=False):
    """A decoder's walk, tokens (B, S) int32 -> ((B, S, E) after the final
    norm, the layers' second results in order, a None left out): the
    embedding ``params["embed_tokens"]`` under `embed`, in the compute
    type; ``layer(x, params[f"layer_{i}"], cfg) -> (x, anything)`` for the
    `cfg.n_layer` layers in order, each recomputed by the backward pass
    when `cfg.remat`, keeping what fits the chip with a chunk of the head's
    logits (`cfg.loss_chunk_rows` of `cfg.vocab_size`) behind the stack
    (`checkpoint_layer`); the final norm by ``params["norm_f"]``'s leaves
    (`_final_norm`: an RMSNorm at `cfg.rms_eps`, or with a bias a LayerNorm
    at `cfg.norm_eps`).

    ``shared``: a model whose later layers read what earlier layers MADE
    (a scan's result, a layer's keys and values).  Its layer is called
    ``layer(x, p, cfg, held, i) -> (x, anything, held)``: ``held`` what the
    layers before handed on, a dict of arrays or None, which the layer hands
    on with what it adds; ``i`` its number here, static.  What is handed on
    is a result of its maker's recomputed pass, so it lives from there to
    its last reader's backward and the stack's budget counts it as held
    (`keep_plan`'s ``shared``); its bytes on one chip are counted on the job
    timeline as the step is traced (`shared.bytes_kept`).  False: no layer
    is handed anything, and the program is what it always was.

    ``walks`` = T, a looped model's: the same layers are walked T times
    over the same parameters, the final norm after EVERY walk, and what it
    gives is both what the next walk starts from and what the caller reads
    -> ((T, B, S, E), every walk's normed state; the second results of all
    T x n calls in order).  One `checkpoint_layer` is over the T x n calls,
    so its budget reckons what T visits of a layer keep.  The walks are a
    Python loop, T x n layer bodies in the program: a `lax.scan` over the
    walks makes n, compiles 20 s sooner and ran 4 % slower on the chip at
    the Ouro cell's sizes (PERF.md section 6, PR 50).  Counted on the job
    timeline as the step is traced: `loop.walks` (T), `loop.layer_calls`
    (T x n) and `loop.layer_traces` (the layer bodies the program holds:
    T x n as long as the walks are unrolled).  None: one walk, as every
    model but a looped one asks for, nothing counted.

    A configuration with a `stream_dtype` (float32 beside bfloat16
    products: a release's `fp32_skip_add`) has the embedding's rows, and so
    the residual stream, in that type; its layer casts what it multiplies.
    One with `norm_unit_offset` has the final RMSNorm's gain as 1 + w
    (`rms_norm`).  Neither: the program it always was.

    `models/gpt2.py` walks by itself: positional embeddings, pipeline
    stages, a mesh's pins."""
    with jax.named_scope("embed"):
        x = params["embed_tokens"]["embedding"][tokens].astype(
            getattr(cfg, "stream_dtype", None) or cfg.compute_dtype)
    layers = [params[f"layer_{i}"] for i in range(cfg.n_layer)]
    behind = jax.ShapeDtypeStruct((cfg.loss_chunk_rows, cfg.vocab_size),
                                  jnp.float32)
    if shared:
        x, seconds = _shared_walk(x, layers, layer, cfg, behind)
        return _final_norm(x, params["norm_f"], cfg), seconds
    if cfg.remat:
        layer = checkpoint_layer(
            layer, stack=[(x, p, cfg) for p in layers] * (walks or 1),
            static_argnums=(2,), behind=behind)

    def walk(x):
        seconds = []
        for p in layers:
            if walks is not None:
                tracing.count("loop.layer_traces")
            x, second = layer(x, p, cfg)
            if second is not None:
                seconds.append(second)
        return _final_norm(x, params["norm_f"], cfg), seconds

    if walks is None:
        return walk(x)
    tracing.count("loop.walks", walks)
    tracing.count("loop.layer_calls", walks * len(layers))
    states, seconds = [], []
    for _ in range(walks):
        x, more = walk(x)
        states.append(x)
        seconds += more
    return jnp.stack(states), seconds


def _chunks(n_chunks: int, *rows):
    """Arrays (N, ...) -> each (n, N / n, ...), a None left as it is:
    ``n_chunks`` chunks, or the next fewer that divide N."""
    N = rows[0].shape[0]
    n_chunks = max(1, min(n_chunks, N))
    while N % n_chunks:
        n_chunks -= 1
    return tuple(r if r is None else
                 r.reshape(n_chunks, N // n_chunks, *r.shape[1:])
                 for r in rows)


def _chunk_logits(wte, xi, ti):
    """One chunk's logits in float32 -> (them, their rows' log-sum-exp,
    the rows' cross-entropies)."""
    logits = jnp.matmul(xi, wte.T, preferred_element_type=jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, ti[:, None], axis=-1)[:, 0]
    return logits, lse, lse - tgt


def _own_buffer(xi):
    """A chunk of x as a buffer of its own.  Left alone, XLA:TPU fuses the
    loop's slice of x into each product that reads the chunk, and the
    product then fetches its rows from the whole (n, R, E) array in HBM
    again for every tile of its result; behind the barrier the chunk is
    sliced once, 8 MB at (2,048, 2,048), and the compiler keeps it in the
    chip's fast memory beside the product.  On the v5e at 2,048 x 2,048 x
    50,304, ms a chunk: the logits 2.70 -> 2.22, dW 3.18 -> 2.42 (3.77 ->
    2.41 with its float32 carry, which then costs nothing)
    (`tools/chip_kernels.py --cases head_loss_8k`; PERF.md section 6,
    PR 53)."""
    return jax.lax.optimization_barrier(xi)


def _weighted_sum(ce, wc):
    return jnp.sum(ce if wc is None else ce * wc)


def _count_walk(xc):
    """One walk over the chunks, on the job timeline as it is traced."""
    tracing.count("loss.chunks", xc.shape[0])
    tracing.count("loss.logits_passes")


@jax.custom_vjp
def chunked_xent(xc, wte, tc, wc):
    """Fused linear + softmax cross-entropy, chunked over tokens (the idea
    of the fused linear-cross-entropy kernels; the products are XLA's, no
    Pallas needed).  xc (n, R, E) in the compute type: n chunks of R rows;
    wte (V, E); tc (n, R) int32 targets; wc (n, R) float32, a weight a row,
    or None: 1 for every row.  -> (sum_r w_r CE_r, the rows' CE (n, R)),
    float32.  The (N, V) logits never exist: a chunk's are made in float32,
    read and dropped.

    This, the primal, computes losses only: a call nobody differentiates
    pays for no gradient.  Under a gradient the forward rule
    (`_chunked_xent_fwd`) walks the chunks ONCE and forms each chunk's
    gradient from its logits while they are there; the backward rule makes
    no product.  The gradient reaches x, wte and the weights (the rows' CE
    times the cotangent: exact).  The rows handed back carry NO gradient:
    their cotangent is dropped, and the one caller that hands them on says
    so with `stop_gradient` (`head_and_weighted_loss`).

    Counted on the job timeline as the step is traced: `loss.chunks` (n)
    and `loss.logits_passes` (1 a walk: the logits are made once)."""
    _count_walk(xc)
    ce = jax.lax.map(
        lambda xt: _chunk_logits(wte, _own_buffer(xt[0]), xt[1])[2], (xc, tc))
    return _weighted_sum(ce, wc), ce


def _chunked_xent_fwd(xc, wte, tc, wc):
    """A chunk: logits -> statistics -> the rows' CE -> d logits = w
    (softmax - onehot), float32 -> in the compute type at once, and
    contracted at once into the chunk's dx (R, E) and into dW (V, E), a
    float32 accumulator carried over the chunks: three products a chunk,
    the two of the gradient with operands in the compute type and float32
    accumulation (what the MXU makes of a float32 cotangent at default
    precision).  The residuals are dx, dW in wte's type, the rows' CE and
    the weights: the gradient for a cotangent of 1."""
    _count_walk(xc)

    def chunk(dw, xtw):
        xi, ti, wi = xtw
        xi = _own_buffer(xi)
        logits, lse, ce = _chunk_logits(wte, xi, ti)
        d = jnp.exp(logits - lse[:, None]) - jax.nn.one_hot(
            ti, logits.shape[-1], dtype=jnp.float32)
        if wi is not None:
            d = d * wi[:, None]
        d = d.astype(xi.dtype)
        dw = dw + jax.lax.dot_general(
            d, xi, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dw, (ce, jnp.matmul(d, wte))

    dw, (ce, dx) = jax.lax.scan(
        chunk, jnp.zeros(wte.shape, jnp.float32), (xc, tc, wc))
    return (_weighted_sum(ce, wc), ce), (dx, dw.astype(wte.dtype), ce, wc)


def _chunked_xent_bwd(res, cotangents):
    dx, dw, ce, wc = res
    g, _ = cotangents           # the rows handed back carry no gradient
    scaled = lambda a: (a.astype(jnp.float32) * g).astype(a.dtype)
    return scaled(dx), scaled(dw), None, None if wc is None else ce * g


chunked_xent.defvjp(_chunked_xent_fwd, _chunked_xent_bwd)


def _head_rows(head, dtype):
    """(V, E) in ``dtype``: an untied head's {"kernel": (E, V)}, or the
    embedding a tied one is, {"embedding": (V, E)}."""
    return head["embedding"].astype(dtype) if "embedding" in head \
        else head["kernel"].astype(dtype).T


def head_and_weighted_loss(x, head, targets, weights, chunk_rows):
    """x (..., E), targets (...) int32, weights (...) float32 or None (1 for
    every row) -> (sum_r w_r CE_r, every row's next-token cross-entropy
    (...) float32), under `head_and_loss`.  ``head``: an untied head's
    parameters {"kernel": (E, V)}, or those of the embedding a tied one is
    {"embedding": (V, E)}.  The logits are made ``chunk_rows`` rows at a
    time, once, and never all held (`chunked_xent`).  The gradient goes
    through the SUM, to x, the head and the weights; the rows are for
    reading (a report, a mean) and carry none.  A looped model hands all
    its walks' states in at once, the targets repeated and its exit
    distribution the weights, and the head is read by one loop over their
    chunks."""
    E = x.shape[-1]
    with jax.named_scope("head_and_loss"):
        xc, tc, wc = _chunks(
            -(-targets.size // chunk_rows), x.reshape(-1, E),
            targets.reshape(-1),
            None if weights is None else weights.reshape(-1))
        total, rows = chunked_xent(xc, _head_rows(head, x.dtype), tc, wc)
        return total, jax.lax.stop_gradient(rows).reshape(targets.shape)


def head_and_loss(x, head, targets, chunk_rows):
    """x (B, S, E), targets (B, S) int32 -> the mean next-token
    cross-entropy, under `head_and_loss`: `head_and_weighted_loss` with
    every row's weight 1, over the rows' number."""
    total, _ = head_and_weighted_loss(x, head, targets, None, chunk_rows)
    with jax.named_scope("head_and_loss"):
        return total / targets.size


def cast_weights(params, dtype):
    """One whole-tree cast of the matmul weights (ndim >= 2) to the compute
    dtype.  Made ONCE up front and not per use: XLA fuses a single-consumer
    f32->bf16 cast INTO the consuming matmul, and a matmul with a fused
    operand conversion leaves the MXU's fast path.  A shared pre-cast
    materializes each bf16 weight once and every matmul takes bf16
    operands.  1-D leaves (biases, norm scales) stay f32 — they only feed
    VPU ops."""
    return jax.tree.map(
        lambda x: x.astype(dtype)
        if x.dtype == jnp.float32 and x.ndim >= 2 else x, params)


def train_step(objective, optimizer, compute_dtype, rule=None,
               counted=False):
    """train_step(params, opt_state, batch) -> (params, opt_state, out) for
    ``objective(cast_params, batch) -> (scalar, out)`` — jit it with the
    appropriate shardings and ``donate_argnums=(0, 1)``.

    ``counted``: the objective asks for the step's number and is called
    ``objective(cast_params, batch, count)``, count the optimizer's own
    count of the updates made so far, as ``opt_state`` holds it (int32, 0
    in the first step): what a step that draws its own noise folds into its
    key, so that the noise is a function of the run's seed and the step and
    a resumed run draws what the unbroken one would.  An objective that
    does not ask is called as it was and its step is what it was.

    Mixed precision: f32 master params; the objective sees the weight tree
    cast to ``compute_dtype`` once (see cast_weights), autodiff flows back
    through the cast, so grads and the optimizer's update stay f32.

    ``rule(params, out) -> params``: state among the parameters that moves
    by a rule of its own after the optimizer's update (a router's
    load-balancing bias, from the step's counts in ``out``).  Such leaves
    get no gradient from the objective and ``optimizer`` is built to leave
    them alone.  A model without such state passes none and its step is
    what it was."""

    # its name is the compiled module's (`jit_train_step`) in every trace
    def train_step(params, opt_state, batch):
        count = (_update_count(opt_state),) if counted else ()

        def cast_objective(p):
            return objective(cast_weights(p, compute_dtype), batch, *count)

        # what a recomputed stack inside cannot see and its budget needs:
        # the state's bytes, and the compiler's account of this very step
        # (a trace made to be measured is told its plans and measures none)
        told = {"state_bytes": state_bytes(params, opt_state, compute_dtype),
                "stacks": itertools.count()}
        if getattr(_told, "forced", None) is None:
            told["decided"] = {}
            if getattr(_told, "account", None) is None:
                told["account"] = _compiled_account(train_step, params,
                                                    opt_state, batch)
        with _telling(**told):
            (_, out), grads = jax.value_and_grad(cast_objective,
                                                 has_aux=True)(params)
        with jax.named_scope("optimizer_update"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = jax.tree.map(lambda p, u: p + u, params, updates)
        if rule is not None:
            params = rule(params, out)
        return params, opt_state, out

    return train_step


def _update_count(opt_state):
    """The first `count` an optax state holds: the updates made so far."""
    import optax

    (_, count), *_ = optax.tree_utils.tree_get_all_with_path(opt_state,
                                                             "count")
    return count


def normal_kernel(key, *shape, std=0.02):
    """{"kernel": Normal(0, ``std``) of ``shape``, float32}: a matrix, or
    (its "kernel" taken out) a stack of them or an embedding, as an
    `init_params` draws it from one key."""
    return {"kernel": jax.random.normal(key, shape, jnp.float32) * std}


def unit_scale(width):
    """{"scale": ones (width,), float32}: a norm's gain as it starts."""
    return {"scale": jnp.ones((width,), jnp.float32)}


def num_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))

"""Phi-4-mini-flash-reasoning (`model_type` `phi4flash`: Microsoft's 3.8 B
hybrid; Ren et al., "Decoder-Hybrid-Decoder Architecture for Efficient
Reasoning with Long Generation", arXiv:2507.06607: SambaY with differential
attention) for the Train path: a decoder whose layers are of FIVE kinds by
their published index, the later of which read tensors two earlier layers
made.

The equations (E the stream's width, C = `expand` x E channels, N =
`d_state`, R = `dt_rank`; LN a LayerNorm with gain and bias at `norm_eps`;
no position embedding of any kind: the Mamba layers carry the order):

  every layer:  x = x + mixer(LN1(x));  x = x + W2 (up * SiLU(gate)) with
    gate = LN2(x) W_gate, up = LN2(x) W_up, no bias (the published W1 is
    [W_gate | W_up] side by side).
  Mamba-1 mixer (even layers up to the middle one, `n_published / 2`):
    [u | z] = h W_in;  u = SiLU(conv(u) + b), causal, `d_conv` taps a
    channel;  [r | B_t | C_t] = u W_x (widths R, N, N);
    dt = softplus(r W_dt + b_dt);  A = -exp(A_log) (C x N);
    s_t = exp(dt_t (x) A) s_{t-1} + (dt_t u_t) (x) B_t, s_{-1} = 0;
    y_t = s_t C_t + D u_t;  the mixer gives (y * SiLU(z)) W_out.  The middle
    layer also hands on m = y, before the gate and with the D term.
  differential attention (odd layers up to the middle + 1):
    q = h W_q + b in H heads of d, k and v likewise in H_kv.  q1 the even
    query heads and q2 the odd; k1, k2, v1, v2 likewise of the key/value
    heads: H / 2 pairs of queries on H_kv / 2 pairs of keys, pair j reading
    key pair j // (H / H_kv).  a1 = softmax(q1 k1' d^-1/2 + mask) [v1 | v2],
    a2 = softmax(q2 k2' d^-1/2 + mask) [v1 | v2], each 2 d wide;
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda0, four learned vectors
    of d a layer, lambda0 = 0.8 - 0.6 exp(-0.3 i) at the PUBLISHED index i;
    o = RMSNorm_2d(a1 - lambda a2) (1 - lambda0), one gain of 2 d a layer;
    the mixer gives o W_o + b.  Mask: causal, and below the middle + 1 only
    the `window` latest keys, the row's own among them; layer middle + 1
    attends every earlier key and hands on its k and v.
  cross layer (odd layers past the middle + 1): q = h W_q + b alone; the
    same differential attention, causal, over the handed-on k and v; its
    own lambda vectors, gain and W_o.
  gated memory unit (even layers past the middle):
    (m * SiLU(h W_1)) W_2, no bias; m the middle layer's.
  the final LN, then the logits by the embedding's own rows (tied).

Which heads pair is a convention (another is this one up to a permutation
of W_q's, W_k's and W_v's columns); so is the split of W1.

`first_layer`: the published index of `layer_0` held here (a chip that
holds layers 14-19 of the 32 says 14): a layer's kind and lambda0 go by the
published index.  A stage that holds a reader holds its maker.

What the layers share is `models/layers.py:trunk`'s third result (`shared`:
a dict a layer adds to, "memory" by the middle layer, "k1", "k2" and "v" by
the full attention layer, kept from its maker's pass to its last reader's
backward and counted in the recomputed stack's budget).  Also shared:
LayerNorm, the SwiGLU, the causal convolution (`ops/causal_conv.py` behind
`layers.causal_conv`), the head's chunked loss and the mixed-precision step;
`parallel/attention.py` (the flash kernels: two calls a layer, queries and
keys d wide on values 2 d wide, grouped queries, under a window or not) and
`ops/selective_scan.py`.

`jax.named_scope`s (`models/layers.py:SCOPES`): embed, norm,
mamba/{in_proj,conv,x_proj,scan,out_proj}, attention/{qkv,cross,kernel,
diff,out}, gmu, ffn/dense, head_and_loss, optimizer_update.  Counted on the
job timeline as the step is traced: `attention.diff_pairs` (the pairs of
query heads, a layer), `shared.kv_readers`, `shared.memory_readers`.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.layers import (
    causal_conv,
    dense_ffn,
    head_and_loss,
    layer_norm,
    named,
    normal_kernel,
    num_params,  # noqa: F401  (`phi4flash.num_params` is public)
    swiglu,
    train_step,
    trunk,
)
from ray_tpu.ops.flash_attention import BlockRule
from ray_tpu.ops.selective_scan import selective_scan
from ray_tpu.parallel.attention import attention
from ray_tpu.util import tracing

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"


@dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064          # rows of the tied embedding held here
    n_layer: int = 32                 # layers held here
    first_layer: int = 0              # the published index of `layer_0`
    n_published: int = 32             # the model's depth: the roles' frame
    n_embd: int = 2560
    n_head: int = 40
    n_kv_head: int = 20
    head_dim: int = 64
    dense_width: int = 10240
    window: int = 512
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160                # ceil(n_embd / 16)
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    lambda_std: float = 0.1
    norm_eps: float = 1e-5            # the LayerNorms'
    rms_eps: float = 1e-5             # the norm over a pair's 2 d
    compute_dtype: Any = jnp.bfloat16
    # jax.checkpoint each layer, keeping what its attention kernels' backward
    # reads, what it hands on to later layers and, of `layers.KEPT_NAMES`,
    # what the chip has room for (`layers.checkpoint_layer`)
    remat: bool = True
    loss_chunk_rows: int = 2048       # `layers.chunked_xent`

    @property
    def channels(self) -> int:
        return self.expand * self.n_embd

    def kind(self, i: int) -> str:
        """The kind of the layer held at ``i``, by its published index."""
        at, middle = self.first_layer + i, self.n_published // 2
        if at % 2 == 0:
            return MAMBA if at <= middle else GMU
        return WINDOW if at < middle + 1 else \
            FULL if at == middle + 1 else CROSS

    def lambda_init(self, i: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * (self.first_layer + i))


PHI4_MINI_FLASH = Phi4FlashConfig()
# all five kinds, both makers and both readers: published layers 2..7 of 8
PHI4FLASH_TINY = Phi4FlashConfig(
    vocab_size=512, n_layer=6, first_layer=2, n_published=8, n_embd=64,
    n_head=8, n_kv_head=4, head_dim=8, dense_width=160, window=24,
    d_state=4, dt_rank=4, loss_chunk_rows=32)


def _norm(width):
    return {"scale": jnp.ones((width,), jnp.float32),
            "bias": jnp.zeros((width,), jnp.float32)}


def _biased(key, rows, cols):
    return dict(normal_kernel(key, rows, cols),
                bias=jnp.zeros((cols,), jnp.float32))


def _diff(key, cfg):
    """What a differential attention has besides its projections in: the
    four lambda vectors, the gain over a pair's 2 d and W_o with its bias."""
    E, H, d = cfg.n_embd, cfg.n_head, cfg.head_dim
    ks = jax.random.split(key, 5)
    lam = lambda k: jax.random.normal(k, (d,), jnp.float32) * cfg.lambda_std
    return {"lambda_q1": lam(ks[0]), "lambda_k1": lam(ks[1]),
            "lambda_q2": lam(ks[2]), "lambda_k2": lam(ks[3]),
            "diff_norm": {"scale": jnp.ones((2 * d,), jnp.float32)},
            "o_proj": _biased(ks[4], H * d, E)}


def init_params(rng, cfg: Phi4FlashConfig) -> Dict[str, Any]:
    """Normal(0, 0.02) matrices, biases 0, LayerNorm gains 1; A_log =
    log(1..N) a channel, D = 1, the bias of dt the inverse softplus of dt
    drawn log-uniform in [`dt_min`, `dt_max`] and floored at `dt_floor`; the
    taps and their bias uniform(+-K^-1/2), as a depthwise `Conv1d` of K taps
    is left; the lambda vectors Normal(0, `lambda_std`).  Names are those
    `parallel/sharding.py:infer_param_logical_dims` lays out.  The head is
    the embedding: there is no `lm_head`."""
    E, W, C = cfg.n_embd, cfg.dense_width, cfg.channels
    H, Hkv, d = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    N, R, K = cfg.d_state, cfg.dt_rank, cfg.d_conv
    keys = jax.random.split(rng, 1 + cfg.n_layer)
    params = {
        "embed_tokens": {
            "embedding": normal_kernel(keys[0], cfg.vocab_size, E)["kernel"]},
        "norm_f": _norm(E),
    }
    for i in range(cfg.n_layer):
        ks = jax.random.split(keys[1 + i], 10)
        layer = {
            "norm1": _norm(E), "norm2": _norm(E),
            "mlp": {"gate_proj": normal_kernel(ks[0], E, W),
                    "up_proj": normal_kernel(ks[1], E, W),
                    "down_proj": normal_kernel(ks[2], W, E)}}
        kind = cfg.kind(i)
        if kind == MAMBA:
            bound = K ** -0.5
            dt = jnp.exp(jax.random.uniform(
                ks[6], (C,), jnp.float32, math.log(cfg.dt_min),
                math.log(cfg.dt_max)))
            dt = jnp.maximum(dt, cfg.dt_floor)
            layer[MAMBA] = {
                "in_proj": normal_kernel(ks[3], E, 2 * C),
                "conv": {
                    "kernel": jax.random.uniform(
                        ks[4], (C, K), jnp.float32, -bound, bound),
                    "bias": jax.random.uniform(
                        ks[5], (C,), jnp.float32, -bound, bound)},
                "x_proj": normal_kernel(ks[7], C, R + 2 * N),
                "dt_proj": dict(normal_kernel(ks[8], R, C),
                                bias=dt + jnp.log(-jnp.expm1(-dt))),
                "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                    1, N + 1, dtype=jnp.float32)), (C, N)),
                "D": jnp.ones((C,), jnp.float32),
                "out_proj": normal_kernel(ks[9], C, E),
            }
        elif kind in (WINDOW, FULL):
            layer[kind] = {"q_proj": _biased(ks[3], E, H * d),
                           "k_proj": _biased(ks[4], E, Hkv * d),
                           "v_proj": _biased(ks[5], E, Hkv * d),
                           **_diff(ks[6], cfg)}
        elif kind == CROSS:
            layer[CROSS] = {"q_proj": _biased(ks[3], E, H * d),
                            **_diff(ks[6], cfg)}
        else:
            layer[GMU] = {"in_proj": normal_kernel(ks[3], E, C),
                          "out_proj": normal_kernel(ks[4], C, E)}
        params[f"layer_{i}"] = layer
    return params


def _mamba(h, p, cfg: Phi4FlashConfig):
    """-> (the mixer's result (B, S, E), what it may hand on (B, S, C))."""
    C, N, R = cfg.channels, cfg.d_state, cfg.dt_rank
    with jax.named_scope("in_proj"):
        uz = named(h @ p["in_proj"]["kernel"].astype(h.dtype), "ssm/in_proj")
    with jax.named_scope("conv"):
        # u where it lies, W_in's first C columns
        (u,) = causal_conv(uz, p["conv"], jax.nn.silu, widths=(C,))
    with jax.named_scope("x_proj"):
        rbc = u @ p["x_proj"]["kernel"].astype(u.dtype)
        dt = rbc[..., :R] @ p["dt_proj"]["kernel"].astype(u.dtype)
    with jax.named_scope("scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_proj"]["bias"])
        y = selective_scan(u, dt, -jnp.exp(p["A_log"]), rbc[..., R:R + N],
                           rbc[..., R + N:], p["D"])
    gated = (y.astype(jnp.float32)
             * jax.nn.silu(uz[..., C:].astype(jnp.float32))).astype(h.dtype)
    with jax.named_scope("out_proj"):
        return gated @ p["out_proj"]["kernel"].astype(h.dtype), \
            _memory(y, gated)


def _memory(y, gated):
    """What a Mamba-1 layer hands on: the scan's y, before the gate."""
    return y


def _heads(h, p, name, head_dim):
    """h W + b as heads, (B, S, heads, d); the product carries the name a
    recomputed layer may keep it by."""
    B, S, _ = h.shape
    proj = p[name]
    y = named(h @ proj["kernel"].astype(h.dtype), "attention/qkv") \
        + proj["bias"].astype(h.dtype)
    return y.reshape(B, S, -1, head_dim)


def _halves(x):
    """(B, S, heads, d) -> (the even heads, the odd heads)."""
    B, S, H, d = x.shape
    pairs = x.reshape(B, S, H // 2, 2, d)
    return pairs[:, :, :, 0], pairs[:, :, :, 1]


def _keys_values(h, p, cfg):
    """-> {"k1", "k2": (B, S, H_kv / 2, d), "v": (B, S, H_kv / 2, 2 d)}."""
    k1, k2 = _halves(_heads(h, p, "k_proj", cfg.head_dim))
    v = _heads(h, p, "v_proj", 2 * cfg.head_dim)    # [v1 | v2] a pair
    return {"k1": k1, "k2": k2, "v": v}


def _rule(cfg: Phi4FlashConfig, kind: str) -> BlockRule:
    """The keys a kind of layer attends, as the kernels' rule."""
    return BlockRule(window=cfg.window if kind == WINDOW else None)


def _lambda(p, lambda_init):
    """exp(lq1 . lk1) - exp(lq2 . lk2) + lambda0, a float32 scalar."""
    f32 = lambda name: p[name].astype(jnp.float32)
    return jnp.exp(jnp.sum(f32("lambda_q1") * f32("lambda_k1"))) \
        - jnp.exp(jnp.sum(f32("lambda_q2") * f32("lambda_k2"))) + lambda_init


def _pair_norm(o, eps):
    """An RMSNorm over each pair's 2 d, without its gain."""
    return o * jax.lax.rsqrt(
        jnp.mean(jnp.square(o), axis=-1, keepdims=True) + eps)


def _scale(lambda_init):
    return 1.0 - lambda_init


def _differential(q, kv, p, cfg: Phi4FlashConfig, rule, lambda_init):
    """q (B, S, H, d) and the keys and values of `_keys_values` -> the
    mixer's result (B, S, E): the two softmaxes' difference, normed, scaled
    and through W_o."""
    B, S, H, d = q.shape
    tracing.count("attention.diff_pairs", H // 2)
    q1, q2 = _halves(q)
    with jax.named_scope("kernel"):
        a1 = attention(q1, kv["k1"], kv["v"], causal=rule)
        a2 = attention(q2, kv["k2"], kv["v"], causal=rule)
    with jax.named_scope("diff"):
        o = a1.astype(jnp.float32) \
            - _lambda(p, lambda_init) * a2.astype(jnp.float32)
        o = (_pair_norm(o, cfg.rms_eps) * (
            p["diff_norm"]["scale"] * _scale(lambda_init))).astype(q.dtype)
    with jax.named_scope("out"):
        out = p["o_proj"]
        return named(o.reshape(B, S, H * d) @ out["kernel"].astype(q.dtype),
                     "attention/out") + out["bias"].astype(q.dtype)


def _gmu(h, memory, p):
    gate = named(h @ p["in_proj"]["kernel"].astype(h.dtype), "ffn/hidden")
    gated = (memory.astype(jnp.float32)
             * jax.nn.silu(gate.astype(jnp.float32))).astype(h.dtype)
    return gated @ p["out_proj"]["kernel"].astype(h.dtype)


def _hands_on_keys(kind: str) -> bool:
    """The full attention layer's keys and values are the cross layers'."""
    return kind == FULL


def _layer(x, p, cfg: Phi4FlashConfig, shared, i: int):
    """-> (x, None, what this and earlier layers hand on): the layer held
    at ``i``, its kind the name of its mixer's subtree."""
    shared = dict(shared or {})
    kind = cfg.kind(i)
    h = layer_norm(x, p["norm1"], cfg.norm_eps)
    if kind == MAMBA:
        with jax.named_scope("mamba"):
            y, memory = _mamba(h, p[MAMBA], cfg)
        if cfg.first_layer + i == cfg.n_published // 2:
            shared["memory"] = memory
    elif kind == GMU:
        tracing.count("shared.memory_readers")
        with jax.named_scope("gmu"):
            y = _gmu(h, shared["memory"], p[GMU])
    else:
        with jax.named_scope("attention"):
            if kind == CROSS:
                tracing.count("shared.kv_readers")
                kv = shared
                with jax.named_scope("cross"):
                    q = _heads(h, p[CROSS], "q_proj", cfg.head_dim)
            else:
                with jax.named_scope("qkv"):
                    q = _heads(h, p[kind], "q_proj", cfg.head_dim)
                    kv = _keys_values(h, p[kind], cfg)
                if _hands_on_keys(kind):
                    shared.update(kv)
            y = _differential(q, kv, p[kind], cfg, _rule(cfg, kind),
                              cfg.lambda_init(i))
    x = x + y
    h = layer_norm(x, p["norm2"], cfg.norm_eps)
    with jax.named_scope("ffn"), jax.named_scope("dense"):
        x = x + dense_ffn(h, p["mlp"], swiglu)
    return x, None, shared or None


def hidden(params, tokens, cfg: Phi4FlashConfig, streams: bool = False):
    """tokens (B, S) int32 -> (B, S, E) after the final norm; with
    ``streams`` also the stream after each of the layers held, in order."""
    def watched(x, p, cfg, shared, i):
        x, _, shared = _layer(x, p, cfg, shared, i)
        return x, x, shared

    x, seconds = trunk(params, tokens, watched if streams else _layer, cfg,
                       shared=True)
    return (x, seconds) if streams else x


def forward(params, tokens, cfg: Phi4FlashConfig):
    """tokens (B, S) int32 -> logits (B, S, rows held) float32, by the
    embedding's own rows."""
    x = hidden(params, tokens, cfg)
    rows = params["embed_tokens"]["embedding"].astype(cfg.compute_dtype)
    return jnp.matmul(x, rows.T, preferred_element_type=jnp.float32)


def loss_fn(params, batch, cfg: Phi4FlashConfig):
    """batch {"tokens": (B, S + 1)} -> (next-token cross-entropy over the
    rows of the vocabulary held here, {"loss": the same}).  The head's
    logits are made `cfg.loss_chunk_rows` rows at a time and never all
    held."""
    tokens = batch["tokens"]
    x = hidden(params, tokens[:, :-1], cfg)
    xent = head_and_loss(x, params["embed_tokens"], tokens[:, 1:],
                         cfg.loss_chunk_rows)
    return xent, {"loss": xent}


def make_train_step(cfg: Phi4FlashConfig, optimizer):
    """train_step(params, opt_state, batch) -> (params, opt_state, out), to
    be jitted with its shardings and `donate_argnums=(0, 1)` as
    `gpt2.make_train_step`'s.  `out` carries "loss"."""
    return train_step(lambda params, batch: loss_fn(params, batch, cfg),
                      optimizer, cfg.compute_dtype)


def attended_pairs(seq_len: int, window) -> int:
    """(query, key) pairs a causal row rule attends over a sequence: the
    triangle, or under a window the triangle of its first W rows and W a
    row after."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def scan_flops_per_token(cfg: Phi4FlashConfig) -> float:
    """Forward operations a token of ONE Mamba-1 layer's recurrence, a
    multiply and an add counted as two: the decay's exponent dt A, the
    decay's product with the state and the input's outer product added,
    the sum with C_t (each C N pairs), dt u and D u (each C)."""
    return (1 + 2 + 1 + 2) * cfg.channels * cfg.d_state + 3 * cfg.channels


def count_flops_per_token(cfg: Phi4FlashConfig, seq_len: int) -> float:
    """Training (forward + backward) operations per token HERE: 6 x the
    parameters a token multiplies (the tied embedding once, as the head;
    every layer's feed-forward; a Mamba-1 mixer's W_in, W_x, W_dt, W_out and
    taps; an attention layer's four matrices, a cross layer's two, a memory
    unit's two) + the attention products over the pairs each layer's rule
    attends, two score maps a pair of heads at d and two value products at
    2 d, forward once and backward twice + the recurrences."""
    E, C, d = cfg.n_embd, cfg.channels, cfg.head_dim
    kinds = [cfg.kind(i) for i in range(cfg.n_layer)]
    mixer = {
        MAMBA: E * 2 * C + C * cfg.d_conv + C * (cfg.dt_rank + 2 * cfg.d_state)
        + cfg.dt_rank * C + C * E,
        WINDOW: 2 * E * cfg.n_head * d + 2 * E * cfg.n_kv_head * d,
        CROSS: 2 * E * cfg.n_head * d,
        GMU: 2 * E * C,
    }
    mixer[FULL] = mixer[WINDOW]
    n = cfg.vocab_size * E + sum(
        mixer[k] + 3 * E * cfg.dense_width for k in kinds)
    pairs = sum(attended_pairs(
        seq_len, cfg.window if k == WINDOW else None) / seq_len
        for k in kinds if k in (WINDOW, FULL, CROSS))
    # a pair of heads: q1 k1' and q2 k2' at d, two products with [v1 | v2]
    attention_ops = 6 * pairs * (cfg.n_head // 2) * 2 * (d + 2 * d)
    return 6 * n + attention_ops \
        + 3 * kinds.count(MAMBA) * scan_flops_per_token(cfg)

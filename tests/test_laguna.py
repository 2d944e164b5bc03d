"""The `laguna` model (`ray_tpu/models/laguna.py`: a trunk whose layers are of
two kinds that differ in their rule, their query heads, their rotary table
and how much of a head it turns; a gate a head on attention's result; a
dense first layer and a sigmoid-routed mixture beside a shared expert
after) against the plain reference (`benchmark/reference/laguna.py`: float32
`jax.numpy`, each kind's rule written out, attention as one masked softmax,
the half-head split written out, the experts as a loop over those held), and
the flash kernels at groups of 3 and 4 query heads under a window narrower
than a pair of tiles, at small sizes on the CPU: five layers (full + dense,
three sliding, full), hidden 64, 6 and 8 query heads on 2 key/value heads of
16, 8 experts 24 wide of which 4 are held, 3 a token, vocabulary 512,
sequences of 128 under a window of 48, YaRN by 4 over 32 original positions
at dim 8, seeded random weights.

The matrices are drawn four times as wide as the assumed 0.02: at 0.02 and
these widths an operator's output is a thousandth of the residual stream
and a fault would hide under any tolerance.
"""

import dataclasses
import math
import warnings

import jax
import jax.numpy as jnp
import model_kit as kit
import numpy as np
import pytest
from model_kit import max_diff

from benchmark.families.laguna import groups_of, to_reference
from benchmark.reference import laguna as reference
from ray_tpu.models import laguna as model
from ray_tpu.models import layers
from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.flash_attention import BlockRule
from ray_tpu.ops.moe import ROUTING_BIAS, trained_by
from ray_tpu.util import tracing

F32 = dataclasses.replace(model.LAGUNA_TINY, held=(2, 4),
                          compute_dtype=jnp.float32)
GROUPS = groups_of(F32.layer_types, F32.mlp_layer_types)
SIZES = reference.Sizes(
    n_kv_head=2, head_dim=16, top_k=3,
    groups=tuple(reference.FULL if kind == model.FULL else reference.SLIDING
                 for kind, _, _ in GROUPS),
    window=48, held_first=2, rotary_full=8, rotary_sliding=16,
    yarn_factor=4.0, yarn_original=32, yarn_beta_fast=8.0,
    query_block=32, head_block=64)
BATCH, SEQ = 2, 128
OPTIMIZER = {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
             "weight_decay": 0.1}
# float32 compute: the routing is identical and only summation order
# differs (flash tiles under a rule against a whole softmax, sorted groups
# against a loop over experts)
F32_TOL = 2e-5
SEEDS = [0, 1, 2147483900]


pytestmark = pytest.mark.usefixtures("highest_precision")


@kit.once
def make_params(seed=0, cfg=F32):
    """Matrices four times as wide as drawn; routing biases that differ by
    expert, so that they pick."""
    return kit.drawn(
        lambda key: model.init_params(key, cfg), seed,
        [kit.Vector((f"layer_{i}", "moe", "router", ROUTING_BIAS), 0.05,
                    key=seed + i, start=0.0) for i in cfg.moe_layers])


def make_tokens(seed=0):
    return kit.tokens(1000 + seed % 1000, BATCH, SEQ, 512)


@kit.once
def results(which, seed):
    """(the cross-entropy, every row's cross-entropy, rows sent to the
    experts, the loss's gradients in the reference's layout) of the system
    in float32 or of the reference, each one jitted program."""
    params, tokens = make_params(seed), make_tokens(seed)
    with jax.default_matmul_precision("highest"):
        if which == "system":
            def run(params):
                logits, _ = model.forward(params, tokens[:, :-1], F32)
                ce = -jnp.take_along_axis(
                    jax.nn.log_softmax(logits), tokens[:, 1:, None],
                    axis=-1)[..., 0]
                (_, parts), grads = jax.value_and_grad(
                    model.loss_fn, has_aux=True)(
                        params, {"tokens": tokens}, F32)
                return (parts["loss"], ce, parts["expert_rows"],
                        to_reference(grads)[0])
            return jax.jit(run)(params)

        def run(params, biases):
            (loss, (rows, ce)), grads = jax.value_and_grad(
                reference.losses, has_aux=True)(params, biases, tokens, SIZES)
            return loss, ce, rows, grads
        return jax.jit(run)(*to_reference(params))


# -- the system against the plain reference ---------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_the_losses_and_the_rows_match_the_reference(seed):
    got, want = results("system", seed), results("reference", seed)
    assert abs(float(got[0]) - float(want[0])) < F32_TOL      # cross-entropy
    assert max_diff(got[1], want[1]) < 5 * F32_TOL            # the rows' CE
    assert (np.asarray(got[2]) == np.asarray(want[2])).all()  # expert rows
    assert np.asarray(want[2]).shape == (4, 8)     # the four sparse layers
    assert 5.5 < float(want[0]) < 7.5       # near log(512)


@pytest.mark.parametrize("seed", SEEDS)
def test_gradients_of_every_leaf_match(seed):
    got, want = results("system", seed)[3], results("reference", seed)[3]
    flat = jax.tree_util.tree_flatten_with_path(want)[0]
    assert len(flat) == len(jax.tree.leaves(got))
    for (path, w), g in zip(flat, jax.tree.leaves(got)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-6
        assert max_diff(g, w) < 2e-4 * scale + 1e-6, \
            (jax.tree_util.keystr(path), max_diff(g, w), scale)
        assert scale > 1e-6, jax.tree_util.keystr(path)   # every leaf trains


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_three_steps_match_the_reference_program(seed):
    """AdamW on every leaf but the routing biases, which move by their
    rule, on both sides."""
    params, tokens = make_params(seed), make_tokens(seed)
    want = reference.first_losses(
        *kit.own(to_reference(params)),
        jnp.stack([tokens] * 3), SIZES, OPTIMIZER)
    optimizer = trained_by(reference.adamw(OPTIMIZER))
    step = jax.jit(model.make_train_step(F32, optimizer))
    opt_state = optimizer.init(params)
    before = params["layer_1"]["moe"]["router"][ROUTING_BIAS]
    for n in range(3):
        params, opt_state, out = step(params, opt_state, {"tokens": tokens})
        assert abs(float(out["loss"]) - want[n]) < 1e-4, (n, want[n])
    assert want[2] < want[0]
    moved = params["layer_1"]["moe"]["router"][ROUTING_BIAS] - before
    assert float(jnp.max(jnp.abs(moved))) <= 3 * F32.bias_update_speed + 1e-7
    assert float(jnp.max(jnp.abs(moved))) > 0


def test_a_recomputed_stack_of_two_shapes_of_layer_is_the_same_step():
    cfg = dataclasses.replace(F32, remat=True)
    params, tokens = make_params(), make_tokens()
    optimizer = trained_by(reference.adamw(OPTIMIZER))
    outs = []
    for c in (F32, cfg):
        step = jax.jit(model.make_train_step(c, optimizer))
        new, _, out = step(params, optimizer.init(params),
                           {"tokens": tokens})
        outs.append((out["loss"], new))
    assert abs(float(outs[0][0]) - float(outs[1][0])) < 1e-6
    assert max(jax.tree.leaves(jax.tree.map(max_diff, outs[0][1],
                                            outs[1][1]))) < 1e-5


def test_bfloat16_compute_stays_close():
    seed = 3
    cfg = dataclasses.replace(F32, compute_dtype=jnp.bfloat16)
    params, tokens = make_params(seed), make_tokens(seed)
    cast = layers.cast_weights(params, jnp.bfloat16)
    _, parts = jax.jit(lambda p: model.loss_fn(
        p, {"tokens": tokens}, cfg))(cast)
    want = results("reference", seed)[0]
    assert abs(float(parts["loss"]) - float(want)) < 0.05


def test_the_trunk_and_the_plan_take_three_shapes_of_layer():
    """The kinds' leaves have other shapes (W_q, W_o and W_g by the kind's
    heads) and layer 0's feed-forward other leaves: `trunk` walks once,
    `keep_plan` takes its marks once a shape of layer (three here: full +
    dense, sliding + sparse, full + sparse) and `jax.checkpoint` traces
    each layer once."""
    params = make_params()
    kinds = [model.SLIDING if model.SLIDING in params[f"layer_{i}"]
             else model.FULL for i in range(5)]
    assert tuple(kinds) == F32.layer_types
    width = lambda i, kind, name: \
        params[f"layer_{i}"][kind][name]["kernel"].shape
    assert width(0, model.FULL, "q_proj") == (64, 6 * 16)
    assert width(1, model.SLIDING, "q_proj") == (64, 8 * 16)
    assert width(0, model.FULL, "g_proj") == (64, 6)
    assert width(1, model.SLIDING, "g_proj") == (64, 8)
    assert width(4, model.FULL, "o_proj") == (6 * 16, 64)
    assert width(1, model.SLIDING, "k_proj") == (64, 2 * 16) \
        == width(0, model.FULL, "v_proj")
    assert "mlp" in params["layer_0"] and "moe" not in params["layer_0"]
    assert all("moe" in params[f"layer_{i}"] for i in range(1, 5))
    traced = []
    layer = model._layer

    def counting(x, p, cfg):
        traced.append((model.SLIDING if model.SLIDING in p else model.FULL,
                       "mlp" in p))
        return layer(x, p, cfg)

    cfg = dataclasses.replace(F32, remat=True)
    plans = []
    with layers.assume_memory_limit(1 << 30, plans):
        jax.eval_shape(lambda p: layers.trunk(
            p, make_tokens()[:, :-1], counting, cfg)[0], params)
    # three shapes of layer for the plan's marks, and `jax.checkpoint`
    # traces one body a shape of layer for the five layers walked
    assert len(traced) == 3 + 3
    assert set(traced) == {(model.FULL, True), (model.SLIDING, False),
                           (model.FULL, False)}
    (plan,) = plans
    assert "attention/gate" in plan["marked"]
    # the gate's product, (B, S, H) float32 a layer: 6, 8, 8, 8 and 6 heads
    assert plan["marked"]["attention/gate"] == BATCH * SEQ * 4 * 36


# -- the window, by perturbation ----------------------------------------------

def _first_layer(tokens, kind=model.SLIDING, params=None, cfg=F32):
    """The attention of the first layer of ``kind`` on the embedded tokens,
    (B, S, E)."""
    params = make_params() if params is None else params
    i = cfg.layer_types.index(kind)
    p = params[f"layer_{i}"]
    x = params["embed_tokens"]["embedding"][tokens]
    u = layers.rms_norm(x, p["input_norm"], cfg.rms_eps)
    return model._attention(u, p[kind], cfg, kind)


@pytest.mark.parametrize("j", [0, 17, 60, 100])
def test_a_token_moves_a_sliding_layers_row_iff_the_window_holds_it(j):
    """A change of token x_j moves layer 1's output at row i iff
    i - W < j <= i."""
    tokens = make_tokens()[:, :-1]
    other = tokens.at[0, j].set((tokens[0, j] + 1) % 512)
    moved = jnp.max(jnp.abs(_first_layer(tokens) - _first_layer(other)),
                    axis=-1)
    last = min(j + 48, SEQ)         # rows j .. j + W - 1 hold key j
    assert float(jnp.max(moved[0, :j], initial=0.0)) == 0.0
    assert float(jnp.min(moved[0, j:last])) > 0.0
    assert float(jnp.max(moved[0, last:], initial=0.0)) == 0.0
    assert float(jnp.max(moved[1])) == 0.0      # the other sequence


def test_a_token_moves_every_later_row_of_a_full_layer():
    tokens = make_tokens()[:, :-1]
    other = tokens.at[0, 17].set((tokens[0, 17] + 1) % 512)
    moved = jnp.max(jnp.abs(_first_layer(tokens, model.FULL)
                            - _first_layer(other, model.FULL)), axis=-1)
    assert float(jnp.max(moved[0, :17])) == 0.0
    assert float(jnp.min(moved[0, 17:])) > 0.0


# -- the rotary tables, a kind each -------------------------------------------

def _qk(cfg, kind, positions):
    """q and k of `attention_qkv` under ``kind``'s table with unit
    projections' stand-ins: x itself, head by head."""
    H, D = model.heads(cfg, kind), cfg.head_dim
    E = cfg.n_kv_head * D
    x = jax.random.normal(jax.random.PRNGKey(5), (1, len(positions), E))
    p = {"q_proj": {"kernel": jnp.tile(jnp.eye(E), (1, H // cfg.n_kv_head))},
         "k_proj": {"kernel": jnp.eye(E)}, "v_proj": {"kernel": jnp.eye(E)}}
    q, k, _ = layers.attention_qkv(
        x, p, D, None, lambda S: jnp.asarray(positions),
        *model.rotary(cfg, kind))
    return x.reshape(1, len(positions), cfg.n_kv_head, D), q, k


def test_a_full_layers_last_dims_pass_and_its_first_follow_yarn_at_dim_64():
    """At the published keys: the last 64 dims of every q and k head are
    untouched by position; the first 64 turn by YaRN's closed form at dim
    64 (not 128), cos and sin times the attention factor."""
    cfg = dataclasses.replace(model.LAGUNA_XS_2, compute_dtype=jnp.float32)
    positions = [0, 1, 777, 16383]
    x, q, k = _qk(cfg, model.FULL, positions)
    assert q.shape == (1, 4, 48, 128) and k.shape == (1, 4, 8, 128)
    assert (np.asarray(k[..., 64:]) == np.asarray(x[..., 64:])).all()
    assert (np.asarray(q[..., :8, 64:]) == np.asarray(x[..., 64:])).all()
    # the closed form, by hand
    turns = lambda n: 64 * math.log(4096 / (2 * math.pi * n)) \
        / (2 * math.log(500000))
    low, high = math.floor(turns(64)), math.ceil(turns(1))
    assert (low, high) == (5, 16)
    i = np.arange(32)
    base = 500000.0 ** (i / 32)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    freqs = (1 - ramp) / base + ramp / (64 * base)
    c = 1.4158883083359672
    assert c == pytest.approx(0.1 * math.log(64) + 1, rel=1e-12)
    got_freqs, got_c, width = model.rotary(cfg, model.FULL)
    np.testing.assert_allclose(got_freqs, freqs, rtol=1e-6)
    assert (got_c, width) == (c, 64)
    angle = np.asarray(positions, np.float64)[:, None] * freqs[None]
    x1, x2 = np.asarray(x[0, :, :, :32]), np.asarray(x[0, :, :, 32:64])
    cos, sin = (c * f(angle)[:, None, :] for f in (np.cos, np.sin))
    want = np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    # float32 angles of up to 16,383 radians: a few 1e-3 of a turn
    np.testing.assert_allclose(np.asarray(k[0, ..., :64]), want, atol=2e-2)
    np.testing.assert_allclose(np.asarray(k[0, :2, :, :64]), want[:2],
                               atol=1e-5)
    # and the reference's own lines give the same table
    r, ref_freqs, ref_c = reference.frequencies(
        reference.FULL, reference.Sizes(n_kv_head=8, head_dim=128, top_k=8,
                                        groups=(0,)))
    np.testing.assert_allclose(np.asarray(ref_freqs), freqs, rtol=2e-6)
    assert r == 64 and float(ref_c) == pytest.approx(c, rel=1e-6)


def test_the_two_kinds_tables_differ_in_base_and_in_width():
    cfg = dataclasses.replace(model.LAGUNA_XS_2, compute_dtype=jnp.float32)
    theta, scale, width = model.rotary(cfg, model.SLIDING)
    assert (theta, scale, width) == (1e4, None, 128)
    positions = [0, 3, 500]
    x, q, k = _qk(cfg, model.SLIDING, positions)
    assert q.shape == (1, 3, 64, 128)
    want = layers.rope(x, jnp.asarray(positions), 1e4)
    assert max_diff(k, want) == 0.0
    # every dim of a sliding layer's head turns: none passes
    assert float(jnp.min(jnp.max(jnp.abs(k - x)[0, 1:], axis=(0, 1)))) > 0
    # the full layers' table is no stretch of the sliding layers': another
    # base under it
    full = model.rotary(cfg, model.FULL)[0]
    plain = 1e4 ** (-np.arange(32) / 32)
    assert full[0] == plain[0] == 1.0
    assert abs(full[1] - 500000.0 ** (-1 / 32)) < 1e-7      # fast: unscaled
    assert abs(full[1] - plain[1]) > 0.05
    r, ref_freqs, ref_c = reference.frequencies(
        reference.SLIDING, reference.Sizes(n_kv_head=8, head_dim=128,
                                           top_k=8, groups=(1,)))
    np.testing.assert_allclose(np.asarray(ref_freqs),
                               1e4 ** (-np.arange(64) / 64), rtol=2e-6)
    assert (r, ref_c) == (128, 1.0)


def test_the_references_half_head_split_is_the_systems():
    x = jax.random.normal(jax.random.PRNGKey(2), (7, 3, 16))
    positions = jnp.asarray([0, 1, 5, 9, 33, 100, 127])
    freqs, c = layers.yarn_frequencies(8, 5e5, 4.0, 32, 8.0, 1.0)
    want = reference.rope_first_dims(x, positions, 8, jnp.asarray(freqs), c)
    got = jnp.concatenate([layers.rope(x[None, ..., :8], positions, freqs,
                                       scale=c)[0], x[..., 8:]], axis=-1)
    assert max_diff(got, want) < 1e-6
    assert (np.asarray(want[..., 8:]) == np.asarray(x[..., 8:])).all()


# -- the gate -----------------------------------------------------------------

@pytest.mark.parametrize("kind", [model.FULL, model.SLIDING])
def test_a_zero_gate_halves_the_attentions_result_exactly(kind):
    """W_g = 0: g = sigmoid(0) = 1/2 on every head and token, and W_o is
    linear; the ungated operator is `attention_out` with no gate input."""
    params = make_params()
    i = F32.layer_types.index(kind)
    p = params[f"layer_{i}"][kind]
    tokens = make_tokens()[:, :-1]
    zero = {**params, f"layer_{i}": {**params[f"layer_{i}"], kind: {
        **p, "g_proj": {"kernel": jnp.zeros_like(p["g_proj"]["kernel"])}}}}
    x = params["embed_tokens"]["embedding"][tokens]
    u = layers.rms_norm(x, params[f"layer_{i}"]["input_norm"], F32.rms_eps)
    q, k, v = layers.attention_qkv(u, p, 16, None, jnp.arange,
                                   *model.rotary(F32, kind))
    o = model.attention(q, k, v, causal=model.rule(F32, kind))
    ungated = layers.attention_out(o, p)
    halved = _first_layer(tokens, kind, zero)
    assert (np.asarray(halved) == np.asarray(layers.attention_out(
        0.5 * o, p))).all()
    assert max_diff(halved, 0.5 * ungated) < 1e-6
    gated = _first_layer(tokens, kind, params)
    assert max_diff(gated, halved) > 1e-3       # a drawn W_g gates by head


def test_the_gate_reads_the_normed_input_a_head_and_its_gradient_arrives():
    params, tokens = make_params(), make_tokens()
    grads = jax.grad(lambda p: model.loss_fn(p, {"tokens": tokens}, F32)[0])(
        params)
    for i, kind in enumerate(F32.layer_types):
        g = grads[f"layer_{i}"][kind]["g_proj"]["kernel"]
        assert g.shape == (64, model.heads(F32, kind))
        assert float(jnp.min(jnp.max(jnp.abs(g), axis=0))) > 0   # every head
    # one scalar a head and token: o_h <- sigmoid(u W_g)_h o_h
    p = params["layer_1"][model.SLIDING]
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 64))
    o = jax.random.normal(jax.random.PRNGKey(4), (1, 8, 8, 16))
    g = jax.nn.sigmoid(u @ p["g_proj"]["kernel"])
    want = (o * g[..., None]).reshape(1, 8, 128) @ p["o_proj"]["kernel"]
    assert max_diff(layers.attention_out(o, p, gate_input=u), want) < 1e-5


def test_the_new_calls_are_counted_on_the_job_timeline():
    """`attention.gated` a gated call traced, `rope.partial` a call that
    turns a part of a head, beside `rope.scaled`."""
    params, tokens = make_params(), make_tokens()[:, :-1]
    names = ("attention.gated", "rope.partial", "rope.scaled")
    with tracing.timeline_span("train.fit", root=True) as job:
        before = [tracing.counter(name) for name in names]
        jax.eval_shape(lambda: _first_layer(tokens, model.SLIDING, params))
        # no part of a sliding head passes; its table is a base
        assert [tracing.counter(n) - b for n, b in zip(names, before)] \
            == [1, 0, 0]
        jax.eval_shape(lambda: _first_layer(tokens, model.FULL, params))
        # q and k of the full layer: half a head each under YaRN's table
        assert [tracing.counter(n) - b for n, b in zip(names, before)] \
            == [2, 1, 2]
    tracing.timeline_take(job.trace_id)


# -- every other caller's program is what it was ------------------------------

def _parent_attention_qkv(x, p, head_dim, eps=None, positions=None,
                          theta=None, scale=None):
    """`layers.attention_qkv` as the parent commit wrote it."""
    B, S, _ = x.shape
    with jax.named_scope("qkv"):
        q, k, v = layers.named(tuple(
            (x @ p[name]["kernel"].astype(x.dtype)).reshape(
                B, S, -1, head_dim)
            for name in ("q_proj", "k_proj", "v_proj")), "attention/qkv")
        at = None if positions is None else positions(S)

        def turned(heads, norm):
            if norm in p:
                heads = layers.rms_norm(heads, p[norm], eps)
            return heads if at is None else layers.rope(heads, at, theta,
                                                        scale=scale)
        return turned(q, "q_norm"), turned(k, "k_norm"), v


def _parent_attention_out(o, p):
    B, S = o.shape[:2]
    with jax.named_scope("out"):
        return layers.named(o.reshape(B, S, -1)
                            @ p["o_proj"]["kernel"].astype(o.dtype),
                            "attention/out")


@pytest.mark.parametrize("norms", [False, True])
@pytest.mark.parametrize("how", ["no positions", "a base", "a table",
                                 "the whole head named"])
def test_every_other_callers_jaxpr_of_attention_qkv_is_what_it_was(
        norms, how):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 32))
    kernel = lambda i, width: {"kernel": jax.random.normal(
        jax.random.PRNGKey(i), (32, width))}
    p = {"q_proj": kernel(1, 64), "k_proj": kernel(2, 32),
         "v_proj": kernel(3, 32)}
    if norms:
        p.update(q_norm=layers.unit_scale(16), k_norm=layers.unit_scale(16))
    table = 1e4 ** (-np.arange(8, dtype=np.float32) / 8)
    args = {"no positions": (1e-6,), "a base": (1e-6, jnp.arange, 5e5),
            "a table": (1e-6, jnp.arange, table, 1.25),
            "the whole head named": (1e-6, jnp.arange, table, 1.25)}[how]
    more = {"rotary_dim": 16} if how == "the whole head named" else {}
    with tracing.timeline_span("train.fit", root=True) as job:
        got = jax.make_jaxpr(lambda x, p: layers.attention_qkv(
            x, p, 16, *args, **more))(x, p)
        assert tracing.counter("rope.partial") == 0
    tracing.timeline_take(job.trace_id)
    want = jax.make_jaxpr(lambda x, p: _parent_attention_qkv(
        x, p, 16, *args))(x, p)
    assert str(got) == str(want)


def test_every_other_callers_jaxpr_of_attention_out_is_what_it_was():
    o = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, 16))
    p = {"o_proj": {"kernel": jax.random.normal(jax.random.PRNGKey(1),
                                                (64, 32))}}
    assert str(jax.make_jaxpr(layers.attention_out)(o, p)) \
        == str(jax.make_jaxpr(_parent_attention_out)(o, p))
    with tracing.timeline_span("train.fit", root=True) as job:
        layers.attention_out(o, p)
        assert tracing.counter("attention.gated") == 0
    tracing.timeline_take(job.trace_id)


# -- the share of the experts -------------------------------------------------

def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """One sparse layer with the router's 256 columns, 8 a token: the parts
    that its sixteen shares of 16 experts give (`first` 0, 16, .. 240),
    the shared expert, which every chip computes alike, counted once, add
    up to what the uncut reference gives for the whole layer, every share
    seeing the routing over all 256."""
    cfg = dataclasses.replace(F32, n_experts=256, top_k=8, expert_width=8,
                              held=None)
    params = make_params(cfg=cfg)
    p = params["layer_1"]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(9), (BATCH, SEQ, cfg.n_embd))
    routed_alone = {k: v for k, v in p.items() if k != "shared"}
    total, rows = 0, []
    for first in range(0, 256, 16):
        share = {**routed_alone, **{k: p[k][first:first + 16]
                                    for k in ("wi_gate", "wi_up", "wo")}}
        y, sent = layers.routed_layer(u, share, model._route(cfg), 256,
                                      (first, 16), layers.swiglu)
        total += y
        rows.append(sent)
    # what every chip computes alike, once
    total += layers.dense_ffn(u, p["shared"], layers.swiglu)
    whole, biases = to_reference(params)
    layer = jax.tree.map(lambda leaf: leaf[0], whole["groups"][1])
    want, want_rows = reference.moe(
        u.reshape(-1, cfg.n_embd), layer, biases[1][0],
        SIZES._replace(top_k=8, held_first=0))
    assert max_diff(total.reshape(want.shape), want) < F32_TOL
    for sent in rows:
        assert (np.asarray(sent) == np.asarray(want_rows)).all()
    assert int(np.asarray(want_rows).sum()) == BATCH * SEQ * 8
    # one share alone is not the layer, and with its shared expert a share
    # is what a chip's layer gives
    assert max_diff(y.reshape(want.shape), want) > 0.01
    with_shared, _ = layers.routed_layer(
        u, {**share, "shared": p["shared"]}, model._route(cfg), 256,
        (240, 16), layers.swiglu)
    assert max_diff(with_shared, y + layers.dense_ffn(
        u, p["shared"], layers.swiglu)) < 1e-6


def test_counts_are_of_the_models_work_by_kind():
    from benchmark.harness import registry

    family = registry.family(registry.config("laguna-xs.2-ep16"))
    cfg = family.model_config()
    assert cfg.layer_types == (model.FULL,) + (model.SLIDING,) * 3 \
        + (model.FULL,)
    assert (cfg.n_head_full, cfg.n_head_sliding) == (48, 64)
    assert (cfg.rotary_full, cfg.rotary_sliding) == (64, 128)
    assert (cfg.theta_full, cfg.theta_sliding) == (5e5, 1e4)
    assert cfg.held == (0, 16) and cfg.n_experts == 256
    assert family.flops_per_token(16384) == pytest.approx(
        model.count_flops_per_token(cfg, 16384), rel=1e-12)
    pairs = family.attended_pairs_by_kind(16384)
    assert pairs["full_attention"] == model.attended_pairs(16384, None) \
        == 16384 * 16385 // 2
    assert pairs["sliding_attention"] == model.attended_pairs(16384, 512) \
        == 512 * 513 // 2 + 15872 * 512
    # a layer's pairs times ITS heads
    assert family.attended_head_pairs_a_pass(16384) \
        == 2 * 48 * pairs["full_attention"] \
        + 3 * 64 * pairs["sliding_attention"]
    assert family.attended_head_pairs_a_pass(
        16384, kinds=("sliding_attention",)) \
        == 3 * 64 * pairs["sliding_attention"]
    cost = family.attention_cost(1, 16384)
    assert cost["flops"] == 6 * 2 * 128 * family.attended_head_pairs_a_pass(
        16384)
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0),
                                                      cfg))
    assert family.param_count() == layers.num_params(shapes)
    # the issue's count: 490.3 M here, attention 46 % of 2.98 G a token
    assert family.param_count() / 1e6 == pytest.approx(490.3, abs=0.1)
    attention = 6 * family.attended_head_pairs_a_pass(16384) / 16384 * 256
    assert attention / family.flops_per_token(16384) \
        == pytest.approx(0.46, abs=0.01)
    assert family.flops_per_token(16384) / 1e9 == pytest.approx(2.98,
                                                                abs=0.02)
    assert family.expected_rows_per_token() == 0.5
    assert family.buffered_rows(16384) == 16384
    # each kind's rule written out attends that many pairs
    for kind, window in ((reference.SLIDING, 48), (reference.FULL, None)):
        seen = reference.attended(jnp.arange(128), 128, kind, 48)
        assert int(seen.sum()) == model.attended_pairs(128, window)
    assert int(reference.attended(jnp.arange(128), 128, reference.SLIDING,
                                  48).sum(axis=1).max()) == 48


# -- the names sharding reads -------------------------------------------------

@pytest.mark.parametrize("fsdp", [1, 4])
def test_the_leaves_resolve_under_a_layout(fsdp):
    """Every leaf carries the logical dimensions `parallel/sharding.py`
    reads off its name, whichever kind names its attention subtree and
    whatever its heads, the gate's (E, H) among them, under `fsdp=1` (the
    cell's) and under a mesh of four."""
    from ray_tpu.parallel.sharding import (ShardingConfig,
                                           infer_param_logical_dims,
                                           param_shardings)

    shapes = jax.eval_shape(
        lambda key: model.init_params(key, F32), jax.random.PRNGKey(0))
    dims = {"/".join(str(getattr(k, "key", k)) for k in path):
            infer_param_logical_dims(
                tuple(getattr(k, "key", k) for k in path), leaf.shape)
            for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert dims["embed_tokens/embedding"] == ("vocab", "embed")
    assert dims["lm_head/kernel"] == ("embed", "vocab")
    for layer, kind in (("layer_0", model.FULL), ("layer_1", model.SLIDING),
                        ("layer_4", model.FULL)):
        for name in ("q_proj", "k_proj", "v_proj", "g_proj"):
            assert dims[f"{layer}/{kind}/{name}/kernel"] \
                == ("embed", "heads"), name
        assert dims[f"{layer}/{kind}/o_proj/kernel"] == ("heads", "embed")
        for norm in ("input_norm/scale", "post_norm/scale"):
            assert dims[f"{layer}/{norm}"] == (None,)
    for name in ("gate_proj", "up_proj"):
        assert dims[f"layer_0/mlp/{name}/kernel"] == ("embed", "mlp")
        assert dims[f"layer_1/moe/shared/{name}/kernel"] == ("embed", "mlp")
    assert dims["layer_0/mlp/down_proj/kernel"] == ("mlp", "embed")
    assert dims["layer_1/moe/router/kernel"] == ("embed", None)
    assert dims[f"layer_1/moe/router/{ROUTING_BIAS}"] == ("embed",)
    assert dims["layer_1/moe/wi_gate"][0] == "expert"
    assert dims["layer_1/moe/wo"][0] == "expert"
    layout = ShardingConfig(fsdp=fsdp)
    mesh = layout.build_mesh(jax.devices()[:fsdp])
    placed = param_shardings(shapes, layout, mesh)
    cut = [s for s, leaf in zip(jax.tree.leaves(placed),
                                jax.tree.leaves(shapes))
           if s.shard_shape(leaf.shape) != leaf.shape]
    assert bool(cut) == (fsdp > 1)
    assert len(jax.tree.leaves(placed)) == len(jax.tree.leaves(shapes))


# -- the kernels at these groups under a narrow window ------------------------

def _qkv(S, H, Hkv, D, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (1, H, S, D), jnp.float32),
            jax.random.normal(ks[1], (1, Hkv, S, D), jnp.float32),
            jax.random.normal(ks[2], (1, Hkv, S, D), jnp.float32),
            jax.random.normal(ks[3], (1, H, S, D), jnp.float32))


def _dense(window, S):
    """The rule as `reference.attended` writes it."""
    kind = reference.FULL if window is None else reference.SLIDING
    return reference.attended(jnp.arange(S), S, kind, window)


# (query heads, S, window, `_WHOLE_SEQ_MAX`, block): groups of 3 and of 4
# query heads on 2 key/value heads; a window narrower than a pair of tiles
# (both visited tiles crossed by both bounds), of one tile and of a tile
# and a half; and no window
GROUP_CASES = [
    (heads, *case) for heads in (6, 8) for case in (
        (512, 128, 128, 256),           # half a tile: Laguna's W to 512s
        (512, 128, 128, 128),           # one tile
        (512, 100, 128, 256),           # divides nothing
        (512, 128, None, 256),          # a grid step the whole sequence
        (512, None, 128, 128),          # a full layer's call
    )]


@pytest.mark.parametrize("heads,S,window,whole_max,block", GROUP_CASES)
def test_groups_of_three_and_four_heads_match_a_dense_mask(
        monkeypatch, heads, S, window, whole_max, block):
    """Forward and backward, interpreted, against `reference_attention`
    with the rule as a dense mask and k and v repeated a group's times."""
    if whole_max:
        monkeypatch.setattr(fa, "_WHOLE_SEQ_MAX", whole_max)
    q, k, v, do = _qkv(S, heads, 2, 32)
    scale, rule = 32 ** -0.5, BlockRule(window=window)
    with warnings.catch_warnings():
        warnings.simplefilter("error", fa.AttentionFallbackWarning)
        o, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, rule, None, block, block), q, k, v)
        got = vjp(do)
    mask = _dense(window, S).astype(jnp.int8)[None]
    group = heads // 2
    kr, vr = (jnp.repeat(t, group, axis=1) for t in (k, v))
    want_o, lse = fa.reference_attention(q, kr, vr, scale, False, mask)
    dq, dk, dv = fa._reference_backward(
        q, kr, vr, lse, do, jnp.sum(do * want_o, -1), scale, False, mask)
    # a key/value head's gradient is the sum over its group's query heads
    dk, dv = (t.reshape(1, 2, group, S, 32).sum(axis=2) for t in (dk, dv))
    assert max_diff(o, want_o) < 1e-5
    for g, w in zip(got, (dq, dk, dv)):
        assert max_diff(g, w) < 5e-5


# -- the tiles a call takes by itself -----------------------------------------

def test_auto_tiles_reads_the_windows_width():
    """A call with no window takes the tiles it took; a window a band of
    256-tiles holds (`_band`) takes what the band's sweep on the chip found
    fastest (PERF.md §6, PR 64: 256 in both passes at W = 512 and at
    1,024, where the walk of PR 60 took 512), a wider one the walk's 512."""
    for S in (8192, 16384):
        assert fa._auto_tiles(S, BlockRule(window=1024)) \
            == ((256, 256), (256, 256))
        assert fa._auto_tiles(S, BlockRule(window=4096)) \
            == ((512, 512), (512, 512))
        assert fa._auto_tiles(S, True) == fa._auto_tiles(S, BlockRule()) \
            == ((1024, 1024), (512, 512))
        assert fa._auto_tiles(S, BlockRule(4, 2)) \
            == ((512, 512), (512, 512))
        # at W = 512 the walk read 19.42 ms a layer at 512 (23.18 at 256,
        # 39.37 at 128); the band 11.40 at 256, 12.20 at 128
        assert fa._auto_tiles(S, BlockRule(window=512)) \
            == fa._auto_tiles(S, BlockRule(window=300)) \
            == ((256, 256), (256, 256))
    assert fa._auto_tiles(1024, BlockRule(window=512)) \
        == fa._auto_tiles(1024, True)
    # the counters mean under W = 512 what they mean under W = 1,024: at the
    # cell's sizes a windowed kernel's walk of 512-tiles visited 63 of the
    # square's 1,024 (two a q tile, one for the first), of 256-tiles 189, of
    # 128-tiles 630; its band multiplies 512 + a tile's keys a row
    names = ("attention.window", "attention.window_pairs_visited")

    def traced(block):
        x = jax.ShapeDtypeStruct((1, 16384, 8, 32), jnp.float32)
        k = jax.ShapeDtypeStruct((1, 16384, 2, 32), jnp.float32)
        before = [tracing.counter(name) for name in names]
        jax.eval_shape(lambda q, k, v: fa.flash_attention_bshd(
            q, k, v, BlockRule(window=512), None, block, block), x, k, k)
        return [tracing.counter(name) - b for name, b in zip(names, before)]

    with tracing.timeline_span("train.fit", root=True) as job:
        assert traced(512) == [512, 16384 * 1024]
        assert traced(256) == [512, 16384 * 768]
        assert traced(128) == [512, 16384 * 640]
    tracing.timeline_take(job.trace_id)
    rule = BlockRule(window=512)
    assert [fa._tiles_visited(rule, 16384, b, b) for b in (512, 256, 128)] \
        == [63, 189, 630]
    attended = model.attended_pairs(16384, 512)
    assert attended / (63 * 512 * 512) == pytest.approx(0.500, abs=5e-3)
    assert attended / (16384 * 1024) == pytest.approx(0.492, abs=5e-3)
    assert attended / (16384 * 768) == pytest.approx(0.656, abs=5e-3)
    assert attended / (16384 * 640) == pytest.approx(0.788, abs=5e-3)

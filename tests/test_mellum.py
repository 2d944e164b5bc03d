"""The `mellum` model (`ray_tpu/models/mellum.py`: a Qwen3-MoE-lineage trunk
whose layers are of two kinds, three that attend a window of the latest
keys to one that attends every earlier key under YaRN's rotary table)
against the plain reference (`benchmark/reference/mellum.py`: float32
`jax.numpy`, each kind's rule written out, attention as one masked softmax,
the experts as a loop over those held), and the flash kernels under a
window (`ops/flash_attention.py:BlockRule(window=W)`) against the dense
reference, at small sizes on the CPU: one period of four layers, hidden 64,
8 query heads on 2 key/value heads of 16, 8 experts 24 wide of which 4 are
held, 3 a token, vocabulary 512, sequences of 128 under a window of 48,
YaRN by 4 over 32 original positions, seeded random weights.

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

from benchmark.families.mellum import to_reference
from benchmark.reference import mellum as reference
from ray_tpu.models import layers, mellum as model
from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.flash_attention import BlockRule
from ray_tpu.util import tracing

F32 = dataclasses.replace(model.MELLUM_TINY, held=(2, 4), aux_weight=0.1,
                          compute_dtype=jnp.float32)
SIZES = reference.Sizes(
    n_head=8, n_kv_head=2, top_k=3, kinds=(0, 0, 0, 1), window=48,
    held_first=2, yarn_factor=4.0, yarn_original=32, aux_weight=0.1,
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
    return kit.drawn(lambda key: model.init_params(key, cfg), seed)


def make_tokens(seed=0):
    return kit.tokens(1000 + seed % 1000, BATCH, SEQ, 512)


@kit.once
def results(which, seed):
    """(the cross-entropy, L_B, every row's cross-entropy, rows sent to the
    experts, the objective's gradients in the reference's layout) of the
    system in float32 or of the reference, each one jitted program."""
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
                return (parts["loss"], parts["aux_loss"], ce,
                        parts["expert_rows"], to_reference(grads))
            return jax.jit(run)(params)

        def run(params):
            (_, (loss, balance, rows, ce)), grads = jax.value_and_grad(
                reference.losses, has_aux=True)(params, tokens, SIZES)
            return loss, balance, ce, rows, grads
        return jax.jit(run)(to_reference(params))


# -- the system against the plain reference ---------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_the_losses_and_the_rows_match_the_reference(seed):
    got, want = results("system", seed), results("reference", seed)
    assert abs(float(got[0]) - float(want[0])) < F32_TOL      # cross-entropy
    assert abs(float(got[1]) - float(want[1])) < F32_TOL      # L_B
    assert max_diff(got[2], want[2]) < 5 * F32_TOL            # the rows' CE
    assert (np.asarray(got[3]) == np.asarray(want[3])).all()  # expert rows
    assert 5.5 < float(want[0]) < 7.5       # near log(512)


@pytest.mark.parametrize("seed", SEEDS)
def test_gradients_of_every_leaf_match(seed):
    got, want = results("system", seed)[4], results("reference", seed)[4]
    flat = jax.tree_util.tree_flatten_with_path(want)[0]
    for (path, w), g in zip(flat, jax.tree.leaves(got)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-6
        assert max_diff(g, w) < 2e-4 * scale + 1e-6, \
            (jax.tree_util.keystr(path), max_diff(g, w), scale)
        assert scale > 1e-6, jax.tree_util.keystr(path)   # every leaf trains


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_three_steps_match_the_reference_program(seed):
    params, tokens = make_params(seed), make_tokens(seed)
    want = reference.first_losses(
        kit.own(to_reference(params)),
        jnp.stack([tokens] * 3), SIZES, OPTIMIZER)
    optimizer = reference.adamw(OPTIMIZER)
    step = jax.jit(model.make_train_step(F32, optimizer))
    opt_state = optimizer.init(params)
    for n in range(3):
        params, opt_state, out = step(params, opt_state, {"tokens": tokens})
        assert abs(float(out["loss"]) - want[n][0]) < 1e-4, (n, want[n])
        assert abs(float(out["aux_loss"]) - want[n][1]) < 1e-4
    assert want[2][0] < want[0][0]


def test_a_recomputed_stack_of_two_kinds_is_the_same_step():
    cfg = dataclasses.replace(F32, remat=True)
    params, tokens = make_params(), make_tokens()
    optimizer = reference.adamw(OPTIMIZER)
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


def test_a_layers_kind_is_the_name_of_its_attention_subtree():
    """Both kinds have the same leaves; the trunk walks once and a
    recomputed layer is traced once a kind."""
    params = make_params()
    kinds = [model.SLIDING if model.SLIDING in params[f"layer_{i}"]
             else model.FULL for i in range(4)]
    assert tuple(kinds) == F32.layer_types
    shapes = lambda i, kind: jax.tree.map(jnp.shape,
                                          params[f"layer_{i}"][kind])
    assert shapes(0, model.SLIDING) == shapes(3, model.FULL)
    traced = []
    layer = model._layer

    def counting(x, p, cfg):
        traced.append(model.SLIDING if model.SLIDING in p else model.FULL)
        return layer(x, p, cfg)

    cfg = dataclasses.replace(F32, remat=True)
    jax.eval_shape(lambda p: layers.trunk(
        p, make_tokens()[:, :-1], counting, cfg)[0], params)
    # `keep_plan` traces a kind once for its marks, `jax.checkpoint` once
    # for the walk
    assert sorted(set(traced)) == sorted({model.SLIDING, model.FULL})
    assert len(traced) == 4


# -- the window, by perturbation ----------------------------------------------

def _first_layer(tokens, kind=model.SLIDING):
    """Layer 0's attention on the embedded tokens, (B, S, E)."""
    params = make_params()
    i = F32.layer_types.index(kind)
    p = params[f"layer_{i}"]
    x = params["embed_tokens"]["embedding"][tokens]
    u = layers.rms_norm(x, p["input_norm"], F32.rms_eps)
    return model._attention(u, p[kind], F32, kind)


@pytest.mark.parametrize("j", [0, 17, 60, 100])
def test_a_token_moves_a_sliding_layers_row_iff_the_window_holds_it(j):
    """A change of token x_j moves layer 0's output at row i iff
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


# -- the rotary tables --------------------------------------------------------

def test_yarn_frequencies_are_the_closed_form_at_the_published_keys():
    freqs, scale = layers.yarn_frequencies(128, 500000, 16, 8192, 32, 1)
    turns = lambda n: 128 * math.log(8192 / (2 * math.pi * n)) \
        / (2 * math.log(500000))
    assert math.floor(turns(32)) == 18 and math.ceil(turns(1)) == 35
    i = np.arange(64)
    base = 500000.0 ** (i / 64)
    ramp = np.clip((i - 18) / (35 - 18), 0, 1)
    want = (1 - ramp) / base + ramp / (16 * base)
    np.testing.assert_allclose(freqs, want, rtol=1e-6)
    assert freqs.dtype == np.float32
    # the fast dims as they were, the slow ones stretched 16 times
    np.testing.assert_allclose(freqs[:19], 1 / base[:19], rtol=1e-6)
    np.testing.assert_allclose(freqs[35:], 1 / (16 * base[35:]), rtol=1e-6)
    assert scale == pytest.approx(0.1 * math.log(16) + 1)
    assert scale == pytest.approx(1.2772588722239782, rel=1e-12)
    assert layers.yarn_frequencies(128, 500000, 16, 8192, 32, 1, 1.5)[1] \
        == 1.5
    # and the reference's own lines give the same table
    got, c = reference.frequencies(128, reference.FULL, reference.Sizes(
        n_head=32, n_kv_head=4, top_k=8, kinds=(1,)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-6)
    assert float(c) == pytest.approx(scale, rel=1e-6)


def test_a_theta_callers_jaxpr_is_what_it_was():
    """`layers.rope` with a base: the program it always was; with a table
    and a scale: the same rotation by those angles, times the scale."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16))
    positions = jnp.arange(8)

    def parent_rope(x, positions, theta):
        D = x.shape[-1]
        freqs = theta ** (-jnp.arange(0, D // 2, dtype=jnp.float32)
                          / (D // 2))
        angles = positions[..., None].astype(jnp.float32) * freqs
        cos = jnp.cos(angles)[..., None, :].astype(x.dtype)
        sin = jnp.sin(angles)[..., None, :].astype(x.dtype)
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               axis=-1)

    for theta in (1e4, 500000, 1e6):
        assert str(jax.make_jaxpr(lambda x: layers.rope(
            x, positions, theta))(x)) == str(jax.make_jaxpr(
                lambda x: parent_rope(x, positions, theta))(x))
    table = 1e4 ** (-np.arange(8, dtype=np.float32) / 8)
    assert max_diff(layers.rope(x, positions, table, scale=1.5),
                    1.5 * layers.rope(x, positions, 1e4)) < 1e-6
    with tracing.timeline_span("train.fit", root=True) as job:
        layers.rope(x, positions, 1e4)
        assert tracing.counter("rope.scaled") == 0
        layers.rope(x, positions, table, scale=1.5)
        assert tracing.counter("rope.scaled") == 1
    tracing.timeline_take(job.trace_id)


# -- the share of the experts -------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer():
    """One routed layer with the router's 64 columns, 8 a token: the parts
    that its four shares of 16 experts give add up to what the uncut
    reference gives for the whole layer, every share seeing the routing
    over all 64."""
    cfg = dataclasses.replace(F32, n_experts=64, top_k=8, expert_width=8,
                              held=None)
    params = make_params(cfg=cfg)
    p = params["layer_1"]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(9), (BATCH, SEQ, cfg.n_embd))
    total, rows = 0, []
    for first in range(0, 64, 16):
        share = {**p, **{k: p[k][first:first + 16]
                         for k in ("wi_gate", "wi_up", "wo")}}
        y, sent, _ = layers.routed_layer(u, share, model._route(cfg), 64,
                                      (first, 16), layers.swiglu)
        total += y
        rows.append(sent)
    whole = jax.tree.map(lambda leaf: leaf[1],
                         to_reference(params)["layers"])
    want, want_rows, _ = reference.moe(
        u.reshape(-1, cfg.n_embd), whole,
        SIZES._replace(top_k=8, held_first=0))
    assert max_diff(total.reshape(want.shape), want) < F32_TOL
    for sent in rows:
        assert (np.asarray(sent) == np.asarray(want_rows)).all()
    assert max_diff(y.reshape(want.shape), want) > 0.01


def test_counts_are_of_the_work_the_model_asks_for():
    from benchmark.harness import registry

    family = registry.family(registry.config("mellum2-12b-a2.5b-ep4"))
    cfg = family.model_config()
    assert family.flops_per_token(16384) == pytest.approx(
        model.count_flops_per_token(cfg, 16384), rel=1e-12)
    pairs = family.attended_pairs_by_kind(16384)
    assert pairs["full_attention"] == model.attended_pairs(16384, None) \
        == 16384 * 16385 // 2
    assert pairs["sliding_attention"] == model.attended_pairs(16384, 1024) \
        == 1024 * 1025 // 2 + 15360 * 1024
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0),
                                                      cfg))
    assert family.param_count() == layers.num_params(shapes)
    # each kind's rule written out attends that many pairs
    for kind, window in ((reference.SLIDING, 48), (reference.FULL, None)):
        seen = reference.attended(jnp.arange(128), 128, kind, 48)
        assert int(seen.sum()) == model.attended_pairs(128, window)
    assert int(reference.attended(jnp.arange(128), 128, 0, 48)
               .sum(axis=1).max()) == 48


# -- the names sharding reads -------------------------------------------------

@pytest.mark.parametrize("fsdp", [1, 4])
def test_the_leaves_resolve_under_a_layout(fsdp):
    """Every leaf carries the logical dimensions `parallel/sharding.py`
    reads off its name, whichever kind names its attention subtree, under
    `fsdp=1` (the cell's) and under a mesh of four, where the experts'
    stacks, the heads and the vocabulary are cut."""
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
    for layer, kind in (("layer_0", model.SLIDING), ("layer_3", model.FULL)):
        assert dims[f"{layer}/{kind}/q_proj/kernel"] == ("embed", "heads")
        assert dims[f"{layer}/{kind}/k_proj/kernel"] == ("embed", "heads")
        assert dims[f"{layer}/{kind}/v_proj/kernel"] == ("embed", "heads")
        assert dims[f"{layer}/{kind}/o_proj/kernel"] == ("heads", "embed")
        for norm in ("input_norm/scale", "post_norm/scale",
                     f"{kind}/q_norm/scale", f"{kind}/k_norm/scale"):
            assert dims[f"{layer}/{norm}"] == (None,)
        assert dims[f"{layer}/moe/router/kernel"] == ("embed", None)
        assert dims[f"{layer}/moe/wi_gate"][0] == "expert"
        assert dims[f"{layer}/moe/wo"][0] == "expert"
    layout = ShardingConfig(fsdp=fsdp)
    mesh = layout.build_mesh(jax.devices()[:fsdp])
    placed = param_shardings(shapes, layout, mesh)
    cut = [s for s, leaf in zip(jax.tree.leaves(placed),
                                jax.tree.leaves(shapes))
           if s.shard_shape(leaf.shape) != leaf.shape]
    assert bool(cut) == (fsdp > 1)
    assert len(jax.tree.leaves(placed)) == len(jax.tree.leaves(shapes))


# -- the kernels under a window -----------------------------------------------

def _qkv(S, H, Hkv, D, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (1, H, S, D), jnp.float32),
            jax.random.normal(ks[1], (1, Hkv, S, D), jnp.float32),
            jax.random.normal(ks[2], (1, Hkv, S, D), jnp.float32),
            jax.random.normal(ks[3], (1, H, S, D), jnp.float32))


def _dense(window, S):
    """The rule as `reference.attended` writes it."""
    return reference.attended(jnp.arange(S), S, reference.SLIDING, window)


# (S, window, `_WHOLE_SEQ_MAX`, block_q, block_k): a grid step the whole
# sequence (the short form) and a tile of it (the long one); windows of
# half a tile, one tile, one and a half and two, and widths that neither
# divide a tile nor are divided by one; tiles of unlike sizes; grouped
# queries throughout
KERNEL_CASES = [
    (512, 64, None, 128, 128),          # half a tile, unrolled
    (512, 64, 128, 128, 128),           # the same on the grid
    (512, 128, 128, 128, 128),          # one tile
    (512, 192, 128, 128, 128),          # one and a half
    (512, 256, 128, 128, 128),          # two
    (512, 192, None, 128, 128),
    (256, 48, None, None, None),        # `_auto_tiles`', one tile a sequence
    (512, 100, 128, 256, 128),          # q tiles twice the k's
    (512, 300, 128, 128, 256),          # and half
    (512, 1, 128, 128, 128),            # a row's own key alone
    (1024, 333, 256, 256, 256),
]


@pytest.mark.parametrize("S,window,whole_max,bq,bk", KERNEL_CASES)
def test_the_kernels_under_a_window_match_a_dense_mask(
        monkeypatch, S, window, whole_max, bq, bk):
    """Forward and backward, interpreted, against `reference_attention`
    with the rule as a dense mask (data, not the kernels' own
    classification)."""
    if whole_max:
        monkeypatch.setattr(fa, "_WHOLE_SEQ_MAX", whole_max)
    q, k, v, do = _qkv(S, 4, 2, 32)
    scale, rule = 32 ** -0.5, BlockRule(window=window)
    with warnings.catch_warnings():
        warnings.simplefilter("error", fa.AttentionFallbackWarning)
        o, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, rule, None, bq, bk), q, k, v)
        got = vjp(do)
    mask = _dense(window, S).astype(jnp.int8)[None]
    want_o, lse = fa.reference_attention(q, k, v, scale, False, mask)
    want = fa._reference_backward(q, k, v, lse, do, jnp.sum(do * want_o, -1),
                                  scale, False, mask)
    assert max_diff(o, want_o) < 1e-5
    for g, w in zip(got, want):
        assert max_diff(g, w) < 2e-5
    # and the reference path of the rule itself is that mask
    assert max_diff(fa.reference_attention(q, k, v, scale, rule)[0],
                    want_o) == 0.0


@pytest.mark.parametrize("whole_max", [None, 128])
def test_a_window_longer_than_the_sequence_is_causal_bit_for_bit(
        monkeypatch, whole_max):
    if whole_max:
        monkeypatch.setattr(fa, "_WHOLE_SEQ_MAX", whole_max)
    q, k, v, do = _qkv(512, 4, 2, 32)

    def run(causal):
        o, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, causal, None, 128, 128), q, k, v)
        return (o, *vjp(do))

    for a, b in zip(run(True), run(BlockRule(window=5000))):
        assert (np.asarray(a) == np.asarray(b)).all()
    # no window is the diagonal's own program
    jaxpr = lambda causal: str(jax.make_jaxpr(
        lambda q, k, v: fa.flash_attention(q, k, v, causal, None, 128, 128))(
            q, k, v))
    assert jaxpr(True) == jaxpr(BlockRule(window=None))


def test_the_bshd_entry_takes_a_window_head_major():
    """(B, S, H, D) with heads of 64 in pairs and k, v with q's heads,
    which the lane layout would take: under a window the call goes
    head-major, where the kernels' forms say `_window`."""
    S, rule = 256, BlockRule(window=100)
    q, k, v, do = (x.transpose(0, 2, 1, 3) for x in _qkv(S, 2, 2, 64))
    o, vjp = jax.vjp(lambda q, k, v: fa.flash_attention_bshd(q, k, v, rule),
                     q, k, v)
    tr = lambda x: x.transpose(0, 2, 1, 3)
    want_o, lse = fa.reference_attention(tr(q), tr(k), tr(v), 64 ** -0.5,
                                         rule)
    want = fa._reference_backward(
        tr(q), tr(k), tr(v), lse, tr(do),
        jnp.sum(tr(do) * want_o, -1), 64 ** -0.5, rule)
    assert max_diff(tr(o), want_o) < 1e-5
    for g, w in zip(vjp(do), want):
        assert max_diff(tr(g), w) < 2e-5
    lowered = jax.jit(jax.grad(lambda q: jnp.sum(fa.flash_attention_bshd(
        q, k, v, rule)))).lower(q).as_text(debug_info=True)
    assert "fwd_rows_window" in lowered and "bwd_fused_window" in lowered
    assert "fwd_lanes" not in lowered


@pytest.mark.parametrize("S,bq,bk,window", [
    (1024, 128, 128, 64), (1024, 128, 128, 128), (1024, 128, 128, 192),
    (1024, 128, 128, 256), (1024, 128, 128, 300), (1024, 256, 128, 48),
    (1024, 256, 128, 383), (1024, 128, 256, 383), (1024, 128, 256, 384),
    (1024, 256, 256, 511), (1024, 256, 256, 5000), (1024, 128, 128, 1),
])
def test_the_spans_are_the_tiles_a_window_leaves(S, bq, bk, window):
    """`_k_spans` and `_q_spans`, the one classification walked either way,
    visit exactly the tiles that hold an attended pair, and mask exactly
    those that also hold one that is not; three runs a tile."""
    rule = BlockRule(window=window)
    seen = np.asarray(_dense(window, S)).reshape(S // bq, bq, S // bk, bk)
    some, every = seen.any(axis=(1, 3)), seen.all(axis=(1, 3))
    by_rows = np.zeros_like(some, dtype=int)      # 1 whole, 2 masked
    for i in range(S // bq):
        spans = fa._k_spans(rule, i, bq, bk, S)[2]
        assert len(spans) == 3
        for first, last, how in spans:
            assert first <= last
            by_rows[i, first:last] += 2 if how else 1
    by_cols = np.zeros_like(by_rows)
    for j in range(S // bk):
        spans = fa._q_spans(rule, j, bq, bk, S)[1]
        assert len(spans) == 3
        for first, last, how, _ in spans:
            assert first <= last
            by_cols[first:last, j] += 2 if how else 1
    want = np.where(every, 1, np.where(some, 2, 0))
    assert (by_rows == want).all() and (by_cols == want).all()


def _visited(S, bq, bk, window):
    """Tiles with an attended pair, by the rule written out."""
    seen = np.asarray(_dense(window, S))
    return int(seen.reshape(S // bq, bq, S // bk, bk).any(axis=(1, 3)).sum())


def test_the_counters_equal_their_formulas_under_a_window():
    """`attention.tiles`, `attention.tiles_skipped` and
    `attention.pairs_visited` mean under a window what they mean under the
    diagonal; `attention.window_kernels` counts the kernels traced under
    one, `attention.window` sums their widths and
    `attention.window_pairs_visited` their part of the pairs.  At the cell's sizes a
    head's windowed kernel WALKED 93 of the square's 1,024 512-tiles and
    the attended pairs were 0.667 of the visited, 0.80 with 256-tiles; since
    PR 64 it takes a band of 1,024 + a tile's keys a row (`_band`: 96 tiles'
    worth at 512-tiles, 320 at 256; `tests/test_flash_window_band.py`); a
    full layer's 528 and 0.970."""
    names = ("attention.tiles", "attention.tiles_skipped",
             "attention.pairs_visited", "attention.window_kernels",
             "attention.window", "attention.window_pairs_visited")

    def traced(S, rule, block):
        x = jax.ShapeDtypeStruct((1, S, 4, 32), jnp.float32)
        before = [tracing.counter(name) for name in names]
        jax.eval_shape(lambda q, k, v: fa.flash_attention_bshd(
            q, k, v, rule, None, block, block), x, x, x)
        return [tracing.counter(name) - b for name, b in zip(names, before)]

    with tracing.timeline_span("train.fit", root=True) as job:
        for S, window, block in ((512, 100, 128), (1024, 256, 128),
                                 (1024, 300, 256), (1024, 48, 256)):
            tiles = (S // block) ** 2
            visited = _visited(S, block, block, window)
            assert traced(S, BlockRule(window=window), block) == [
                tiles, tiles - visited, visited * block * block, 1, window,
                visited * block * block]
        assert traced(1024, True, 256)[3:] == [0, 0, 0]
        assert traced(16384, BlockRule(window=1024), 512) == [
            1024, 1024 - 96, 16384 * 1536, 1, 1024, 16384 * 1536]
        assert traced(16384, BlockRule(window=1024), 256)[2] \
            == 16384 * 1280 == 320 * 256 * 256
        # the walk's 93 and 310 are what `_tiles_visited` still says
        rule = BlockRule(window=1024)
        assert fa._tiles_visited(rule, 16384, 512, 512) == 93
        assert fa._tiles_visited(rule, 16384, 256, 256) == 310
        assert traced(16384, True, 512)[:3] == [
            1024, 1024 - 528, 528 * 512 * 512]
    tracing.timeline_take(job.trace_id)
    windowed = model.attended_pairs(16384, 1024)
    assert windowed / (93 * 512 * 512) == pytest.approx(0.667, abs=5e-4)
    assert windowed / (310 * 256 * 256) == pytest.approx(0.80, abs=0.01)
    assert model.attended_pairs(16384, None) / (528 * 512 * 512) \
        == pytest.approx(0.970, abs=5e-4)


def test_a_window_beside_blocks_or_two_kinds_is_refused_with_a_reason():
    q, k, v, _ = _qkv(256, 2, 2, 32)
    for rule in (BlockRule(4, 1, 64), BlockRule(1, 2, 64), BlockRule(4, 2, 8),
                 BlockRule(window=0)):
        with pytest.raises(NotImplementedError, match="window"):
            fa.flash_attention(q, k, v, rule)


@pytest.mark.parametrize("variant", ["ring", "ulysses"])
def test_the_sequence_parallel_variants_decline_a_window(variant):
    from ray_tpu.parallel.attention import attention

    x = jnp.zeros((1, 128, 2, 16))
    with pytest.raises(NotImplementedError, match="rule"):
        attention(x, x, x, causal=BlockRule(window=32), variant=variant)

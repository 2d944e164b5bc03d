"""The `sdar` model (`ray_tpu/models/sdar.py`: a Qwen3-MoE trunk trained as
block diffusion: a noised copy beside every sequence, attention under a
rule of blocks, a masked, 1/t-weighted loss) against the plain reference
(`benchmark/reference/sdar.py`: float32 `jax.numpy`, the rule written out,
attention as one masked softmax, the experts as a loop over those held),
and the flash kernels under the rule (`ops/flash_attention.py:BlockRule`)
against the dense reference, at small sizes on the CPU: two layers, hidden
64, 8 query heads on 2 key/value heads of 16, 8 experts 24 wide of which 4
are held, 3 a token, vocabulary 500 + MASK in 512 rows, sequences of 64 in
blocks of 4, seeded random weights.

The matrices are drawn four times as wide as the assumed 0.02: at 0.02 and
these widths an operator's output is a thousandth of the residual stream
and a fault would hide under any tolerance.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import model_kit as kit
import numpy as np
import optax
import pytest
from model_kit import max_diff

from benchmark.families.sdar import to_reference
from benchmark.reference import sdar as reference
from ray_tpu.models import layers, sdar as model
from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.flash_attention import BlockRule
from ray_tpu.util import tracing

F32 = dataclasses.replace(model.SDAR_TINY, held=(2, 4), aux_weight=0.1,
                          compute_dtype=jnp.float32)
SIZES = reference.Sizes(n_head=8, n_kv_head=2, top_k=3, mask_token=500,
                        block_length=4, held_first=2, aux_weight=0.1,
                        query_block=32)
BATCH, SEQ = 2, 64
OPTIMIZER = {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
             "weight_decay": 0.1}
# float32 compute: the noise and the routing are identical and only
# summation order differs (flash tiles under a rule against a whole
# softmax, sorted groups against a loop over experts)
F32_TOL = 2e-5
SEEDS = [0, 1, 2147483900]


pytestmark = pytest.mark.usefixtures("highest_precision")


@kit.once
def make_params(seed=0, cfg=F32):
    return kit.drawn(lambda key: model.init_params(key, cfg), seed)


def make_tokens(seed=0):
    return kit.tokens(1000 + seed % 1000, BATCH, SEQ, 500)


@kit.once
def results(which, seed, step=0):
    """(L_D, L_B, the noised rows' cross-entropies, rows sent to the
    experts, the objective's gradients in the reference's layout) of the
    system in float32 or of the reference under the noise of (seed, step),
    each one jitted program."""
    params, tokens = make_params(seed), make_tokens(seed)
    with jax.default_matmul_precision("highest"):
        if which == "system":
            def run(params):
                masked, _ = model.draw_noise(model.noise_key(seed), step,
                                             BATCH, SEQ, 4)
                logits, _ = model.forward(params, tokens[:, :-1], masked, F32)
                ce = -jnp.take_along_axis(
                    jax.nn.log_softmax(logits), tokens[:, :-1, None],
                    axis=-1)[..., 0]
                (_, parts), grads = jax.value_and_grad(
                    model.loss_fn, has_aux=True)(
                        params, {"tokens": tokens}, F32,
                        model.noise_key(seed), step)
                return (parts["loss"], parts["aux_loss"], ce,
                        parts["expert_rows"], to_reference(grads))
            return jax.jit(run)(params)

        def run(params):
            (_, (loss, balance, rows, ce, _, _)), grads = jax.value_and_grad(
                reference.losses, has_aux=True)(params, tokens, seed, step,
                                                SIZES)
            return loss, balance, ce, rows, grads
        return jax.jit(run)(to_reference(params))


# -- the system against the plain reference ---------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_the_losses_and_the_noised_rows_match_the_reference(seed):
    got, want = results("system", seed), results("reference", seed)
    assert abs(float(got[0]) - float(want[0])) < F32_TOL      # L_D
    assert abs(float(got[1]) - float(want[1])) < F32_TOL      # L_B
    assert max_diff(got[2], want[2]) < 5 * F32_TOL            # the rows' CE
    assert (np.asarray(got[3]) == np.asarray(want[3])).all()  # expert rows
    assert 4.0 < float(want[0]) < 9.0       # near log(512), 1/t-weighted


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
    """Three AdamW steps on the same batch, each under its own step's
    noise: the system's step draws from the optimizer's count."""
    params, tokens = make_params(seed), make_tokens(seed)
    want = reference.first_losses(
        kit.own(to_reference(params)),
        jnp.stack([tokens] * 3), seed, SIZES, OPTIMIZER)
    optimizer = reference.adamw(OPTIMIZER)
    step = jax.jit(model.make_train_step(F32, optimizer, seed))
    opt_state = optimizer.init(params)
    shares = []
    for n in range(3):
        params, opt_state, out = step(params, opt_state, {"tokens": tokens})
        assert abs(float(out["loss"]) - want[n][0]) < 1e-4, (n, want[n])
        assert abs(float(out["aux_loss"]) - want[n][1]) < 1e-4
        shares.append(float(out["masked_share"]))
    assert len(set(shares)) == 3        # another step, another noise


def test_a_recomputed_share_of_the_experts_is_the_same_step():
    cfg = dataclasses.replace(F32, remat=True)
    params, tokens = make_params(), make_tokens()
    optimizer = reference.adamw(OPTIMIZER)
    outs = []
    for c in (F32, cfg):
        step = jax.jit(model.make_train_step(c, optimizer, 7))
        new, _, out = step(params, optimizer.init(params),
                           {"tokens": tokens})
        outs.append((out["loss"], new))
    assert abs(float(outs[0][0]) - float(outs[1][0])) < 1e-6
    assert max(jax.tree.leaves(jax.tree.map(max_diff, outs[0][1],
                                            outs[1][1]))) < 1e-5


def test_bfloat16_compute_stays_close():
    """bfloat16 compute against the float32 reference at these sizes (L_D
    about 6.3 +- 1 with its 1/t weights)."""
    seed = 3
    cfg = dataclasses.replace(F32, compute_dtype=jnp.bfloat16)
    params, tokens = make_params(seed), make_tokens(seed)
    cast = layers.cast_weights(params, jnp.bfloat16)
    _, parts = jax.jit(lambda p: model.loss_fn(
        p, {"tokens": tokens}, cfg, model.noise_key(seed), 0))(cast)
    want = results("reference", seed)[0]
    assert abs(float(parts["loss"]) - float(want)) < 0.05


# -- no leak, by perturbation -----------------------------------------------

def _noised_logits(tokens, masked):
    logits, _ = model.forward(make_params(), tokens, masked, F32)
    return logits


@pytest.mark.parametrize("j", [5, 30])
def test_a_clean_token_moves_only_later_blocks(j):
    """A change of clean token x_j, where the noised copy hides it, moves
    the logits of noised row i only if block(j) < block(i)."""
    tokens = make_tokens()[:, :-1]
    masked = jnp.zeros((BATCH, SEQ), bool).at[:, j].set(True)
    other = tokens.at[0, j].set((tokens[0, j] + 1) % 500)
    moved = jnp.max(jnp.abs(_noised_logits(tokens, masked)
                            - _noised_logits(other, masked)), axis=-1)
    first_later = (j // 4 + 1) * 4
    assert float(jnp.max(moved[0, :first_later])) == 0.0
    assert float(jnp.min(moved[0, first_later:])) > 0.0
    assert float(jnp.max(moved[1])) == 0.0      # the other sequence


@pytest.mark.parametrize("j", [6, 29])
def test_a_noised_token_moves_only_its_own_block(j):
    """A change of the noised copy at j alone (MASK or not, the clean
    token as it was) moves the logits of noised row i only if block(j) =
    block(i)."""
    tokens = make_tokens()[:, :-1]
    none = jnp.zeros((BATCH, SEQ), bool)
    moved = jnp.max(jnp.abs(
        _noised_logits(tokens, none)
        - _noised_logits(tokens, none.at[0, j].set(True))), axis=-1)
    own = slice(j // 4 * 4, j // 4 * 4 + 4)
    assert float(jnp.min(moved[0, own])) > 0.0
    assert float(jnp.max(moved[0].at[own].set(0.0))) == 0.0
    assert float(jnp.max(moved[1])) == 0.0


# -- the noise ----------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_the_same_seed_and_step_draw_the_same_noise_on_both_sides(seed):
    for n in (0, 1):
        masked, weights = model.draw_noise(model.noise_key(seed), n, 4, 64, 4)
        m, t = reference.noise(seed, n, 4, 64, 4)
        assert (np.asarray(masked) == np.asarray(m)).all()
        assert max_diff(weights, jnp.where(m, 1.0 / t, 0.0)) == 0.0
        # one level a block
        assert (np.asarray(t).reshape(4, 16, 4) ==
                np.asarray(t).reshape(4, 16, 4)[..., :1]).all()
    again, _ = model.draw_noise(model.noise_key(seed), 0, 4, 64, 4)
    assert not (np.asarray(again) == np.asarray(masked)).all()


def test_the_weights_mean_is_near_one():
    """E[m / t] = 1 (a heavy tail: its variance has no bound, so a wide
    band over many blocks) and about half the tokens are masked."""
    masked, weights = model.draw_noise(model.noise_key(5), 0, 64, 4096, 4)
    assert abs(float(jnp.mean(weights)) - 1.0) < 0.05
    assert abs(float(jnp.mean(masked)) - 0.5) < 0.01


# -- the share of the experts -------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One routed layer with the router's 128 columns, 8 a token: the parts
    that its eight shares of 16 experts give add up to what the uncut
    reference gives for the whole layer, every share seeing the routing
    over all 128."""
    cfg = dataclasses.replace(F32, n_experts=128, top_k=8, expert_width=8,
                              held=None)
    params = make_params(cfg=cfg)
    p = params["layer_1"]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(9), (BATCH, 2 * SEQ, cfg.n_embd))
    total, rows = 0, []
    for first in range(0, 128, 16):
        share = {**p, **{k: p[k][first:first + 16]
                         for k in ("wi_gate", "wi_up", "wo")}}
        y, sent, _ = layers.routed_layer(u, share, model._route(cfg), 128,
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

    family = registry.family(registry.config("sdar-30b-a3b-chat-ep8"))
    cfg = family.model_config()
    assert family.flops_per_token(8192) == pytest.approx(
        model.count_flops_per_token(cfg, 8192), rel=1e-12)
    assert family.attended_pairs(8192) == model.attended_pairs(8192, 4) \
        == 8192 ** 2 + 4 * 8192
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0),
                                                      cfg))
    assert family.param_count() == layers.num_params(shapes)
    # the rule written out attends that many pairs
    seen = reference.attended(jnp.arange(256), 128, 4)
    assert int(seen.sum()) == model.attended_pairs(128, 4)
    assert int(seen.sum(axis=1).min()) == 4     # every row attends 4 keys


# -- the kernels under the rule -----------------------------------------------

def _qkv(S, H, Hkv, D, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (1, H, S, D), jnp.float32),
            jax.random.normal(ks[1], (1, Hkv, S, D), jnp.float32),
            jax.random.normal(ks[2], (1, Hkv, S, D), jnp.float32),
            jax.random.normal(ks[3], (1, H, S, D), jnp.float32))


def _dense(rule, S):
    """The rule as `reference.attended` writes it (two kinds), or block
    indices compared (one)."""
    if rule.kinds == 2:
        return reference.attended(jnp.arange(S), S // 2, rule.block)
    at = jnp.arange(S) // rule.block
    return at[None] <= at[:, None]


# (S, rule, `_WHOLE_SEQ_MAX`, block_q, block_k): a grid step the whole
# sequence (the short form) and a tile of it (the long one), a kind's rows
# one tile and several, tiles of unlike sizes, grouped queries throughout
KERNEL_CASES = [
    (256, BlockRule(4, 2), None, None, None),       # L one tile of 128
    (128, BlockRule(4, 2), None, None, None),       # L = 64: a tile under 128
    (512, BlockRule(4, 2), None, 128, 128),         # L two tiles, unrolled
    (512, BlockRule(4, 2), 128, 128, 128),          # the same on the grid
    (1024, BlockRule(4, 2), 128, 256, 128),         # q tiles twice the k's
    (1024, BlockRule(4, 2), 128, 128, 256),         # and half
    (512, BlockRule(8, 1), None, 128, 128),         # block-causal, one kind
    (512, BlockRule(4, 1), 128, 128, 128),
    (512, BlockRule(1, 2), 128, 128, 128),          # two kinds, blocks of 1
]


@pytest.mark.parametrize("S,rule,whole_max,bq,bk", KERNEL_CASES)
def test_the_kernels_under_the_rule_match_a_dense_mask(
        monkeypatch, S, rule, whole_max, bq, bk):
    """Forward and backward, interpreted, against `reference_attention`
    with the rule as a dense mask (data, not the kernels' own
    classification)."""
    if whole_max:
        monkeypatch.setattr(fa, "_WHOLE_SEQ_MAX", whole_max)
    q, k, v, do = _qkv(S, 4, 2, 32)
    scale = 32 ** -0.5
    with warnings.catch_warnings():
        warnings.simplefilter("error", fa.AttentionFallbackWarning)
        o, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, rule, None, bq, bk), q, k, v)
        got = vjp(do)
    mask = _dense(rule, S).astype(jnp.int8)[None]
    want_o, lse = fa.reference_attention(q, k, v, scale, False, mask)
    want = fa._reference_backward(q, k, v, lse, do, jnp.sum(do * want_o, -1),
                                  scale, False, mask)
    assert max_diff(o, want_o) < 1e-5
    for g, w in zip(got, want):
        assert max_diff(g, w) < 2e-5
    # and the reference path of the rule itself is that mask
    assert max_diff(fa.reference_attention(q, k, v, scale, rule)[0],
                    want_o) == 0.0


def test_the_lane_layout_takes_the_rule_too():
    """(B, S, H, D) with heads of 64 in pairs and k, v with q's heads: the
    lane kernels share the cores, so the rule is theirs as well."""
    S, rule = 256, BlockRule(4, 2)
    q, k, v, do = (x.transpose(0, 2, 1, 3) for x in _qkv(S, 2, 2, 64))
    o, vjp = jax.vjp(lambda q, k, v: fa.flash_attention_bshd(q, k, v, rule),
                     q, k, v)
    tr = lambda x: x.transpose(0, 2, 1, 3)
    want_o, lse = fa.reference_attention(tr(q), tr(k), tr(v), 64 ** -0.5, rule)
    want = fa._reference_backward(
        tr(q), tr(k), tr(v), lse, tr(do),
        jnp.sum(tr(do) * want_o, -1), 64 ** -0.5, rule)
    assert max_diff(tr(o), want_o) < 1e-5
    for g, w in zip(vjp(do), want):
        assert max_diff(tr(g), w) < 2e-5


@pytest.mark.parametrize("whole_max", [None, 128])
def test_block_length_one_of_one_kind_is_causal_bit_for_bit(monkeypatch,
                                                            whole_max):
    if whole_max:
        monkeypatch.setattr(fa, "_WHOLE_SEQ_MAX", whole_max)
    q, k, v, do = _qkv(512, 4, 2, 32)

    def run(causal):
        o, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, causal, None, 128, 128), q, k, v)
        return (o, *vjp(do))

    for a, b in zip(run(True), run(BlockRule(1, 1))):
        assert (np.asarray(a) == np.asarray(b)).all()
    jaxpr = lambda causal: str(jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
        q, k, v, causal, None, 128, 128))(q, k, v))
    assert jaxpr(True) == jaxpr(BlockRule(1, 1))


def _visited(S, bq, bk, rule):
    """Tiles with an attended pair, by the rule written out."""
    seen = np.asarray(_dense(rule, S))
    return int(seen.reshape(S // bq, bq, S // bk, bk).any(axis=(1, 3)).sum())


@pytest.mark.parametrize("S,bq,bk,rule", [
    (512, 128, 128, BlockRule(4, 2)),
    (1024, 256, 128, BlockRule(4, 2)),
    (1024, 128, 256, BlockRule(4, 2)),
    (1024, 128, 128, BlockRule(8, 1)),
    (1024, 256, 256, BlockRule(1, 1)),
])
def test_the_spans_are_the_tiles_the_rule_leaves(S, bq, bk, rule):
    """`_k_spans` and `_q_spans`, the one classification walked either way,
    visit exactly the tiles that hold an attended pair, and mask exactly
    those that also hold one that is not."""
    seen = np.asarray(_dense(rule, S)).reshape(S // bq, bq, S // bk, bk)
    some, every = seen.any(axis=(1, 3)), seen.all(axis=(1, 3))
    by_rows = np.zeros_like(some, dtype=int)      # 1 whole, 2 masked
    for i in range(S // bq):
        for first, last, how in fa._k_spans(rule, i, bq, bk, S)[2]:
            by_rows[i, first:last] += 2 if how else 1
    by_cols = np.zeros_like(by_rows)
    for j in range(S // bk):
        for first, last, how, _ in fa._q_spans(rule, j, bq, bk, S)[1]:
            by_cols[first:last, j] += 2 if how else 1
    want = np.where(every, 1, np.where(some, 2, 0))
    assert (by_rows == want).all() and (by_cols == want).all()


def test_the_counters_equal_their_formulas():
    """`attention.tiles`, `attention.tiles_skipped` and
    `attention.pairs_visited` under the rule, once a kernel as it is
    traced; at the cell's sizes a head visits 288 of the square's 1,024
    512-tiles a sequence and the attended pairs are 0.889 of the visited."""
    names = ("attention.tiles", "attention.tiles_skipped",
             "attention.pairs_visited")

    def traced(S, rule, block):
        x = jax.ShapeDtypeStruct((1, S, 4, 32), jnp.float32)
        before = [tracing.counter(name) for name in names]
        jax.eval_shape(lambda q, k, v: fa.flash_attention_bshd(
            q, k, v, rule, None, block, block), x, x, x)
        return [tracing.counter(name) - b for name, b in zip(names, before)]

    with tracing.timeline_span("train.fit", root=True) as job:
        for S, rule, block in ((512, BlockRule(4, 2), 128),
                               (1024, BlockRule(4, 2), 128),
                               (1024, BlockRule(4, 1), 256),
                               (1024, True, 256)):
            tiles = (S // block) ** 2
            visited = _visited(S, block, block, fa._rule(rule))
            assert traced(S, rule, block) == [
                tiles, tiles - visited, visited * block * block]
        half = 16384 // 512 // 2
        assert traced(16384, BlockRule(4, 2), 512) == [
            1024, 1024 - 288, 288 * 512 * 512]
        assert 288 == 2 * (half * (half + 1) // 2) + half
        assert model.attended_pairs(8192, 4) / (288 * 512 * 512) > 0.88
    tracing.timeline_take(job.trace_id)


def test_a_rule_the_tiles_do_not_divide_runs_the_reference_and_says_so():
    q, k, v, _ = _qkv(384, 2, 2, 32)       # L = 192: tiles of 128 cross it
    with pytest.warns(fa.AttentionFallbackWarning, match="kinds of row"):
        o = fa.flash_attention(q, k, v, BlockRule(4, 2), None, 128, 128)
    want, _ = fa.reference_attention(q, k, v, 32 ** -0.5, BlockRule(4, 2))
    assert max_diff(o, want) == 0.0


@pytest.mark.parametrize("variant", ["ring", "ulysses"])
def test_the_sequence_parallel_variants_decline_a_rule(variant):
    from ray_tpu.parallel.attention import attention

    x = jnp.zeros((1, 128, 2, 16))
    with pytest.raises(NotImplementedError, match="rule"):
        attention(x, x, x, causal=BlockRule(4, 2), variant=variant)


# -- the step's number --------------------------------------------------------

def test_an_objective_that_asks_is_handed_the_optimizers_count():
    optimizer = optax.adamw(1e-3)
    params = {"w": jnp.ones((2, 2))}

    def objective(p, batch, count):
        return jnp.sum(p["w"]) * 0.0, {"count": count}

    step = jax.jit(layers.train_step(objective, optimizer, jnp.float32,
                                     counted=True))
    opt_state = optimizer.init(params)
    for n in range(3):
        params, opt_state, out = step(params, opt_state, {})
        assert int(out["count"]) == n


def test_an_objective_that_does_not_ask_is_called_as_it_was():
    """`counted` left out: the objective is called with the parameters and
    the batch alone, as every other model's is; asked for without an
    argument to take it, the count has nowhere to go."""
    optimizer = optax.adamw(1e-3)
    params = {"w": jnp.ones((2, 2))}
    objective = lambda p, batch: (jnp.sum(p["w"] ** 2), {})
    lower = lambda **kw: jax.jit(layers.train_step(
        objective, optimizer, jnp.float32, **kw)).lower(
            params, optimizer.init(params), {})
    lower()
    with pytest.raises(TypeError):
        lower(counted=True)


# -- the names sharding reads -------------------------------------------------

@pytest.mark.parametrize("fsdp", [1, 4])
def test_the_leaves_resolve_under_a_layout(fsdp):
    """Every leaf carries the logical dimensions `parallel/sharding.py`
    reads off its name, under `fsdp=1` (the cell's) and under a mesh of
    four, where the experts' stacks, the heads and the vocabulary are
    cut."""
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
    assert dims["layer_0/attn/q_proj/kernel"] == ("embed", "heads")
    assert dims["layer_0/attn/o_proj/kernel"] == ("heads", "embed")
    assert dims["layer_1/moe/wi_gate"][0] == "expert"
    assert dims["layer_1/moe/wo"][0] == "expert"
    for norm in ("input_norm/scale", "post_norm/scale", "attn/q_norm/scale",
                 "attn/k_norm/scale"):
        assert dims[f"layer_0/{norm}"] == (None,)
    layout = ShardingConfig(fsdp=fsdp)
    mesh = layout.build_mesh(jax.devices()[:fsdp])
    placed = param_shardings(shapes, layout, mesh)
    cut = [s for s, leaf in zip(jax.tree.leaves(placed),
                                jax.tree.leaves(shapes))
           if s.shard_shape(leaf.shape) != leaf.shape]
    assert bool(cut) == (fsdp > 1)
    assert len(jax.tree.leaves(placed)) == len(jax.tree.leaves(shapes))


# -- compiled for the chip, without one ---------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here, or it is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_rule_kernels_compile_for_the_chip_at_the_cells_shape(one_chip):
    """Mosaic takes the kernels under the rule at (2, 16384, 32 / 4, 128) in
    bfloat16, forward and the one backward, with the thin integer divisions
    of the crossed tiles' mask and 80 MiB of scoped VMEM; nothing runs."""
    def both(q, k, v, do):
        o, vjp = jax.vjp(lambda q, k, v: fa.flash_attention_bshd(
            q, k, v, BlockRule(4, 2)), q, k, v)
        return o, vjp(do)

    x = lambda heads: jax.ShapeDtypeStruct((2, 16384, heads, 128),
                                           jnp.bfloat16, sharding=one_chip)
    # as the step traces them: no matmul precision asked for
    with warnings.catch_warnings(), jax.default_matmul_precision("default"):
        warnings.simplefilter("error", fa.AttentionFallbackWarning)
        text = jax.jit(both).lower(x(32), x(4), x(4), x(32)).compile() \
            .as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "16384,16384" not in text


@pytest.mark.parametrize("window", [1024, 1000])
def test_the_windowed_kernels_compile_for_the_chip_at_the_cells_shape(
        one_chip, window):
    """Mosaic takes the kernels under a window (`BlockRule(window=W)`: the
    Mellum 2 cell's 1,024, and a width no tile divides) at
    (1, 16384, 32 / 4, 128) in bfloat16, forward and the one backward, the
    runs' bounds worked out from the grid's tile index; nothing runs, and
    no (S, S) array exists.  Here beside the rule's, for the reason below."""
    def both(q, k, v, do):
        o, vjp = jax.vjp(lambda q, k, v: fa.flash_attention_bshd(
            q, k, v, BlockRule(window=window)), q, k, v)
        return o, vjp(do)

    x = lambda heads: jax.ShapeDtypeStruct((1, 16384, heads, 128),
                                           jnp.bfloat16, sharding=one_chip)
    with warnings.catch_warnings(), jax.default_matmul_precision("default"):
        warnings.simplefilter("error", fa.AttentionFallbackWarning)
        text = jax.jit(both).lower(x(32), x(4), x(4), x(32)).compile() \
            .as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "16384,16384" not in text


def test_the_gated_norm_kernels_compile_for_the_chip_at_nemotrons_shape(
        one_chip):
    """Mosaic takes `ops/gated_norm.py`'s two kernels at the nemotron
    cell's (2 x 8192, 4096) bfloat16 rows in 8 groups, with a row tile's
    five blocks twice over in scoped VMEM; nothing runs.  Here and not in
    `test_gated_norm.py`: one file of the suite, so one worker, loads the
    TPU's compiler."""
    from ray_tpu.ops import gated_norm as gn

    rows = jax.ShapeDtypeStruct((16384, 4096), jnp.bfloat16,
                                sharding=one_chip)
    gain = jax.ShapeDtypeStruct((4096,), jnp.float32, sharding=one_chip)
    static = (8, 1e-5, gn._row_tile(16384, 4096, 8))

    def both(y, z, gain, dout):
        out, vjp = jax.vjp(lambda *a: gn._kernels(*a, static), y, z, gain)
        return out, vjp(dout)

    text = jax.jit(both).lower(rows, rows, gain, rows).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "f32[16384,4096]" not in text        # nothing float32 in HBM


def test_the_convolutions_kernels_compile_for_the_chip_at_nemotrons_shape(
        one_chip):
    """Mosaic takes `ops/causal_conv.py`'s kernels at the nemotron cell's
    shape: x, B and C out of W_in's (2, 8192, 10304) result at column 4,096,
    4 taps, a bias and a SiLU in bfloat16, a kernel a result forward and
    backward; nothing runs.  Here for `test_gated_norm`'s reason."""
    from ray_tpu.ops import causal_conv as cc

    def both(v, w, b, dys):
        outs, vjp = jax.vjp(lambda v, w, b: cc.causal_conv(
            v, w, b, "silu", 4096, (4096, 1024, 1024)), v, w, b)
        return outs, vjp(dys)

    x = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    text = jax.jit(both).lower(
        x(2, 8192, 10304), x(6144, 4, dtype=jnp.float32),
        x(6144, dtype=jnp.float32),
        tuple(x(2, 8192, width) for width in (4096, 1024, 1024))) \
        .compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 6

"""The `bailing_hybrid` model (`ray_tpu/models/bailing_hybrid.py`: Kimi Delta
Attention in five layers of six and latent attention in the sixth by the
published index, a leading dense layer, sigmoid-routed experts picked in
groups, heads and experts held as a share) against the plain reference
(`benchmark/reference/bailing_hybrid.py`: float32 `jax.numpy`, the delta rule
position by position, the routing written out with its groups) at a small
size on the CPU: published layers 0 and 2..7, hidden 64, 2 of 4 heads of 16,
4 of 16 experts in 4 groups, sequence 64 in chunks of 32, vocabulary 512,
seeded random weights.

The matrices are drawn four times as wide as the assumed 0.02 and the gains,
A_log, dt_bias and the routing biases are not what they start as: at 0.02 and
these widths a mixer's output is a thousandth of the residual stream and a
fault would hide under any tolerance.
"""

import contextlib
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import model_kit as kit
import numpy as np
import pytest
from model_kit import max_diff

from benchmark.families.bailing_hybrid import (Family, from_reference,
                                               to_reference)
from benchmark.harness import registry
from benchmark.reference import bailing_hybrid as reference
from benchmark.tests.bailing_hybrid_faults import FAULTS
from benchmark.tests.mellum_faults import patched
from ray_tpu.models import bailing_hybrid as model
from ray_tpu.models import layers
from ray_tpu.ops.kda import KdaFallbackWarning
from ray_tpu.util import tracing

BF16 = model.BAILING_HYBRID_TINY
F32 = dataclasses.replace(BF16, compute_dtype=jnp.float32)
BATCH, SEQ = 2, 64
OPTIMIZER = {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
             "weight_decay": 0.1}
# float32 compute: only the order of the sums differs (the chunked algebra
# against the recurrence, flash blocks against a whole softmax)
F32_TOL = 5e-5
# what every seeded fault below moves the float32 logits by at the least,
# two thousand times float32's tolerance
FAULT_MARGIN = 0.1

pytestmark = [
    pytest.mark.usefixtures("highest_precision"),
    pytest.mark.filterwarnings("ignore::ray_tpu.ops.kda.KdaFallbackWarning")]


def sizes(cfg=F32, **changed):
    return reference.Sizes(**{**dict(
        kinds=tuple(cfg.kind(i) for i in range(cfg.n_layer)),
        n_head=cfg.n_head, head_dim=cfg.head_dim,
        kv_lora_rank=cfg.kv_lora_rank, qk_nope_dim=cfg.qk_nope_dim,
        qk_rope_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
        top_k=cfg.top_k, n_group=cfg.n_group, topk_group=cfg.topk_group,
        routed_scale=cfg.routed_scale,
        held_first=cfg.held[0] if cfg.held else 0,
        gate_bound=cfg.gate_bound, rope_theta=cfg.rope_theta,
        rms_eps=cfg.rms_eps, bias_update_speed=cfg.bias_update_speed,
        query_block=16, scan_block=16, row_block=32), **changed})


def vectors(cfg):
    """The vectors that are not what they start as, in the order their keys
    are drawn."""
    for i in range(cfg.n_layer):
        layer = f"layer_{i}"
        yield kit.Vector((layer, "input_norm"), 0.2)
        yield kit.Vector((layer, "post_norm"), 0.2)
        if cfg.kind(i) == model.KDA:
            yield kit.Vector((layer, model.KDA, "head_norm", "scale"), 0.3)
            # decays over the whole of (-5, 0), a channel its own
            yield kit.Vector((layer, model.KDA, "A_log"), 0.3, start=0.0)
            yield kit.Vector((layer, model.KDA, "dt_bias"), 1.0, start=0.0)
        else:
            yield kit.Vector((layer, model.MLA, "kv_a_norm", "scale"), 0.3)
        if i in cfg.moe_layers:
            yield kit.Vector(
                (layer, "moe", "router", model.ROUTING_BIAS), 0.1)
    yield kit.Vector(("norm_f",), 0.2)


@kit.once
def make_params(seed=0, cfg=F32):
    """Seeded weights four times as wide, and vectors that are not what
    they start as."""
    return kit.drawn(lambda key: model.init_params(key, cfg), seed,
                     vectors(cfg), narrow=("conv",),
                     sequence=(100 + seed, 96))


def make_tokens(seed=0, batch=BATCH):
    return kit.tokens(50 + seed, batch, SEQ, F32.vocab_size)


def system_logits(params, tokens, cfg=F32):
    """A program of its own a call: what a fault's patch needs."""
    return jax.jit(lambda p, t: model.forward(
        layers.cast_weights(p, cfg.compute_dtype), t, cfg)[0])(params, tokens)


@kit.once
def sound_reference_logits(seed=0):
    """The reference's logits of `make_params(seed)` on
    `make_tokens(seed)`, one jitted program."""
    return jax.jit(lambda p, b, t: reference.logits(p, b, t, sizes()))(
        *to_reference(make_params(seed)), make_tokens(seed)[:, :-1])


@kit.once
def sound_system_logits(seed=0):
    return system_logits(make_params(seed), make_tokens(seed)[:, :-1])


# -- against the reference ----------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_the_forward_pass_matches_the_reference_in_float32(seed):
    want = sound_reference_logits(seed)
    assert float(jnp.std(want)) > 0.5
    assert max_diff(sound_system_logits(seed), want) < F32_TOL * 10


def test_the_stream_after_every_layer_matches():
    params, tokens = make_params(), make_tokens()[0, :-1]
    want, _ = reference.streams(*to_reference(params), tokens, sizes())
    _, got = model.hidden(params, tokens[None], F32, streams=True)
    assert len(got) == len(want) == F32.n_layer
    for g, w in zip(got, want):
        assert max_diff(g[0], w) < F32_TOL * 10


def test_gradients_of_every_leaf_match():
    params, tokens = make_params(), make_tokens()
    got = jax.jit(jax.grad(lambda p: model.loss_fn(
        p, {"tokens": tokens}, F32)[0]))(params)
    ref_params, biases = to_reference(params)
    want = from_reference(jax.grad(
        lambda p: reference.losses(p, biases, tokens, sizes())[0])(
            ref_params), [jnp.zeros_like(b) for b in biases])
    flat = jax.tree_util.tree_flatten_with_path(got)[0]
    assert len(flat) == len(jax.tree.leaves(want))
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-6
        assert max_diff(g, w) < 2e-4 * scale + 1e-7, jax.tree_util.keystr(path)


def test_three_steps_match_the_reference_program():
    """AdamW on every leaf but the routing biases, which move by their rule:
    the losses, and the biases after three steps."""
    params, tokens = make_params(), make_tokens()
    ref_params, biases = kit.own(to_reference(params))
    want = reference.first_losses(
        ref_params, biases, jnp.stack([tokens] * 3), sizes(), OPTIMIZER)
    optimizer = model.trained_by(reference.adamw(OPTIMIZER))
    step = jax.jit(model.make_train_step(F32, optimizer))
    state, got = optimizer.init(params), []
    before = params["layer_1"]["moe"]["router"][model.ROUTING_BIAS]
    for _ in range(3):
        params, state, out = step(params, state, {"tokens": tokens})
        got.append(float(out["loss"]))
    assert want[0] > want[1] > want[2]
    # every leaf's gradient matches to 2e-4 of its largest (the test above);
    # AdamW's first steps move an element by the learning rate whatever its
    # gradient's size, so the few of them whose gradient is float32's noise
    # (one in 2,000 to 30,000 of six leaves) go the other way on one side
    np.testing.assert_allclose(got[:1], want[:1], atol=2e-5)
    np.testing.assert_allclose(got, want, atol=5e-3)
    moved = params["layer_1"]["moe"]["router"][model.ROUTING_BIAS] - before
    assert float(jnp.max(jnp.abs(moved))) <= 3 * F32.bias_update_speed + 1e-7
    assert float(jnp.max(jnp.abs(moved))) > 0


def test_bfloat16_compute_stays_near():
    """At these widths (weights four times as wide, 16 experts in 4 groups)
    bfloat16 moves many a token's choice of experts, so the band is wide: a
    quarter of the logits' norm, where float32 stands at a millionth."""
    params, tokens = make_params(), make_tokens()[:, :-1]
    want = sound_reference_logits()
    moved = system_logits(params, tokens, BF16) - want
    share = float(jnp.linalg.norm(moved) / jnp.linalg.norm(want))
    assert 1e-4 < share < 0.3, share


def test_a_recomputed_stack_is_the_same_step():
    params, batch = make_params(), {"tokens": make_tokens()}
    grads = lambda cfg: jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, batch, cfg)[0]))(params)
    (loss, want), (again, got) = grads(
        dataclasses.replace(F32, remat=False)), grads(F32)
    assert abs(float(loss) - float(again)) < 1e-6
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert max_diff(g, w) < 1e-4 * (float(jnp.max(jnp.abs(w))) + 1e-6)


# what `keep_plan` gave the ling cell with the rule's o among its marks (my
# no-chip compile, PR 68, of the parent: `tools/aot_collectives.py`)
PARENT_KEPT = ("ffn/moe/route", "attention/gate")
PARENT_DECLINED = ("kda/rule", "attention/latent_down", "attention/out",
                   "kda/out_proj", "attention/qkv", "kda/proj", "ffn/hidden",
                   "attention/latent_up", "kda/conv")


@pytest.mark.parametrize("room", ["parents", "its_own"])
def test_the_cells_plan_neither_marks_nor_weighs_the_rules_o(room):
    """The stack of the ling cell at its shape (1 x 16,384 at the published
    widths, abstract) under a v5e's limit.  `kda/rule` is no mark any more
    (kept, the rule's o would drop no kernel: the replay runs the forward
    kernel for the backward's states), so in the room the parent had the
    plan keeps the same names and declines one fewer.  Its own room is wider
    by what the reserve held for the unmarked o, `_LIVE_LAYERS` times its
    64 MiB, and the one latent-attention layer's two narrow products fit."""
    config = registry.config("ling-3.0-flash-ep64")
    traffic = registry.traffic("resident-16k")
    family = registry.family(config)
    cfg = family.model_config()
    params = jax.eval_shape(family._init, jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(family.optimizer().init, params)
    cast = jax.eval_shape(
        lambda p: layers.cast_weights(p, cfg.compute_dtype), params)
    x = jax.ShapeDtypeStruct((traffic["batch"], traffic["seq"], cfg.n_embd),
                             cfg.compute_dtype)
    o_bytes = x.size // cfg.n_embd * cfg.n_head * cfg.head_dim * 2
    calls = [(x, cast[f"layer_{i}"], cfg) for i in range(cfg.n_layer)]
    behind = jax.ShapeDtypeStruct((cfg.loss_chunk_rows, cfg.vocab_size),
                                  jnp.float32)
    with layers._telling(
            state_bytes=layers.state_bytes(params, opt_state,
                                           cfg.compute_dtype),
            memory_limit=int(15.75 * 2 ** 30)):
        own = layers.keep_plan(model._layer, calls, (2,), behind)
        plan = own if room == "its_own" else layers.keep_plan(
            model._layer, calls, (2,), behind,
            room=own["room"] - int(layers._LIVE_LAYERS * o_bytes))
    assert "kda/rule" not in plan["marked"]
    assert "kda/rule" not in layers.KEPT_NAMES
    gib = 2.0 ** 30
    if room == "parents":
        assert abs(plan["room"] / gib - 0.209) < 1e-3
        assert plan["names"] == PARENT_KEPT
        assert plan["declined"] == PARENT_DECLINED[1:]
    else:
        assert abs(plan["room"] / gib - 0.366) < 1e-3
        assert plan["names"] == (
            "ffn/moe/route", "attention/latent_down", "attention/gate",
            "attention/out")
        assert abs(plan["bytes_kept"] / gib - 0.299) < 1e-3
        assert set(plan["declined"]) == set(PARENT_DECLINED[1:]) - set(
            plan["names"])


def test_heads_of_128_take_the_kernels_and_match():
    """A KDA head as wide as the published one: the Pallas kernels
    (interpreted) inside the model's step, against the reference."""
    cfg = dataclasses.replace(F32, n_layer=2, head_dim=128, kda_chunk=64)
    params, tokens = make_params(cfg=cfg), make_tokens()
    with warnings.catch_warnings():
        warnings.simplefilter("error", KdaFallbackWarning)
        with tracing.timeline_span("train.fit", root=True) as job:
            got = jax.jit(jax.value_and_grad(lambda p: model.loss_fn(
                p, {"tokens": tokens}, cfg)[0]))(params)
            assert tracing.counter("kda.rule_kernel") >= 1
            assert tracing.counter("kda.rule_plain") == 0
            assert tracing.counter("kda.bwd_kernel") >= 1
            # a kernel a pass: no second forward for the backward's states
            assert tracing.counter("kda.kernel_passes") == tracing.counter(
                "kda.rule_kernel") + tracing.counter("kda.bwd_kernel")
            # both of this size's heads a grid step in every one of them
            assert tracing.counter("kda.heads_per_step") \
                == cfg.n_head * tracing.counter("kda.kernel_passes")
            assert tracing.counter("moe.route_groups") == 1
            # q's and k's L2 norms and the head's norm with its gate, the
            # rows of each, a traced KDA layer
            assert tracing.counter("kda.head_norm_rows_fused") \
                == 3 * BATCH * SEQ * tracing.counter("kda.layers") > 0
        tracing.timeline_take(job.trace_id)
    ref_params, biases = to_reference(params)
    want = jax.value_and_grad(lambda p: reference.losses(
        p, biases, tokens, sizes(cfg))[0])(ref_params)
    assert abs(float(got[0]) - float(want[0])) < 1e-5
    want = from_reference(want[1], [jnp.zeros_like(b) for b in biases])
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want)):
        assert max_diff(g, w) < 2e-4 * float(jnp.max(jnp.abs(w))) + 1e-7


@pytest.mark.parametrize("head_dim", [16, 128])
def test_the_flat_path_is_the_norms_on_the_view(head_dim):
    """`_unit` and `_gated_head_norm` over (B, S, H D) rows against the
    arithmetic PR 65 ran on a (B, S, H, D) view (`_l2`, `_head_norm`,
    `_out_gate`: a mixer traced while one of them is not the module's own
    runs them, which is how the benchmark's seeded faults reach it): the
    logits and every leaf's gradient.  Heads of 16 take the rule's plain
    form and count no row; heads of 128 its kernels, interpreted."""
    cfg = dataclasses.replace(F32, n_layer=2, head_dim=head_dim,
                              kda_chunk=64 if head_dim == 128 else 32)
    params, tokens = make_params(cfg=cfg), make_tokens()

    def run():
        with tracing.timeline_span("train.fit", root=True) as job:
            out = jax.jit(lambda p: (
                model.forward(p, tokens[:, :-1], cfg)[0],
                jax.grad(lambda p: model.loss_fn(
                    p, {"tokens": tokens}, cfg)[0])(p)))(params)
            rows = tracing.counter("kda.head_norm_rows_fused")
        tracing.timeline_take(job.trace_id)
        return out, rows

    got, rows = run()
    assert rows == (3 * BATCH * SEQ * 2 if head_dim == 128 else 0)
    same = lambda f: lambda *a: f(*a)       # the arithmetic, not the object
    with contextlib.ExitStack() as patches:
        for name in ("_l2", "_head_norm", "_out_gate"):
            patches.enter_context(patched(model, name, same))
        want, viewed_rows = run()
    assert viewed_rows == 0
    assert max_diff(got[0], want[0]) < F32_TOL
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got[1])[0],
                            jax.tree.leaves(want[1])):
        scale = float(jnp.max(jnp.abs(w))) + 1e-6
        assert max_diff(g, w) < 2e-4 * scale + 1e-7, jax.tree_util.keystr(path)


# -- the seeded faults --------------------------------------------------------

@pytest.mark.parametrize("name", sorted(FAULTS))
def test_a_seeded_fault_moves_the_logits_past_the_margin(name):
    """The system under each fault of ISSUE 65 against the reference: the
    float32 logits differ by `FAULT_MARGIN` and more."""
    config = registry.config("ling-3.0-flash-ep64", rehearse=True)
    params, tokens = make_params(), make_tokens()[:, :-1]
    want = sound_reference_logits()
    with FAULTS[name](config).patch():
        moved = max_diff(system_logits(params, tokens), want)
    # not a number (the decay without its bound overflows) fails as well
    assert not moved <= FAULT_MARGIN, (name, moved)


# -- the shares add up ----------------------------------------------------------

def _columns(kernel, heads, width, of):
    """The columns of ``heads`` (a slice) out of ``of`` heads ``width``
    wide."""
    rows = kernel.shape[0]
    return kernel.reshape(rows, of, width)[:, heads].reshape(rows, -1)


def _kda_share(p, heads, cfg, of):
    d = cfg.head_dim
    part = lambda name, w=d: {"kernel": _columns(p[name]["kernel"], heads,
                                                 w, of)}
    qkv = p["qkv_proj"]["kernel"].reshape(-1, 3, of, d)[:, :, heads]
    taps = p["conv"]["kernel"].reshape(3, of, d, -1)[:, heads]
    return {
        "qkv_proj": {"kernel": qkv.reshape(qkv.shape[0], -1)},
        "conv": {"kernel": taps.reshape(-1, taps.shape[-1])},
        "f_proj": part("f_proj"), "g_proj": part("g_proj"),
        "b_proj": part("b_proj", 1), "A_log": p["A_log"][heads],
        "dt_bias": p["dt_bias"].reshape(of, d)[heads].reshape(-1),
        "head_norm": p["head_norm"],
        "o_proj": {"kernel": p["o_proj"]["kernel"].reshape(
            of, d, -1)[heads].reshape(-1, cfg.n_embd)}}


def _mla_share(p, heads, cfg, of):
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    kv = cfg.qk_nope_dim + cfg.v_head_dim
    return {
        "q_proj": {"kernel": _columns(p["q_proj"]["kernel"], heads, qk, of)},
        "kv_a_proj": p["kv_a_proj"], "kv_a_norm": p["kv_a_norm"],
        "kv_b_proj": {"kernel": _columns(p["kv_b_proj"]["kernel"], heads, kv,
                                         of)},
        "g_proj": {"kernel": _columns(p["g_proj"]["kernel"], heads, 1, of)},
        "o_proj": {"kernel": p["o_proj"]["kernel"].reshape(
            of, cfg.v_head_dim, -1)[heads].reshape(-1, cfg.n_embd)}}


def test_the_shares_of_heads_and_experts_add_up_to_the_uncut_layer():
    """All 4 heads and all 16 experts drawn once; the system run on each
    HALF of the heads and on each of the four shares of 4 experts, every
    share given what the configuration says a chip holds (its heads' columns
    and rows, its experts' stacks; the latent's down-projection, the router
    and the shared expert whole): the halves' mixers add up to the uncut
    reference's mixer, both kinds, and the shares' routed sums + the shared
    expert ONCE to its mixture."""
    whole = dataclasses.replace(F32, n_head=4, held=None)
    half = dataclasses.replace(F32, n_head=2)
    params = make_params(cfg=whole)
    ref_params, biases = to_reference(params)
    u = jax.random.normal(jax.random.PRNGKey(7), (SEQ, whole.n_embd))
    uncut = sizes(whole)
    halves = (slice(0, 2), slice(2, 4))
    for i, mixer, share, ours in (
            (1, reference.kda, _kda_share, model._kda_mixer),
            (4, reference.attention, _mla_share,
             lambda u, p, cfg: layers.latent_attention(u, p, cfg,
                                                       gated=True))):
        assert whole.kind(i) == (model.KDA if mixer is reference.kda
                                 else model.MLA)
        want = mixer(u, ref_params["layers"][i], uncut)
        p = params[f"layer_{i}"][whole.kind(i)]
        got = sum(ours(u[None], share(p, heads, whole, 4), half)[0]
                  for heads in halves)
        assert max_diff(got, want) < F32_TOL
        one = ours(u[None], share(p, halves[0], whole, 4), half)[0]
        assert max_diff(one, want) > 100 * F32_TOL      # a half is no whole
    moe = params["layer_1"]["moe"]
    want, _ = reference.moe(u, ref_params["layers"][1], biases[0], uncut)
    shared = layers.dense_ffn(u, moe["shared"], layers.swiglu)
    got = shared
    for first in range(0, 16, 4):
        held = {**moe, **{name: moe[name][first:first + 4]
                          for name in ("wi_gate", "wi_up", "wo")}}
        y, _ = layers.routed_layer(
            u[None], held, model._route(whole), 16, (first, 4), layers.swiglu)
        got = got + (y[0] - shared)     # a share's sum holds the shared once
    assert max_diff(got, want) < F32_TOL * 4


# -- the kinds by their published index ---------------------------------------

def test_forty_two_layers_place_both_kinds_where_the_published_indices_do():
    cfg = model.LING_3_FLASH
    kinds = [cfg.kind(i) for i in range(cfg.n_layer)]
    assert [i for i, k in enumerate(kinds) if k == model.MLA] \
        == [5, 11, 17, 23, 29, 35, 41]
    assert kinds.count(model.KDA) == 35
    assert list(cfg.moe_layers) == list(range(2, 42))
    cut = Family(registry.config("ling-3.0-flash-ep64")).model_config()
    assert [cut.published(i) for i in range(cut.n_layer)] \
        == [0, 2, 3, 4, 5, 6, 7]
    assert [cut.kind(i) for i in range(cut.n_layer)] \
        == [model.KDA] * 4 + [model.MLA] + [model.KDA] * 2
    assert list(cut.moe_layers) == [1, 2, 3, 4, 5, 6]


def test_counts_at_the_published_widths():
    """The cell's seven layers, 16 of 32 heads, 8 of 512 experts and an
    eighth of the vocabulary count 680 M (ISSUE 65's arithmetic), and the
    family's count is the tree's."""
    config = registry.config("ling-3.0-flash-ep64")
    family = Family(config)
    cfg = family.model_config()
    cut = jax.eval_shape(lambda key: model.init_params(key, cfg),
                         jax.random.PRNGKey(0))
    assert model.num_params(cut) == family.param_count()
    assert round(family.param_count() / 1e6) == 680
    assert round((family.mixer_matrices(model.KDA)
                  + family.mixer_vectors(model.KDA)) / 1e6, 1) == 31.5
    assert round((family.mixer_matrices(model.MLA)
                  + family.mixer_vectors(model.MLA)) / 1e6, 1) == 16.7
    assert family.flops_per_token(16384) == pytest.approx(
        model.count_flops_per_token(cfg, 16384))
    assert family.rule_flops_per_token() \
        == model.rule_flops_per_token(cfg) == 16 * 262144
    cost = family.kda_cost(1, 16384)
    assert cost["flops"] == 6 * 3 * 16384 * 16 * 262144
    assert cost["bytes"] == 6 * 16384 * 16 * (1540 + 2824)


# -- the names sharding reads -------------------------------------------------

@pytest.mark.parametrize("fsdp", [1, 4])
def test_the_leaves_resolve_under_a_layout(fsdp):
    from ray_tpu.parallel.sharding import (ShardingConfig,
                                           infer_param_logical_dims,
                                           param_shardings)

    shapes = jax.eval_shape(
        lambda key: model.init_params(key, F32), jax.random.PRNGKey(0))
    dims = {"/".join(str(getattr(k, "key", k)) for k in path):
            infer_param_logical_dims(
                tuple(getattr(k, "key", k) for k in path), leaf.shape)
            for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    kda = "layer_0/kda/"
    assert dims[kda + "qkv_proj/kernel"] == ("embed", "heads")
    assert dims[kda + "conv/kernel"] == ("heads", None)
    assert dims[kda + "f_proj/kernel"] == ("embed", "heads")
    assert dims[kda + "b_proj/kernel"] == ("embed", "heads")
    assert dims[kda + "g_proj/kernel"] == ("embed", "heads")
    assert dims[kda + "A_log"] == ("heads",)
    assert dims[kda + "dt_bias"] == ("heads",)
    assert dims[kda + "head_norm/scale"] == (None,)
    assert dims[kda + "o_proj/kernel"] == ("heads", "embed")
    mla = "layer_4/attn/"
    assert dims[mla + "q_proj/kernel"] == ("embed", "heads")
    assert dims[mla + "g_proj/kernel"] == ("embed", "heads")
    assert dims[mla + "o_proj/kernel"] == ("heads", "embed")
    assert dims["layer_1/moe/router/kernel"] == ("embed", None)
    assert dims["layer_1/moe/wi_gate"] == ("expert", "embed", "mlp")
    layout = ShardingConfig(fsdp=fsdp)
    mesh = layout.build_mesh(jax.devices()[:fsdp])
    placed = param_shardings(shapes, layout, mesh)
    cut = {"/".join(str(getattr(k, "key", k)) for k in path)
           for (path, s), leaf in zip(
               jax.tree_util.tree_flatten_with_path(placed)[0],
               jax.tree.leaves(shapes))
           if s.shard_shape(leaf.shape) != leaf.shape}
    assert bool(cut) == (fsdp > 1)
    if fsdp > 1:        # no matrix of a KDA mixer is left whole
        assert {kda + n for n in (
            "qkv_proj/kernel", "f_proj/kernel", "g_proj/kernel",
            "o_proj/kernel")} <= cut

"""The `phi4flash` model (`ray_tpu/models/phi4flash.py`: five kinds of layer
by their published index, Mamba-1 mixers, differential attention under a
window and not, a memory unit and a cross layer that read what two earlier
layers made) against the plain reference (`benchmark/reference/phi4flash.py`:
float32 `jax.numpy`, the recurrence position by position, each softmax a
masked softmax with the key heads repeated) at a small size on the CPU:
published layers 2..7 of 8, hidden 64, 8 query heads on 4 key/value heads of
8, 128 channels with a state of 4 and a rank of 4, a window of 24, sequence
64, vocabulary 512, seeded random weights.

The matrices are drawn four times as wide as the assumed 0.02 and A_log, D,
the gains and the biases are not what they start as: at 0.02 and these
widths a mixer's output is a thousandth of the residual stream and a fault
would hide under any tolerance.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import model_kit as kit
import numpy as np
import pytest
from model_kit import max_diff

from benchmark.families.phi4flash import Family, from_reference, to_reference
from benchmark.harness import registry
from benchmark.reference import phi4flash as reference
from benchmark.tests.phi4flash_faults import FAULTS
from ray_tpu.models import layers, phi4flash as model
from ray_tpu.util import tracing

BF16 = model.PHI4FLASH_TINY
F32 = dataclasses.replace(BF16, compute_dtype=jnp.float32)
BATCH, SEQ = 2, 64
OPTIMIZER = {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
             "weight_decay": 0.1}
# float32 compute: only the order of the sums differs (flash blocks against
# a whole softmax, the kernels' walk against the reference's)
F32_TOL = 5e-5
# bfloat16 compute against the float32 reference, logits of size up to 3;
# every seeded fault below moves the float32 logits by more
BF16_LOGITS_TOL = 0.1


def sizes(cfg=F32):
    return reference.Sizes(
        kinds=tuple(cfg.kind(i) for i in range(cfg.n_layer)),
        lambdas=tuple(cfg.lambda_init(i) for i in range(cfg.n_layer)),
        n_head=cfg.n_head, n_kv_head=cfg.n_kv_head, window=cfg.window,
        d_state=cfg.d_state, dt_rank=cfg.dt_rank, norm_eps=cfg.norm_eps,
        rms_eps=cfg.rms_eps, query_block=16, scan_block=16, row_block=32)


pytestmark = pytest.mark.usefixtures("highest_precision")


def vectors(cfg):
    """The vectors that are not what they start as, in the order their keys
    are drawn."""
    for i in range(cfg.n_layer):
        layer, kind = f"layer_{i}", cfg.kind(i)
        yield kit.Vector((layer, "norm1"), 0.2)
        yield kit.Vector((layer, "norm2"), 0.2)
        if kind == model.MAMBA:
            yield kit.Vector((layer, kind, "D"), 0.5)
            yield kit.Vector((layer, kind, "A_log"), 0.5)
            # steps of a half to two: the decays differ by their state index
            yield kit.Vector((layer, kind, "dt_proj", "bias"), plus=5.0)
        elif kind != model.GMU:
            yield kit.Vector((layer, kind, "diff_norm", "scale"), 0.3)
            # a cross layer reads another's k and v
            for name in ("o_proj", "q_proj") if kind == model.CROSS \
                    else ("k_proj", "o_proj", "q_proj", "v_proj"):
                yield kit.Vector((layer, kind, name, "bias"), 0.1)
    yield kit.Vector(("norm_f",), 0.2)


@kit.once
def make_params(seed=0, cfg=F32):
    """Seeded weights four times as wide, and vectors that are not what
    they start as."""
    return kit.drawn(lambda key: model.init_params(key, cfg), seed,
                     vectors(cfg), narrow=("conv", "A_log"),
                     sequence=(100 + seed, 64))


def make_tokens(seed=0, batch=BATCH):
    return kit.tokens(50 + seed, batch, SEQ, F32.vocab_size)


def system_logits(params, tokens, cfg=F32):
    """A program of its own a call: what a fault's patch needs."""
    return jax.jit(lambda p, t: model.forward(
        layers.cast_weights(p, cfg.compute_dtype), t, cfg))(params, tokens)


@kit.once
def sound_reference_logits(seed=0):
    """The reference's logits of `make_params(seed)` on
    `make_tokens(seed)`, one jitted program."""
    return jax.jit(lambda p, t: reference.logits(p, t, sizes()))(
        to_reference(make_params(seed)), make_tokens(seed)[:, :-1])


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
    want = reference.streams(to_reference(params), tokens, sizes())
    _, got = model.hidden(params, tokens[None], F32, streams=True)
    assert len(got) == len(want) == F32.n_layer
    for g, w in zip(got, want):
        assert max_diff(g[0], w) < F32_TOL * 10


def test_gradients_of_every_leaf_match():
    params, tokens = make_params(), make_tokens()
    got = jax.jit(jax.grad(lambda p: model.loss_fn(
        p, {"tokens": tokens}, F32)[0]))(params)
    want = from_reference(
        jax.grad(reference.losses)(to_reference(params), tokens, sizes()),
        sizes().kinds)
    flat = jax.tree_util.tree_flatten_with_path(got)[0]
    assert len(flat) == len(jax.tree.leaves(want))
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-6
        assert max_diff(g, w) < 2e-4 * scale + 1e-7, jax.tree_util.keystr(path)


def test_three_steps_match_the_reference_program():
    params, tokens = make_params(), make_tokens()
    want = reference.first_losses(
        kit.own(to_reference(params)),
        jnp.stack([tokens] * 3), sizes(), OPTIMIZER)
    optimizer = reference.adamw(OPTIMIZER)
    step = jax.jit(model.make_train_step(F32, optimizer))
    state, got = optimizer.init(params), []
    for _ in range(3):
        params, state, out = step(params, state, {"tokens": tokens})
        got.append(float(out["loss"]))
    assert want[0] > want[1] > want[2]
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_bfloat16_compute_stays_close():
    params, tokens = make_params(), make_tokens()[:, :-1]
    moved = max_diff(system_logits(params, tokens, BF16),
                     sound_reference_logits())
    assert F32_TOL < moved < BF16_LOGITS_TOL


def test_a_recomputed_stack_is_the_same_step():
    """`remat` on and off: the same loss and the same gradients (what the
    layers hand on is a result of its maker's recomputed pass)."""
    params, batch = make_params(), {"tokens": make_tokens()}
    grads = lambda cfg: jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, batch, cfg)[0]))(params)
    (loss, want), (again, got) = grads(
        dataclasses.replace(F32, remat=False)), grads(F32)
    assert abs(float(loss) - float(again)) < 1e-6
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert max_diff(g, w) < 1e-5 * (float(jnp.max(jnp.abs(w))) + 1e-6)


# -- the seeded faults --------------------------------------------------------

@pytest.mark.parametrize("name", sorted(FAULTS))
def test_a_seeded_fault_moves_the_logits_past_the_margin(name):
    """The system under each fault of ISSUE 63 against the reference: the
    logits differ by more than the bfloat16 band, far more than float32's
    tolerance."""
    config = registry.config("phi-4-mini-flash-reasoning-vp8", rehearse=True)
    params, tokens = make_params(), make_tokens()[:, :-1]
    want = sound_reference_logits()
    # that the system as it is stands inside the tolerance is the first
    # test of this file; here, that the fault's patch leaves it as it was
    with kit.patches_undone():
        with FAULTS[name](config).patch():
            moved = max_diff(system_logits(params, tokens), want)
    assert moved > BF16_LOGITS_TOL > F32_TOL, (name, moved)


# -- the kinds by their published index ---------------------------------------

def test_thirty_two_layers_place_every_kind_where_the_published_indices_do():
    cfg = dataclasses.replace(F32, n_layer=32, first_layer=0, n_published=32)
    kinds = [cfg.kind(i) for i in range(32)]
    assert kinds[0:17:2] == [model.MAMBA] * 9
    assert kinds[1:16:2] == [model.WINDOW] * 8
    assert kinds[17] == model.FULL
    assert kinds[18::2] == [model.GMU] * 7
    assert kinds[19::2] == [model.CROSS] * 7
    assert cfg.lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))
    shapes = jax.eval_shape(lambda key: model.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    for i, kind in enumerate(kinds):
        assert set(shapes[f"layer_{i}"]) == {"norm1", "norm2", "mlp", kind}
    assert "k_proj" not in shapes["layer_19"][model.CROSS]
    # the cut of the cell is the same model's layers 14..19
    cut = dataclasses.replace(cfg, n_layer=6, first_layer=14)
    assert [cut.kind(i) for i in range(6)] == kinds[14:20]
    assert [cut.lambda_init(i) for i in range(6)] \
        == [cfg.lambda_init(i) for i in range(14, 20)]
    with tracing.timeline_span("train.fit", root=True) as job:
        jax.eval_shape(lambda p, t: model.forward(p, t, cfg), shapes,
                       jax.ShapeDtypeStruct((1, SEQ), jnp.int32))
        assert tracing.counter("shared.memory_readers") == 7
        assert tracing.counter("shared.kv_readers") == 7
        assert tracing.counter("attention.diff_pairs") == 16 * 4
        assert tracing.counter("sscan.positions") == 9 * SEQ
        # the middle layer's y and the full layer's k and v, bfloat16 here
        held = SEQ * (cfg.channels + 2 * cfg.n_kv_head * cfg.head_dim)
        assert tracing.counter("shared.bytes_kept") == held * 4
    tracing.timeline_take(job.trace_id)


def test_counts_at_the_published_widths():
    """The whole model by the configuration's sizes is the published 3.8 B;
    the cell's six layers and an eighth of the vocabulary 697.3 M (ISSUE
    63's arithmetic), and the family's count is the tree's."""
    whole = jax.eval_shape(lambda key: model.init_params(
        key, model.PHI4_MINI_FLASH), jax.random.PRNGKey(0))
    assert round(model.num_params(whole) / 1e9, 2) == 3.85
    config = registry.config("phi-4-mini-flash-reasoning-vp8")
    family = Family(config)
    cut = jax.eval_shape(lambda key: model.init_params(
        key, family.model_config()), jax.random.PRNGKey(0))
    assert model.num_params(cut) == family.param_count()
    assert round(family.param_count() / 1e6, 1) == 697.3
    cfg = family.model_config()
    assert family.flops_per_token(16384) == pytest.approx(
        model.count_flops_per_token(cfg, 16384))
    assert model.attended_pairs(16384, 512) == family.attended_pairs(
        16384, model.WINDOW) == 512 * 513 // 2 + (16384 - 512) * 512


def test_the_initialisation_is_the_assumed_one():
    params = model.init_params(jax.random.PRNGKey(3), F32)
    m = params["layer_0"][model.MAMBA]
    np.testing.assert_allclose(
        m["A_log"], np.broadcast_to(np.log(np.arange(1, 5)), (128, 4)),
        rtol=1e-6)
    assert (np.asarray(m["D"]) == 1).all()
    dt = np.asarray(jax.nn.softplus(m["dt_proj"]["bias"]))
    assert (dt >= 0.001 - 1e-6).all() and (dt <= 0.1 + 1e-6).all()
    assert float(jnp.max(jnp.abs(m["conv"]["kernel"]))) <= 0.5
    a = params["layer_1"][model.WINDOW]
    assert float(jnp.std(a["q_proj"]["kernel"])) == pytest.approx(0.02,
                                                                  rel=0.1)
    assert float(jnp.std(a["lambda_q1"])) == pytest.approx(0.1, rel=0.6)
    assert not np.asarray(a["q_proj"]["bias"]).any()
    assert "lm_head" not in params          # the head is the embedding


# -- the trunk ----------------------------------------------------------------

def _parent_trunk(params, tokens, layer, cfg, walks=None):
    """`layers.trunk` as the parent of PR 63 had it, word for word but for
    the names it reads from `layers`."""
    with jax.named_scope("embed"):
        x = params["embed_tokens"]["embedding"][tokens].astype(
            cfg.compute_dtype)
    stack = [params[f"layer_{i}"] for i in range(cfg.n_layer)]
    if cfg.remat:
        layer = layers.checkpoint_layer(
            layer, stack=[(x, p, cfg) for p in stack] * (walks or 1),
            static_argnums=(2,),
            behind=jax.ShapeDtypeStruct(
                (cfg.loss_chunk_rows, cfg.vocab_size), jnp.float32))

    def walk(x):
        seconds = []
        for p in stack:
            if walks is not None:
                tracing.count("loop.layer_traces")
            x, second = layer(x, p, cfg)
            if second is not None:
                seconds.append(second)
        return layers.rms_norm(x, params["norm_f"], cfg.rms_eps), seconds

    if walks is None:
        return walk(x)
    tracing.count("loop.walks", walks)
    tracing.count("loop.layer_calls", walks * len(stack))
    states, seconds = [], []
    for _ in range(walks):
        x, more = walk(x)
        states.append(x)
        seconds += more
    return jnp.stack(states), seconds


def _families():
    from ray_tpu.models import (deepseek_v3, keye_vl, laguna, lfm2_moe,
                                mellum, nemotron_h, olmoe, ouro)
    return [(olmoe, olmoe.OLMOE_TINY, None),
            (deepseek_v3, deepseek_v3.DEEPSEEK_V3_TINY, None),
            (lfm2_moe, lfm2_moe.LFM2_MOE_TINY, None),
            (nemotron_h, nemotron_h.NEMOTRON_H_TINY, None),
            (keye_vl, keye_vl.KEYE_VL_TINY, None),
            (mellum, mellum.MELLUM_TINY, None),
            (laguna, laguna.LAGUNA_TINY, None),
            (ouro, ouro.OURO_TINY, ouro.OURO_TINY.n_walk)]


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("module,cfg,walks", _families(),
                         ids=lambda v: getattr(v, "__name__", None))
def test_every_other_familys_jaxpr_of_the_trunk_is_what_it_was(
        module, cfg, walks, remat):
    """A model that hands nothing on walks as it always did: `trunk`'s
    jaxpr over its layer is the parent's, recomputed or not."""
    cfg = dataclasses.replace(cfg, remat=remat)
    params = module.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((2, 32), jnp.int32)
    # a policy prints as a function at its address
    trace = lambda trunk: re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(
        lambda p, t: trunk(p, t, module._layer, cfg, walks))(params, tokens)))
    assert trace(layers.trunk) == trace(_parent_trunk)


def test_what_is_handed_on_is_counted_as_held_whatever_the_room():
    """`keep_plan` over the six layers: the handed-on tensors are in
    `already`, once, whatever the budget keeps beside them."""
    params = jax.eval_shape(lambda key: model.init_params(key, BF16),
                            jax.random.PRNGKey(0))
    x = jnp.zeros((1, SEQ, BF16.n_embd), jnp.bfloat16)
    plans = []
    with layers.assume_memory_limit(1 << 30, plans), \
            layers._telling(state_bytes=0):
        jax.eval_shape(lambda p, t: model.hidden(p, t, BF16), params,
                       jax.ShapeDtypeStruct((1, SEQ), jnp.int32))
    (plan,) = plans
    handed = SEQ * (BF16.channels + 2 * BF16.n_kv_head * BF16.head_dim) * 2
    streams = BF16.n_layer * x.size * 2
    residuals = plan["already"] - streams - handed
    assert residuals > 0
    # the same stack with nothing handed on keeps that much less
    calls = [(x, params["layer_1"], BF16, None, 1)]
    alone = layers.keep_plan(model._layer, calls, (2, 4), room=0)
    assert alone["already"] == x.size * 2 + sum(
        v for k, v in layers._layer_marks(
            model._layer, calls[0], (2, 4)).items()
        if k in layers.KEPT_RESIDUALS)
    assert {"attention/qkv", "attention/out", "ffn/hidden",
            "ssm/in_proj"} <= set(plan["marked"])


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
    assert dims["embed_tokens/embedding"] == ("vocab", "embed")
    mamba = "layer_0/mamba/"
    assert dims[mamba + "in_proj/kernel"] == ("embed", "mlp")
    assert dims[mamba + "conv/kernel"] == ("embed", None)
    assert dims[mamba + "x_proj/kernel"] == ("embed", None)
    assert dims[mamba + "dt_proj/kernel"] == (None, "embed")
    assert dims[mamba + "dt_proj/bias"] == ("embed",)
    assert dims[mamba + "A_log"] == ("embed", None)
    assert dims[mamba + "D"] == (None,)
    assert dims[mamba + "out_proj/kernel"] == ("heads", "embed")
    for layer, kind in (("layer_1", model.WINDOW), ("layer_3", model.FULL),
                        ("layer_5", model.CROSS)):
        assert dims[f"{layer}/{kind}/q_proj/kernel"] == ("embed", "heads")
        assert dims[f"{layer}/{kind}/o_proj/kernel"] == ("heads", "embed")
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2",
                     "diff_norm/scale", "q_proj/bias", "o_proj/bias"):
            assert dims[f"{layer}/{kind}/{name}"] == (None,), name
    assert dims["layer_4/gmu/in_proj/kernel"] == ("embed", "mlp")
    assert dims["layer_4/gmu/out_proj/kernel"] == ("heads", "embed")
    layout = ShardingConfig(fsdp=fsdp)
    mesh = layout.build_mesh(jax.devices()[:fsdp])
    placed = param_shardings(shapes, layout, mesh)
    cut = {"/".join(str(getattr(k, "key", k)) for k in path)
           for (path, s), leaf in zip(
               jax.tree_util.tree_flatten_with_path(placed)[0],
               jax.tree.leaves(shapes))
           if s.shard_shape(leaf.shape) != leaf.shape}
    assert bool(cut) == (fsdp > 1)
    if fsdp > 1:        # no matrix of a Mamba-1 mixer is left whole
        assert {mamba + n for n in (
            "in_proj/kernel", "x_proj/kernel", "dt_proj/kernel", "A_log",
            "out_proj/kernel", "conv/kernel")} <= cut

"""`models/evabyte.py` against the plain reference
(`benchmark/reference/evabyte.py`): the logits of all prediction heads and
three AdamW losses, at a shape the EVA kernels take (interpreted) and at one
they decline; each seeded fault (`benchmark/tests/evabyte_faults.py`) caught;
the share tied to the model: the two halves of the heads, with the
feed-forward counted once, add up to the uncut reference's layer; the counts
the yardstick copies; the parameters' names and how they start."""

import dataclasses
import json
import os
import warnings

import jax
import jax.numpy as jnp
import model_kit as kit
import numpy as np
import pytest
from model_kit import max_diff

from benchmark.families import evabyte as family_module
from benchmark.reference import evabyte as reference
from benchmark.tests import evabyte_faults
from ray_tpu.models import evabyte, layers
from ray_tpu.ops.eva import EvaFallbackWarning
from ray_tpu.parallel.sharding import param_logical_dims

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = dict(compute_dtype=jnp.float32)
# heads, windows and chunks the kernels take: two windows of sixteen chunks
KERNELS = dataclasses.replace(
    evabyte.EVABYTE_TINY, head_dim=128, window=128, chunk=8,
    loss_chunk_rows=64, remat=True, **F32)
PLAIN = dataclasses.replace(evabyte.EVABYTE_TINY, **F32)
SEQ = {"kernels": 256, "plain": 64}
CONFIGS = {"kernels": KERNELS, "plain": PLAIN}
OPTIMIZER = {"learning_rate": 1e-2, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
             "weight_decay": 0.1}


@pytest.fixture(autouse=True)
def quiet():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", EvaFallbackWarning)
        yield


def sizes(cfg):
    return reference.Sizes(cfg.n_head, cfg.chunk, cfg.window,
                           cfg.n_pred_heads, cfg.rope_theta, cfg.rms_eps,
                           32, 32)


@kit.once
def seeded(cfg, seq, seed=1):
    """Parameters with the norms' w, phi and mu away from where they start,
    and a batch."""
    init = lambda key: evabyte.init_params(key, cfg)
    away = [kit.Vector(tuple(k.key for k in path), 0.2)
            for path, x in jax.tree_util.tree_flatten_with_path(
                jax.eval_shape(init, jax.random.PRNGKey(0)))[0]
            if x.ndim == 1 or "phi" in str(path) or "mu" in str(path)]
    params = kit.drawn(init, seed, away, factor=1.0,
                       sequence=(seed + 1, 64))
    return params, kit.tokens(seed + 2, 2, seq, cfg.vocab_size)


@pytest.mark.parametrize("name", list(CONFIGS))
def test_logits_and_three_adamw_losses_are_the_references(name):
    cfg, seq = CONFIGS[name], SEQ[name]
    params, tokens = seeded(cfg, seq)
    with jax.default_matmul_precision("highest"):
        theirs = family_module.to_reference(params)
        got = evabyte.forward(params, tokens[:, :-1], cfg)
        want = jax.vmap(lambda t: reference.logits(theirs, t, sizes(cfg)))(
            tokens[:, :-1])
        assert got.shape == (2, seq, cfg.n_pred_heads, cfg.vocab_size)
        np.testing.assert_allclose(got, want, atol=2e-5)
        optimizer = reference.adamw(OPTIMIZER)
        step = jax.jit(evabyte.make_train_step(cfg, optimizer))
        state, losses = (params, optimizer.init(params)), []
        for _ in range(3):
            *state, out = step(*state, {"tokens": tokens})
            losses.append(float(out["loss"]))
        want = reference.first_losses(
            kit.own(theirs), jnp.stack([tokens] * 3), sizes(cfg), OPTIMIZER)
    np.testing.assert_allclose(losses, want, atol=2e-5)


def test_the_round_trip_of_the_parameters():
    params, _ = seeded(PLAIN, 64)
    back = family_module.from_reference(family_module.to_reference(params))
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)


# what a fault is seen in: the logits of the first step, or (a fault of the
# loss or of a gradient alone) the loss and phi's gradient
LOSS_FAULTS = ("no_summary_gradient", "target_a_byte_early")
# rounding, not a departure: it passes at every size (PERF.md section 4)
MAY_PASS = ("bf16_stream",)
# faults of what only the kernels' path calls (the flash kernels' rule, the
# pooling's custom gradient) are read at the shape the kernels take
KERNEL_FAULTS = ("local_full_causal", "local_sliding", "no_summary_gradient")


def readings(cfg, seq):
    """(the logits, the loss, phi's gradient in layer 0) of the first step of
    ONE layer, the matrices cast as the step casts them; one jitted function,
    because a fault's patch clears jax's caches."""
    cfg = dataclasses.replace(cfg, n_layer=1)
    params, tokens = seeded(cfg, seq)

    @jax.jit
    def read(params, tokens):
        logits = evabyte.forward(
            layers.cast_weights(params, cfg.compute_dtype), tokens[:, :-1],
            cfg)
        loss, grads = jax.value_and_grad(
            lambda p: evabyte.loss_fn(p, {"tokens": tokens}, cfg)[0])(params)
        return logits, loss, grads["layer_0"]["eva"]["phi"]

    with jax.default_matmul_precision("highest"):
        logits, loss, dphi = read(params, tokens)
    return logits, float(loss), dphi


@kit.once
def sound_readings(which):
    return readings(CONFIGS[which], SEQ[which])


@pytest.mark.parametrize("name", sorted(evabyte_faults.FAULTS))
def test_each_seeded_fault_is_caught(name):
    which = "kernels" if name in KERNEL_FAULTS else "plain"
    cfg, seq = CONFIGS[which], SEQ[which]
    sound = sound_readings(which)
    family = object.__new__(evabyte_faults.FAULTS[name])
    faulty_cfg = dataclasses.replace(cfg, stream_dtype=jnp.bfloat16) \
        if name == "bf16_stream" else cfg
    with family.patch():
        faulty = readings(faulty_cfg, seq)
    moved = max_diff(faulty[0], sound[0])
    scale = float(jnp.max(jnp.abs(sound[0])))
    if name in MAY_PASS:
        assert moved < 0.02 * scale
    elif name in LOSS_FAULTS:
        assert moved == 0.0
        if name == "target_a_byte_early":
            assert abs(faulty[1] - sound[1]) > 1e-3
        else:
            assert max_diff(faulty[2], sound[2]) \
                > 0.1 * float(jnp.max(jnp.abs(sound[2])))
    else:
        # the sound program read twice differs by nothing
        assert moved > 1e-3 * scale, (moved, scale)


def test_the_two_halves_of_the_heads_add_up_to_the_uncut_layer():
    """The share tied to the model: heads 0..1's and heads 2..3's parts of
    W_o's sum, with the feed-forward counted once, are the uncut four-head
    reference's layer."""
    whole = dataclasses.replace(KERNELS, n_head=4, n_head_published=4,
                                n_layer=1)
    half = dataclasses.replace(whole, n_head=2)
    params, tokens = seeded(whole, 256)
    p, D = params["layer_0"], whole.head_dim
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(9), (2, 256, whole.n_embd))

    def share(first):
        cols = slice(first * D, (first + 2) * D)
        m = p["eva"]
        return {"q_proj": {"kernel": m["q_proj"]["kernel"][:, cols]},
                "k_proj": {"kernel": m["k_proj"]["kernel"][:, cols]},
                "v_proj": {"kernel": m["v_proj"]["kernel"][:, cols]},
                "o_proj": {"kernel": m["o_proj"]["kernel"][cols]},
                "phi": m["phi"][first:first + 2],
                "mu": m["mu"][first:first + 2]}

    with jax.default_matmul_precision("highest"):
        u = evabyte._norm(x, p["input_norm"], half)
        with jax.named_scope("eva"):
            parts = [evabyte._mixer(u, share(first), half)
                     for first in (0, 2)]
        h = x + parts[0] + parts[1]
        g = evabyte._norm(h, p["post_norm"], half)
        got = h + layers.dense_ffn(g, p["mlp"], layers.swiglu)
        theirs = family_module.to_reference(params)
        layer = jax.tree.map(lambda leaf: leaf[0], theirs["layers"])
        want = jax.vmap(lambda rows: reference.layer(
            rows, layer, sizes(whole)))(x)
        uncut, _ = evabyte._layer(x, p, whole)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(uncut, want, atol=2e-5)
    # and a half alone is not the layer
    assert float(jnp.max(jnp.abs(x + parts[0] - (h)))) > 1e-3


def cell_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "evabyte-6.5b-4layer.json")) as f:
        return json.load(f)


def test_the_counts_the_yardstick_copies():
    family = family_module.Family(cell_config())
    shapes = jax.eval_shape(family._init, jax.random.PRNGKey(0))
    assert evabyte.num_params(shapes) == family.param_count() == 687_132_672
    cfg = family.model_config()
    assert cfg.n_head == 16 and cfg.n_head_published == 32
    assert cfg.stream_dtype == jnp.float32 and cfg.norm_unit_offset
    seq = 16384
    assert evabyte.count_flops_per_token(cfg, seq) \
        == family.flops_per_token(seq)
    assert evabyte.pool_flops_per_token(cfg) == family.pool_flops_per_token()
    # 1,024.5 + 448 pairs a query; EVA about 3.5 % of the step's operations
    assert family.local_pairs(seq) / seq == 1024.5
    assert family.remote_pairs(seq) / seq == 448.0
    eva = 4 * 6 * 1472.5 * 16 * 2 * 128
    assert 0.03 < eva / family.flops_per_token(seq) < 0.04
    assert round(family.flops_per_token(seq) / 1e9, 2) == 4.26


def test_the_leaves_names_and_where_they_start():
    params = jax.eval_shape(lambda k: evabyte.init_params(k, PLAIN),
                            jax.random.PRNGKey(0))
    dims = {"/".join(str(getattr(k, "key", k)) for k in path): d
            for (path, _), (_, d) in zip(
                jax.tree_util.tree_flatten_with_path(params)[0],
                param_logical_dims(params)[1])}
    assert dims["layer_0/eva/phi"] == dims["layer_0/eva/mu"] \
        == ("heads", None)
    assert dims["layer_0/eva/q_proj/kernel"] == ("embed", "heads")
    assert dims["layer_0/eva/o_proj/kernel"] == ("heads", "embed")
    assert dims["layer_0/mlp/down_proj/kernel"] == ("mlp", "embed")
    assert dims["lm_head/kernel"] == ("embed", "vocab")
    # a norm's w starts at 0: the gain is 1 + w
    real = evabyte.init_params(jax.random.PRNGKey(0), PLAIN)
    assert not np.asarray(real["norm_f"]["scale"]).any()
    assert float(jnp.max(jnp.abs(real["layer_0"]["eva"]["phi"]))) \
        <= PLAIN.head_dim ** -0.5


def test_the_float32_stream_and_the_unit_offset_are_the_configurations():
    """`layers.trunk` embeds in the stream's type and `rms_norm` adds the 1
    only where the configuration says so."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16), jnp.bfloat16)
    p = {"scale": jnp.full((16,), 0.5)}
    plain = layers.rms_norm(x, p, 1e-5)
    offset = layers.rms_norm(x, p, 1e-5, unit_offset=True)
    np.testing.assert_allclose(np.asarray(offset, np.float32),
                               3 * np.asarray(plain, np.float32), rtol=2e-2)
    cfg = dataclasses.replace(evabyte.EVABYTE_TINY, remat=False)
    params, tokens = seeded(cfg, 64)
    _, streams = evabyte.hidden(layers.cast_weights(params, jnp.bfloat16),
                                tokens[:, :-1], cfg, streams=True)
    assert {s.dtype for s in streams} == {jnp.dtype(jnp.float32)}

"""What a recomputed layer keeps (`models/layers.py:checkpoint_layer`): the
flash kernels' forward rules name their output and row statistics
(`ops/flash_attention.py:KEPT_RESIDUALS`), the one policy keeps those names,
and the backward pass of a `remat` model recomputes a layer's forward but
for its attention kernel.  Small sizes on the CPU, kernels interpreted; the
forward kernel's calls are counted in the lowered text, where each is a call
of the jitted `_pallas_forward` / `_pallas_forward_bshd`.
"""

import contextlib
import dataclasses
import io
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name, print_saved_residuals

from ray_tpu.models import deepseek_v3, gpt2, lfm2_moe, olmoe
from ray_tpu.models.layers import checkpoint_layer
from ray_tpu.ops.flash_attention import KEPT_RESIDUALS, flash_attention
from ray_tpu.parallel import pipeline
from ray_tpu.parallel.attention import attention
from ray_tpu.parallel.context import use_mesh
from ray_tpu.parallel.mesh import create_mesh
from ray_tpu.parallel.sharding import ShardingConfig, shard_params
from ray_tpu.util import tracing

F32 = dict(compute_dtype=jnp.float32)
# (the model's module, its configuration, layers that call attention)
MODELS = {
    "gpt2": (gpt2, dataclasses.replace(gpt2.GPT2_TINY, **F32), 2),
    "deepseek_v3": (deepseek_v3, dataclasses.replace(
        deepseek_v3.DEEPSEEK_V3_TINY, **F32), 3),
    "lfm2_moe": (lfm2_moe, dataclasses.replace(
        lfm2_moe.LFM2_MOE_TINY, **F32), 1),
}
BATCH, SEQ = 2, 128
COUNTER = "remat.residuals_kept"


def forward_calls(lowered_text):
    """Calls of the jitted forward kernel in a lowered module (a second
    instance of the same function is `_pallas_forward_<n>`)."""
    return len(re.findall(r"call @_pallas_forward(?:_bshd)?(?:_\d+)?\(",
                          lowered_text))


def scalar_loss(module, cfg, tokens):
    def loss(params):
        out = module.loss_fn(params, {"tokens": tokens}, cfg)
        return out[0] if isinstance(out, tuple) else out
    return loss


def grad_of(module, cfg, remat):
    """(lowered text of the jitted value-and-gradient, its value on seeded
    weights and tokens, what it added to `remat.residuals_kept`)."""
    cfg = dataclasses.replace(cfg, remat=remat)
    params = module.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ + 1), 0,
                                cfg.vocab_size)
    fn = jax.jit(jax.value_and_grad(scalar_loss(module, cfg, tokens)))
    with tracing.timeline_span("train.fit", root=True):
        before = tracing.counter(COUNTER)
        text = fn.lower(params).as_text()
        kept = tracing.counter(COUNTER) - before
    return text, fn(params), kept


@pytest.mark.parametrize("name", list(MODELS))
def test_remat_runs_the_forward_kernel_once_a_layer(name, monkeypatch):
    module, cfg, attention_layers = MODELS[name]
    text, (loss, grads), kept = grad_of(module, cfg, remat=True)
    assert forward_calls(text) == attention_layers
    # both residuals, asked once per traced layer (layers of one shape
    # share a trace), and nothing else
    assert kept % 2 == 0 and 0 < kept <= 2 * attention_layers

    # the parent's layer, a bare `jax.checkpoint`: every kernel twice, the
    # same loss and gradients to the last bit
    monkeypatch.setattr(module, "checkpoint_layer", jax.checkpoint)
    bare_text, (bare_loss, bare_grads), bare_kept = grad_of(
        module, cfg, remat=True)
    assert forward_calls(bare_text) == 2 * attention_layers
    assert bare_kept == 0
    assert float(loss) == float(bare_loss)
    for (path, want), got in zip(
            jax.tree_util.tree_flatten_with_path(bare_grads)[0],
            jax.tree.leaves(grads)):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("name", list(MODELS))
def test_without_remat_nothing_changes(name):
    """`remat` off: a name is nothing.  One forward kernel a layer as
    before, no policy asked, and the gradients of the `remat` step."""
    module, cfg, attention_layers = MODELS[name]
    text, (loss, grads), kept = grad_of(module, cfg, remat=False)
    assert forward_calls(text) == attention_layers
    assert kept == 0
    _, (remat_loss, remat_grads), _ = grad_of(module, cfg, remat=True)
    np.testing.assert_allclose(float(loss), float(remat_loss), rtol=1e-6)
    for want, got in zip(jax.tree.leaves(grads),
                         jax.tree.leaves(remat_grads)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=0)


def test_fsdp4_keeps_the_residuals_through_the_shard_map():
    """XL's route: `_flash_sharded`'s `shard_map` of the kernel on a
    four-device mesh.  The names are inside it and the policy outside."""
    scfg = ShardingConfig(fsdp=4)
    mesh = scfg.build_mesh(jax.devices()[:4])
    cfg = dataclasses.replace(MODELS["gpt2"][1], remat=True)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, SEQ + 1), 0,
                                cfg.vocab_size)
    want = jax.jit(jax.grad(scalar_loss(gpt2, cfg, tokens)))(params)
    with use_mesh(mesh):
        placed = jax.device_put(
            tokens, scfg.named_sharding(mesh, "batch", None))
        fn = jax.jit(jax.grad(scalar_loss(gpt2, cfg, placed)))
        sharded = shard_params(params, scfg, mesh)
        text = fn.lower(sharded).as_text()
        got = fn(sharded)
    assert "manual_computation" in text or "shmap_body" in text
    assert forward_calls(text) == cfg.n_layer
    for want_leaf, got_leaf in zip(jax.tree.leaves(want),
                                   jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(got_leaf),
                                   np.asarray(want_leaf), atol=1e-5, rtol=0)


def _qkv(shape):
    return tuple(jax.random.normal(jax.random.PRNGKey(i), shape)
                 for i in range(3))


def _residual_shapes(fn, *args):
    """Shapes of what `fn`'s checkpoint keeps besides its arguments, as
    jax prints them ("f32[1,2,256] named 'flash_attention.lse' from ...")."""
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        print_saved_residuals(fn, *args)
    return sorted(
        tuple(int(n) for n in line.split("[")[1].split("]")[0].split(","))
        for line in printed.getvalue().splitlines()
        if "from the argument" not in line)


def test_the_ring_keeps_no_chunk_partial():
    """`ring_attention` calls the kernels' shared body once per rotating
    chunk; those partials carry no name, so a checkpointed layer around
    the ring keeps what the parent's did: its arguments.  The public
    `flash_attention` beside it keeps o and lse."""
    B, H, S, D = 1, 2, 256, 16
    mesh = create_mesh({"sp": 4}, jax.devices()[:4])
    q, k, v = _qkv((B, S, H, D))

    def ring_layer(q, k, v):
        with use_mesh(mesh):
            return jnp.sum(attention(q, k, v, causal=True, variant="ring"))

    assert _residual_shapes(checkpoint_layer(ring_layer), q, k, v) \
        == _residual_shapes(jax.checkpoint(ring_layer), q, k, v) == []
    with tracing.timeline_span("train.fit", root=True):
        before = tracing.counter(COUNTER)
        kept = jax.jit(jax.grad(checkpoint_layer(ring_layer), (0, 1, 2)))
        bare = jax.jit(jax.grad(jax.checkpoint(ring_layer), (0, 1, 2)))
        text, bare_text = (f.lower(q, k, v).as_text() for f in (kept, bare))
        assert tracing.counter(COUNTER) == before
    assert forward_calls(text) == forward_calls(bare_text) > 0
    for got, want in zip(kept(q, k, v), bare(q, k, v)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def flash_layer(q, k, v):
        tr = lambda x: x.transpose(0, 2, 1, 3)
        return jnp.sum(flash_attention(tr(q), tr(k), tr(v), True))

    assert _residual_shapes(jax.checkpoint(flash_layer), q, k, v) == []
    # a short sequence: lse as the head-major kernels hold it, a row each
    assert _residual_shapes(checkpoint_layer(flash_layer), q, k, v) \
        == [(B, H, S, D), (B * H, S, 1)]


def test_a_long_sequence_keeps_its_statistics_packed():
    """Past `_WHOLE_SEQ_MAX` the kept lse is (B, H, S): in the kernels'
    (B*H, S, 1) its rows fill a lane each, 128 times the bytes (kanana:
    268 MB a layer).  Shapes only: nothing this long runs on the CPU."""
    B, S, H, D = 1, 2048, 3, 64         # three heads: the head-major route
    q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16)

    def layer(q, k, v):
        return jnp.sum(attention(q, k, v).astype(jnp.float32))

    assert _residual_shapes(checkpoint_layer(layer), q, q, q) \
        == [(B, H, S), (B, S, H, D)]


def test_a_checkpointed_pipeline_lowers_and_agrees():
    """`pipeline_apply(remat=True)`: the scan's body goes through
    `checkpoint_layer`, lowers, and gives `remat=False`'s result and
    gradients.  (No flash kernel runs inside the pipeline's `shard_map`,
    which checks varying axes; the block names a value as the kernels
    would, and the policy keeps it through the scan.)"""
    mesh = create_mesh({"dp": 2, "pp": 2}, jax.devices()[:4])
    E = 32
    stacked = pipeline.stack_layer_params([
        {"w": jax.random.normal(jax.random.PRNGKey(i), (E, E)) / E ** 0.5}
        for i in range(4)])
    x = jax.random.normal(jax.random.PRNGKey(9), (4, 16, E))

    def block(p, h):
        u = checkpoint_name(jnp.tanh(h @ p["w"]), KEPT_RESIDUALS[0])
        return h + u @ p["w"].T, jnp.sum(p["w"][0])

    def loss(stacked, remat):
        out, aux = pipeline.pipeline_apply(
            block, stacked, x, mesh, num_microbatches=2, remat=remat)
        return jnp.sum(out ** 2) + aux

    def kept_and_value(remat):
        fn = jax.jit(jax.value_and_grad(lambda s: loss(s, remat)))
        with tracing.timeline_span("train.fit", root=True):
            before = tracing.counter(COUNTER)
            fn.lower(stacked)
            return tracing.counter(COUNTER) - before, fn(stacked)

    kept, (got, got_grads) = kept_and_value(True)
    none, (want, want_grads) = kept_and_value(False)
    assert kept > 0 and none == 0
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got_grads["w"]),
                               np.asarray(want_grads["w"]), rtol=1e-5, atol=1e-4)


def test_one_function_owns_the_policy():
    """Every per-layer `jax.checkpoint` of `models/` and of the pipeline
    goes through `checkpoint_layer`; the chunked loss's own is no layer."""
    import inspect

    for module in (gpt2, deepseek_v3, lfm2_moe, olmoe, pipeline):
        source = inspect.getsource(module)
        assert "checkpoint_layer(" in source, module.__name__
        assert not re.search(r"jax\.(checkpoint|remat)\(", source), \
            module.__name__

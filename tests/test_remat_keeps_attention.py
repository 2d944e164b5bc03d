"""What a recomputed layer keeps (`models/layers.py:checkpoint_layer`): the
flash kernels' forward rules name their output and row statistics
(`ops/flash_attention.py:KEPT_RESIDUALS`), the one policy keeps those names,
and the backward pass of a `remat` model recomputes a layer's forward but
for its attention kernel.  Small sizes on the CPU, kernels interpreted; the
forward kernel's calls are counted in the lowered text, where each is a call
of the jitted `_pallas_forward` / `_pallas_forward_bshd`.

Since PR 37 also the projections it has room for: the models mark matmul
results with `layers.KEPT_NAMES`, and a stack keeps them, in that order,
whole stacks only, up to a budget (`layers.keep_plan`), given here directly.
"""

import collections
import contextlib
import dataclasses
import inspect
import io
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name, print_saved_residuals

from ray_tpu.models import (
    bailing_hybrid,
    deepseek_v3,
    evabyte,
    gpt2,
    keye_vl,
    laguna,
    layers,
    lfm2_moe,
    nemotron_h,
    olmoe,
)
from ray_tpu.models.layers import KEPT_NAMES, checkpoint_layer, named
from ray_tpu.ops.flash_attention import KEPT_RESIDUALS, flash_attention
from ray_tpu.parallel import pipeline
from ray_tpu.parallel.attention import attention
from ray_tpu.parallel.context import use_mesh
from ray_tpu.parallel.mesh import create_mesh
from ray_tpu.parallel.sharding import (
    ShardingConfig,
    chip_bytes,
    shard_params,
)
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
# the fourth `remat` model: OLMoE's cell runs with it off
FOUR = dict(MODELS, olmoe=(olmoe, dataclasses.replace(
    olmoe.OLMOE_TINY, max_seq=128, **F32), 2))
BATCH, SEQ = 2, 128
COUNTER = "remat.residuals_kept"
ROOMY = 1 << 40


def walker(module):
    """The module whose walk calls `checkpoint_layer`: GPT-2's own, and
    `layers.trunk` for every other model."""
    return gpt2 if module is gpt2 else layers


def forward_calls(lowered_text):
    """Calls of the jitted forward kernel in a lowered module (a second
    instance of the same function is `_pallas_forward_<n>`)."""
    return len(re.findall(r"call @_pallas_forward(?:_bshd)?(?:_\d+)?\(",
                          lowered_text))


def bare_checkpoint(fn, stack=None, behind=(), **kw):
    """PR 34's layer: `jax.checkpoint` and no policy."""
    return jax.checkpoint(fn, **kw)


def scalar_loss(module, cfg, tokens):
    def loss(params):
        out = module.loss_fn(params, {"tokens": tokens}, cfg)
        return out[0] if isinstance(out, tuple) else out
    return loss


def seeded(module, cfg, remat):
    """(the scalar loss as a function of the parameters, seeded ones)."""
    cfg = dataclasses.replace(cfg, remat=remat)
    params = module.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ + 1), 0,
                                cfg.vocab_size)
    return scalar_loss(module, cfg, tokens), params


def grad_of(module, cfg, remat):
    """(lowered text of the jitted value-and-gradient, its value on seeded
    weights and tokens, what it added to `remat.residuals_kept`)."""
    loss, params = seeded(module, cfg, remat)
    fn = jax.jit(jax.value_and_grad(loss))
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
    monkeypatch.setattr(walker(module), "checkpoint_layer",
                        bare_checkpoint)
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
    goes through `checkpoint_layer`: GPT-2's walk, the pipeline's and
    `layers.trunk`, which walks every other model; the chunked loss's own
    is no layer."""
    import inspect

    for walk in (gpt2, pipeline, layers.trunk):
        assert "checkpoint_layer(" in inspect.getsource(walk), walk.__name__
    for module in (gpt2, deepseek_v3, keye_vl, lfm2_moe, nemotron_h, olmoe,
                   pipeline):
        source = inspect.getsource(module)
        assert module in (gpt2, pipeline) or "trunk(" in source
        assert not re.search(r"jax\.(checkpoint|remat)\(", source), \
            module.__name__


# -- PR 37: the projections a stack has room for ----------------------------

KEEP_PLAN = layers.keep_plan


def with_room(monkeypatch, room):
    """The budget given directly: every stack's `keep_plan` gets ``room``
    bytes.  -> the list the plans are appended to."""
    plans = []

    def given(*args, **kw):
        plans.append(KEEP_PLAN(*args, **kw, room=room))
        return plans[-1]

    monkeypatch.setattr(layers, "keep_plan", given)
    return plans


MATMULS = ("dot_general", "ragged_dot", "ragged_dot_general")


def equations(jaxpr):
    """Every equation of a jaxpr, those of every jaxpr among an equation's
    parameters (a recomputed layer, a rule, a branch) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations(sub)


def primitives(jaxpr):
    return collections.Counter(e.primitive.name for e in equations(jaxpr))


def backward_jaxpr(module, cfg, remat):
    loss, params = seeded(module, cfg, remat)
    return jax.make_jaxpr(jax.grad(loss))(params).jaxpr


def backward_primitives(module, cfg, remat):
    return primitives(backward_jaxpr(module, cfg, remat))


def matmuls(found):
    return sum(found[name] for name in MATMULS)


def replayed_matmuls(module, cfg):
    """Counter of the scopes under which the gradient of the `remat` model
    multiplies AGAIN: the matmuls of a replay (jax puts them under
    `rematted_computation`), by what is left of their name stack."""
    return collections.Counter(
        str(e.source_info.name_stack).replace("rematted_computation", "")
        .strip("/")
        for e in equations(backward_jaxpr(module, cfg, remat=True))
        if e.primitive.name in MATMULS
        and "rematted_computation" in str(e.source_info.name_stack))


# What no name reaches: the chunked loss's own checkpoint (no scope), and
# the experts' last product on the path over all the rows, whose result the
# combine's gradient towards the weights reads (the cells' share of the
# experts runs inside a rule that keeps nothing but its arguments).
UNNAMED = {"", "ffn/moe/experts"}


@pytest.mark.parametrize("name", list(FOUR))
def test_with_room_the_replay_multiplies_nothing_again(name, monkeypatch):
    """Room for every name: no replay holds a second matmul of a kept
    projection; the bare checkpoint makes every projection the backward
    reads a second time."""
    module, cfg, _ = FOUR[name]
    plans = with_room(monkeypatch, ROOMY)
    kept = replayed_matmuls(module, cfg)
    assert plans and all(plan["declined"] == () for plan in plans)
    assert all(plan["names"] == tuple(n for n in KEPT_NAMES
                                      if n in plan["marked"])
               for plan in plans)
    assert set(kept) <= UNNAMED, kept
    monkeypatch.setattr(walker(module), "checkpoint_layer",
                        bare_checkpoint)
    bare = replayed_matmuls(module, cfg)
    assert set(bare) - UNNAMED
    assert all(bare[scope] >= kept[scope] for scope in UNNAMED)
    if name == "gpt2":
        assert set(bare) - UNNAMED == {"attention/qkv", "attention/out",
                                       "ffn/dense"}


@pytest.mark.parametrize("name", list(FOUR))
def test_with_no_room_the_layer_is_the_parents(name, monkeypatch):
    """A budget of zero: the gradient's jaxpr is PR 35's, primitive for
    primitive (its policy kept `KEPT_RESIDUALS` and nothing else)."""
    module, cfg, _ = FOUR[name]
    plans = with_room(monkeypatch, 0)
    none = backward_primitives(module, cfg, remat=True)
    assert plans and all(plan["names"] == () and plan["bytes_kept"] == 0
                         for plan in plans)
    assert all(len(plan["declined"]) == len(plan["marked"])
               for plan in plans)

    def pr35(fn, stack=None, behind=(), **kw):
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                *KEPT_RESIDUALS), **kw)

    monkeypatch.setattr(walker(module), "checkpoint_layer", pr35)
    assert backward_primitives(module, cfg, remat=True) == none


@pytest.mark.parametrize("name", list(FOUR))
def test_with_some_room_the_order_decides(name, monkeypatch):
    """Room for some: the names are tried in `KEPT_NAMES`' order, one that
    does not fit whole (every layer counted) is skipped and the next is
    tried, and the policy keeps exactly the values of the names chosen."""
    module, cfg, _ = FOUR[name]
    whole = with_room(monkeypatch, ROOMY)
    backward_primitives(module, cfg, remat=True)
    marked = whole[0]["marked"]
    ordered = [n for n in KEPT_NAMES if n in marked]
    assert len(ordered) >= 3
    # room for the first, not for the second beside it, and for the third
    room = marked[ordered[0]] + marked[ordered[2]]
    if marked[ordered[1]] <= marked[ordered[2]]:
        room = marked[ordered[0]] + marked[ordered[1]] - 1
    plans = with_room(monkeypatch, room)
    with tracing.timeline_span("train.fit", root=True):
        some = backward_primitives(module, cfg, remat=True)
        bytes_kept = tracing.counter("remat.bytes_kept")
        declined = tracing.counter("remat.names_declined")
    plan = plans[0]
    want, total = [], 0
    for n in ordered:
        if total + marked[n] <= room:
            want.append(n)
            total += marked[n]
    assert plan["names"] == tuple(want) and ordered[0] in want
    assert ordered[1] not in want
    assert plan["bytes_kept"] == total == bytes_kept <= room
    assert declined == len(plan["declined"]) == len(ordered) - len(want)
    # between `remat` off (nothing made again) and the parent's replay
    off = matmuls(backward_primitives(module, cfg, remat=False))
    with_room(monkeypatch, 0)
    none = matmuls(backward_primitives(module, cfg, remat=True))
    assert off <= matmuls(some) <= none and off < none


@pytest.mark.parametrize("name", list(FOUR))
def test_kept_projections_leave_float32_as_it_was(name, monkeypatch):
    """In float32 the loss and every gradient of a stack that keeps all it
    marks equal those of `remat` off to the last bit: a kept value is the
    value the replay would have made."""
    module, cfg, _ = FOUR[name]
    with_room(monkeypatch, ROOMY)
    loss, params = seeded(module, cfg, remat=True)
    got, got_grads = jax.jit(jax.value_and_grad(loss))(params)
    loss, params = seeded(module, cfg, remat=False)
    want, want_grads = jax.jit(jax.value_and_grad(loss))(params)
    assert float(got) == float(want)
    for (path, w), g in zip(
            jax.tree_util.tree_flatten_with_path(want_grads)[0],
            jax.tree.leaves(got_grads)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("name", list(FOUR))
def test_a_device_that_states_no_limit_keeps_what_the_parent_kept(name):
    """The CPU states no `bytes_limit`: through `train_step` (which tells
    the state's bytes) as without it, nothing beyond `KEPT_RESIDUALS` is
    kept; nor with a limit assumed where nobody told the state."""
    module, cfg, attention_layers = FOUR[name]
    assert layers._memory_limit() is None
    cfg = dataclasses.replace(cfg, remat=True)
    params = module.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ + 1), 0,
                                cfg.vocab_size)
    import optax
    step = module.make_train_step(cfg, optax.adamw(1e-4))
    plans = []
    with layers.assume_memory_limit(None, plans), \
            tracing.timeline_span("train.fit", root=True):
        jax.jit(step).lower(params, optax.adamw(1e-4).init(params),
                            {"tokens": tokens})
        assert tracing.counter("remat.bytes_kept") == 0
        assert tracing.counter("remat.names_declined") \
            == len(plans[0]["marked"]) > 0
        assert 0 < tracing.counter(COUNTER) <= 2 * attention_layers
    assert plans[0]["names"] == () and plans[0]["room"] == 0
    assert plans[0]["state"] > 16 * layers.num_params(params)
    with layers.assume_memory_limit(1 << 40, plans):
        jax.jit(jax.grad(scalar_loss(module, cfg, tokens))).lower(params)
    assert plans[1]["names"] == () and plans[1]["state"] is None
    # told both, it keeps (a step of its own: jit has the first's trace)
    step = module.make_train_step(cfg, optax.adamw(1e-4))
    with layers.assume_memory_limit(1 << 40, plans):
        jax.jit(step).lower(params, optax.adamw(1e-4).init(params),
                            {"tokens": tokens})
    assert plans[2]["names"] == tuple(
        n for n in KEPT_NAMES if n in plans[2]["marked"]) != ()


def _lowered(loss, params):
    """The lowered gradient's text, the counters jax numbers its private
    functions with (`@silu_212`) dropped."""
    return re.sub(r"(@[A-Za-z_]+)_\d+", r"\1",
                  jax.jit(jax.grad(loss)).lower(params).as_text())


@pytest.mark.parametrize("name", list(FOUR))
def test_without_remat_a_mark_is_nothing(name, monkeypatch):
    """`remat` off: the lowered step is the one of a model that marks
    nothing, instruction for instruction, and no plan is made."""
    module, cfg, _ = FOUR[name]
    plans = with_room(monkeypatch, ROOMY)
    marked = _lowered(*seeded(module, cfg, remat=False))
    assert plans == []
    bare = lambda x, name: x
    monkeypatch.setattr(layers, "named", bare)
    # a model file that marks nothing itself imports no `named`
    monkeypatch.setattr(module, "named", bare, raising=False)
    from ray_tpu.ops import moe
    monkeypatch.setattr(moe, "checkpoint_name", bare)
    jax.clear_caches()
    assert _lowered(*seeded(module, cfg, remat=False)) == marked


def test_the_names_live_in_one_tuple(monkeypatch):
    """`layers.KEPT_NAMES` owns the vocabulary: no model file calls
    `checkpoint_name` (`layers.named` does, once, and refuses a word that
    is not the tuple's), and every name of the tuple is marked by some
    model's layer."""
    for module in (gpt2, bailing_hybrid, deepseek_v3, evabyte, keye_vl,
                   laguna, lfm2_moe, nemotron_h, olmoe, pipeline):
        assert "checkpoint_name(" not in inspect.getsource(module), \
            module.__name__
    assert inspect.getsource(layers).count("checkpoint_name(") == 1
    plans = with_room(monkeypatch, ROOMY)
    # the four, the model whose layers mark the state-space names, the one
    # whose layers mark an indexer's, the one whose attention has a gate, the
    # one whose layers mark a delta-rule mixer's and the one whose layers
    # mark an EVA mixer's summaries (at a shape its kernels take)
    for module, cfg in [v[:2] for v in FOUR.values()] + [
            (nemotron_h, dataclasses.replace(nemotron_h.NEMOTRON_H_TINY,
                                             **F32)),
            (keye_vl, dataclasses.replace(keye_vl.KEYE_VL_TINY, **F32)),
            (laguna, dataclasses.replace(laguna.LAGUNA_TINY, **F32)),
            (bailing_hybrid, dataclasses.replace(
                bailing_hybrid.BAILING_HYBRID_TINY, **F32)),
            (evabyte, dataclasses.replace(
                evabyte.EVABYTE_TINY, head_dim=128, window=128, chunk=8,
                **F32))]:
        backward_jaxpr(module, cfg, remat=True)
    assert {n for plan in plans for n in plan["marked"]} == set(KEPT_NAMES)
    assert not set(KEPT_NAMES) & set(KEPT_RESIDUALS)
    with pytest.raises(ValueError):
        named(jnp.zeros(()), "attention/anything")


# -- the byte accounting alone ----------------------------------------------

def test_bytes_on_one_chip_of_a_cut_batch():
    """Under `ShardingConfig(fsdp=4)` the batch is cut four ways and the
    width is whole; with no mesh, or dims nobody states, one chip holds all
    of it; a batch the axes do not divide is whole."""
    mesh = ShardingConfig(fsdp=4).build_mesh(jax.devices()[:4])
    shape = (16, 1024, 4800)
    assert chip_bytes(shape, jnp.bfloat16, "batch", mesh=mesh) \
        == 4 * 1024 * 4800 * 2
    assert chip_bytes(shape, jnp.bfloat16, mesh=mesh) \
        == chip_bytes(shape, jnp.bfloat16, "batch") == 16 * 1024 * 4800 * 2
    assert chip_bytes((6, 8), jnp.float32, "batch", mesh=mesh) == 6 * 8 * 4
    with use_mesh(mesh):
        assert chip_bytes(shape, jnp.bfloat16, "batch") == 4 * 1024 * 4800 * 2
        # a parameter by its name's dims: "embed" on fsdp
        assert chip_bytes((1600, 4800), jnp.float32, "embed", "heads") \
            == 400 * 4800 * 4


def test_unpacked_row_statistics_count_128_lanes():
    """The head-major kernels' (rows, 1) float32 statistics fill one lane
    of 128 each (XL: 52 MB a layer a chip for 0.4 of data); packed as
    (B, H, S) they are their data.  bfloat16 rows come in sublanes of 16."""
    mesh = ShardingConfig(fsdp=4).build_mesh(jax.devices()[:4])
    rows = chip_bytes((16 * 25, 1024, 1), jnp.float32, "batch", mesh=mesh,
                      tiled=True)
    assert rows == 100 * 1024 * 128 * 4 == 52_428_800
    assert chip_bytes((16, 25, 1024), jnp.float32, "batch", mesh=mesh,
                      tiled=True) == 4 * 32 * 1024 * 4
    assert chip_bytes((16 * 25, 1024, 1), jnp.float32, "batch", mesh=mesh) \
        == 100 * 1024 * 4
    assert chip_bytes((2, 8, 64), jnp.bfloat16, tiled=True) == 2 * 16 * 128 * 2


def _toy_stack(n_layer=3, E=128):
    """A layer that marks three names of 1, 3 and 2 units of (4, 16, E)
    float32, and a kernel-shaped residual; ``n_layer`` calls of it."""
    unit = 4 * 16 * E * 4

    def layer(x, w):
        a = named(jnp.tanh(x @ w), "attention/out")
        b = named(jnp.concatenate([a * a, a + 1, a - 1], -1), "attention/qkv")
        c = named(b[..., :2 * E] * 2, "ffn/hidden")
        lse = checkpoint_name(jnp.sum(c, -1).reshape(-1, 16, 1),
                              KEPT_RESIDUALS[1])
        return x + a + b[..., :E] + c[..., :E] + lse.reshape(4, 16, 1), None

    x = jnp.ones((4, 16, E))
    w = jnp.ones((E, E))
    return layer, [(x, w)] * n_layer, unit


def test_a_name_that_does_not_fit_is_skipped_and_the_next_tried():
    layer, calls, unit = _toy_stack()
    plan = layers.keep_plan(layer, calls, room=ROOMY)
    assert plan["marked"] == {"attention/out": 3 * unit,
                              "attention/qkv": 9 * unit,
                              "ffn/hidden": 6 * unit}
    # the stream of every layer and the residual at 128 lanes a row
    assert plan["already"] == 3 * unit + 3 * (4 * 16 * 128 * 4)
    assert plan["names"] == ("attention/out", "attention/qkv", "ffn/hidden")
    plan = layers.keep_plan(layer, calls, room=9 * unit)
    assert plan["names"] == ("attention/out", "ffn/hidden")
    assert plan["declined"] == ("attention/qkv",)
    assert plan["bytes_kept"] == 9 * unit
    # never a part of a stack: one byte short of the third keeps the first
    plan = layers.keep_plan(layer, calls, room=9 * unit - 1)
    assert plan["names"] == ("attention/out",)
    assert layers.keep_plan(layer, calls, room=0)["names"] == ()
    # no room given: the CPU states no limit and nobody told a state
    assert layers.keep_plan(layer, calls)["names"] == ()


def test_a_value_inside_a_shard_map_is_one_chips_already():
    """XL's kernels run inside a `shard_map`, and the values named there
    have one chip's shape: they are not cut by the mesh a second time.  A
    value named outside is."""
    from ray_tpu.parallel.sharding import dividing_spec
    mesh = ShardingConfig(fsdp=4).build_mesh(jax.devices()[:4])
    x, w = jnp.ones((8, 16, 128)), jnp.ones((128, 128))
    spec = dividing_spec(mesh, ("batch",), x.shape)
    on_a_chip = 2 * 16 * 128 * 4

    def layer(x, w):
        y = jax.shard_map(
            lambda x: checkpoint_name(jnp.tanh(x), KEPT_RESIDUALS[0]),
            mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False)(x)
        return x + named(y @ w, "attention/out"), None

    with use_mesh(mesh):
        plan = layers.keep_plan(layer, [(x, w)] * 2, room=ROOMY)
    assert plan["already"] == 2 * (on_a_chip + on_a_chip)
    assert plan["marked"] == {"attention/out": 2 * on_a_chip}


def test_the_room_is_the_limit_less_state_kept_and_reserve():
    layer, calls, unit = _toy_stack()
    behind = jax.ShapeDtypeStruct((4, 16, 4000), jnp.float32)
    with layers.assume_memory_limit(1000 * unit), \
            layers._telling(state_bytes=100 * unit):
        plan = layers.keep_plan(layer, calls)
        heavy = layers.keep_plan(layer, calls, behind=behind)
    heaviest = unit * (1 + 1 + 3 + 2) + 4 * 16 * 128 * 4
    assert plan["reserve"] == int(layers._LIVE_LAYERS * heaviest)
    assert plan["room"] == int(1000 * unit * (1 - layers._HEADROOM)) \
        - 100 * unit - plan["already"] - plan["reserve"]
    assert heavy["reserve"] == int(layers._LIVE_BEHIND * 4 * 16 * 4000 * 4) \
        > plan["reserve"]
    assert plan["names"] == ("attention/out", "attention/qkv", "ffn/hidden")


def test_the_plan_counts_itself_on_the_timeline(monkeypatch):
    layer, calls, unit = _toy_stack()
    with_room(monkeypatch, 9 * unit)
    x, w = calls[0]

    def loss(w):
        h = x
        stacked = checkpoint_layer(layer, stack=[(h, w)] * 3)
        for _ in range(3):
            h, _ = stacked(h, w)
        return jnp.sum(h)

    with tracing.timeline_span("train.fit", root=True):
        jax.jit(jax.grad(loss)).lower(w)
        assert tracing.counter("remat.bytes_kept") == 9 * unit
        assert tracing.counter("remat.names_declined") == 1
        # out and hidden and the residual of the one trace three layers
        # share, each time the policy is asked
        assert tracing.counter(COUNTER) in (3, 6)


def test_state_bytes_cut_as_the_parameters_are():
    """Parameters, gradients, the optimizer's moments and the matrices'
    bfloat16 copy, on one chip of four under fsdp."""
    import optax
    params = {"h_0": {"attn": {"c_attn": {
        "kernel": jnp.zeros((64, 192)), "bias": jnp.zeros((192,))}}}}
    opt_state = optax.adamw(1e-4).init(params)
    kernel, bias = 64 * 192 * 4, 192 * 4
    assert layers.state_bytes(params, opt_state, jnp.bfloat16) \
        == 4 * (kernel + bias) + kernel // 2 + 4   # + the step count
    mesh = ShardingConfig(fsdp=4).build_mesh(jax.devices()[:4])
    with use_mesh(mesh):
        cut = layers.state_bytes(params, opt_state, jnp.bfloat16)
    held = kernel // 4 + bias
    moments = 2 * (kernel + bias) + 4
    assert cut == 2 * held + kernel // 8 \
        + moments * held // (kernel + bias)


# -- the state-space scan's kernels (PR 39) ----------------------------------

def _scan_layer():
    """A layer whose only marked value is a scan's y, at a size the scan's
    kernels take (`ops/ssd.py`), and its one call's arguments."""
    from ray_tpu.ops import ssd

    H, P, N, S = 2, 64, 128, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    args = (jax.random.normal(ks[0], (BATCH, S, H, P)),
            jax.nn.softplus(jax.random.normal(ks[1], (BATCH, S, H))),
            -jnp.ones((H,)),
            jax.random.normal(ks[2], (BATCH, S, 1, N)),
            jax.random.normal(ks[3], (BATCH, S, 1, N)), jnp.ones((H,)))

    def layer(x, dt, A, Bm, Cm, D):
        # the conv's place: the scan's operands are made from the layer's
        y = named(ssd.ssd_scan(jnp.tanh(x), dt, A, Bm, Cm, D, 8), "ssm/scan")
        return jnp.sum(jnp.tanh(y) * y)         # its backward reads y

    return layer, args


def _scan_kernels(jaxpr, replayed):
    """The compiled-form `pallas_call`s of a jaxpr by kind (the y kernel's
    one 3-D result, the state pass's 5-D, the backward's six), those of a
    replay (``replayed``: under `rematted_computation`, which a
    `platform_dependent`'s `cond` carries for its branches) or of the other
    passes."""
    kinds = {(1, 3): "forward", (1, 5): "states", (6, 3): "backward"}
    found = collections.Counter()

    def walk(jaxpr, inside):
        for e in jaxpr.eqns:
            here = inside or "rematted_computation" in str(
                e.source_info.name_stack)
            if e.primitive.name == "pallas_call":
                if not e.params["interpret"] and here == replayed:
                    found[kinds[len(e.outvars), e.outvars[0].aval.ndim]] += 1
                continue
            for value in e.params.values():
                for sub in value if isinstance(value, (tuple, list)) \
                        else (value,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, here)

    walk(jaxpr, False)
    return found


@pytest.mark.parametrize("room,replay", [(ROOMY, {}), (0, {"forward": 1})])
def test_a_kept_scan_leaves_no_kernel_in_the_replay(room, replay,
                                                    monkeypatch):
    """With `ssm/scan` kept the backward pass of a recomputed layer holds
    the scan's backward kernels (the state pass and the walk back) and no
    forward kernel: its residuals are the scan's inputs alone.  Declined,
    the replay runs the forward kernel and nothing else of the scan."""
    plans = with_room(monkeypatch, room)
    layer, args = _scan_layer()
    grad = jax.grad(lambda *a: checkpoint_layer(layer, stack=[a])(*a),
                    argnums=tuple(range(6)))
    jaxpr = jax.make_jaxpr(grad)(*args).jaxpr
    assert plans[0]["names"] == (("ssm/scan",) if room else ())
    assert _scan_kernels(jaxpr, replayed=True) == replay
    assert _scan_kernels(jaxpr, replayed=False) == {
        "forward": 1, "states": 1, "backward": 1}
    # and the gradients are the bare checkpoint's
    want = jax.grad(lambda *a: jax.checkpoint(layer)(*a),
                    argnums=tuple(range(6)))(*args)
    for got_leaf, want_leaf in zip(grad(*args), want):
        np.testing.assert_allclose(np.asarray(got_leaf),
                                   np.asarray(want_leaf), atol=1e-5, rtol=0)

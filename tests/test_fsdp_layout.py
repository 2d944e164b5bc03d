"""GPT-2's activations state their logical dims and `ShardingConfig`'s rules
pin them (`parallel/sharding.py:constrain`): the sharded step is the
one-device step, `fsdp` gathers weights and moves no activation, and with
no mesh nothing is added.  Four of the virtual CPU devices."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import gpt2
from ray_tpu.parallel import sharding
from ray_tpu.parallel.attention import attention
from ray_tpu.parallel.context import use_mesh
from ray_tpu.parallel.sharding import ShardingConfig, constrain, shard_params
from tools.aot_collectives import collectives

BATCH, SEQ = 8, 128
# block_size differs from SEQ and every width from every other, so that a
# collective's shape says what it carries
TINY = replace(gpt2.GPT2_TINY, block_size=256, compute_dtype=jnp.float32)

LAYOUTS = {
    "fsdp4": (dict(fsdp=4), "flash"),
    "tp2_fsdp2": (dict(tp=2, fsdp=2), "flash"),
    "sp2_fsdp2": (dict(sp=2, fsdp=2), "ring"),
    "pp2": (dict(pp=2, dp=2), "dense"),
}


def _tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ + 1), 0,
                              TINY.vocab_size)


def _three_steps(cfg, params, batch):
    """(losses of three AdamW steps, the first step's gradients)."""
    optimizer = optax.adamw(1e-3)
    step = jax.jit(gpt2.make_train_step(cfg, optimizer))
    grads = jax.jit(jax.grad(
        lambda p: gpt2.loss_fn(p, batch, cfg)))(params)
    state = optimizer.init(params)
    losses = []
    for _ in range(3):
        params, state, out = step(params, state, batch)
        losses.append(float(out["loss"]))
    return losses, grads


@pytest.mark.parametrize("remat", [False, True], ids=["keep", "remat"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_sharded_step_is_the_one_device_step(layout, remat):
    axes, attention = LAYOUTS[layout]
    params = gpt2.init_params(jax.random.PRNGKey(0), TINY)
    batch = {"tokens": _tokens()}
    with jax.default_matmul_precision("highest"):
        want_losses, want_grads = _three_steps(
            replace(TINY, attention="dense"), params, batch)
        if "pp" in axes:
            params = gpt2.to_pipeline_params(params, TINY)
            want_grads = gpt2.to_pipeline_params(want_grads, TINY)
        scfg = ShardingConfig(**axes)
        mesh = scfg.build_mesh(jax.devices()[:4])
        cfg = replace(TINY, attention=attention, remat=remat)
        with use_mesh(mesh):
            placed = {"tokens": jax.device_put(
                batch["tokens"], scfg.named_sharding(mesh, "batch", None))}
            got_losses, got_grads = _three_steps(
                cfg, shard_params(params, scfg, mesh), placed)
    np.testing.assert_allclose(got_losses, want_losses, atol=1e-5, rtol=0)
    for (path, want), got in zip(
            jax.tree_util.tree_flatten_with_path(want_grads)[0],
            jax.tree.leaves(got_grads)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-5, rtol=0,
            err_msg=jax.tree_util.keystr(path))


def _fsdp4_step_hlo():
    scfg = ShardingConfig(fsdp=4)
    mesh = scfg.build_mesh(jax.devices()[:4])
    cfg = replace(TINY, remat=True)
    params = shard_params(gpt2.init_params(jax.random.PRNGKey(0), cfg),
                          scfg, mesh)
    optimizer = optax.adamw(1e-3)
    batch = {"tokens": jax.device_put(
        _tokens(), scfg.named_sharding(mesh, "batch", None))}
    with use_mesh(mesh):
        step = jax.jit(gpt2.make_train_step(cfg, optimizer))
        return step.lower(params, optimizer.init(params),
                          batch).compile().as_text()


def _arrays(shape: str) -> list:
    """[(dtype, dims)] of a collective's result as the tool prints it."""
    return [(part.split("[")[0],
             [int(n) for n in part.split("[")[1].split("]")[0].split(",")
              if n])
            for part in shape.split(", ")]


def _activations_on_the_wire(found: dict) -> list:
    """The collectives whose shape holds the batch's (or a chip's share of
    the batch's) sequences, but for the embedding's: the token ids are
    gathered so that each chip looks up every row in its slice of the
    table's width, and what comes out cut along the width (a quarter of
    n_embd) is re-cut by batch once."""
    def rows(dims):
        return SEQ in dims and (BATCH in dims or BATCH // 4 in dims)

    def embedding(dtype, dims):
        return dtype == "s32" or dims[-1] == TINY.n_embd // 4

    return [(kind, shape) for kind, shape in found
            if any(rows(dims) and not embedding(dtype, dims)
                   for dtype, dims in _arrays(shape))]


def test_fsdp_gathers_weights_and_moves_no_activation():
    found = collectives(_fsdp4_step_hlo())
    assert _activations_on_the_wire(found) == []
    gathered = {tuple(_arrays(shape)[0][1]): count
                for (kind, shape), (count, _) in found.items()
                if kind == "all-gather"}
    # every block matrix is gathered where it is used: forward and, under
    # remat, again for the recomputation, in each of the two layers
    E = TINY.n_embd
    for matrix in [(E, 3 * E), (E, E), (E, 4 * E), (4 * E, E)]:
        assert gathered.get(matrix, 0) >= TINY.n_layer, (matrix, gathered)


def test_without_the_pins_the_partitioner_moves_activations(monkeypatch):
    """The check above has teeth: the same step with `constrain` taken out
    all-reduces partial products of the full batch (the parent's XL)."""
    monkeypatch.setattr(gpt2, "constrain", lambda x, *dims: x)
    assert _activations_on_the_wire(collectives(_fsdp4_step_hlo()))


def _step_jaxpr(mesh, batch_rows=BATCH):
    cfg = replace(TINY, remat=True)
    params = jax.eval_shape(lambda key: gpt2.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    optimizer = optax.adamw(1e-3)
    tokens = jax.ShapeDtypeStruct((batch_rows, SEQ + 1), jnp.int32)

    def trace():
        return str(jax.make_jaxpr(gpt2.make_train_step(cfg, optimizer))(
            params, jax.eval_shape(optimizer.init, params),
            {"tokens": tokens}))

    if mesh is None:
        return trace()
    with use_mesh(mesh):
        return trace()


@pytest.mark.parametrize("case", ["no_mesh", "one_device", "batch_of_6"])
def test_nothing_is_pinned_where_nothing_can_be(case):
    if case == "no_mesh":
        jaxpr = _step_jaxpr(None)
    elif case == "one_device":
        jaxpr = _step_jaxpr(ShardingConfig().build_mesh(jax.devices()[:1]))
    else:
        # six sequences do not divide over fsdp=4: the batch dim's pins go,
        # as `dividing_spec` drops the axis for the attention kernel
        mesh = ShardingConfig(fsdp=4).build_mesh(jax.devices()[:4])
        jaxpr = _step_jaxpr(mesh, batch_rows=6)
    assert "sharding_constraint" not in jaxpr


def test_under_fsdp4_the_step_states_its_pins():
    mesh = ShardingConfig(fsdp=4).build_mesh(jax.devices()[:4])
    assert "sharding_constraint" in _step_jaxpr(mesh)


@pytest.mark.parametrize("axes,dims,spec", [
    (dict(fsdp=4), ("batch", "seq", None), P("fsdp", None, None)),
    (dict(fsdp=4), ("batch", "seq", "mlp"), P("fsdp", None, None)),
    (dict(fsdp=4), ("batch", "seq", "vocab"), P("fsdp", None, None)),
    (dict(tp=2, fsdp=2), ("batch", "seq", "mlp"), P("fsdp", None, "tp")),
    (dict(tp=2, fsdp=2), ("batch", "seq", "heads"), P("fsdp", None, "tp")),
    (dict(sp=2, fsdp=2), ("batch", "seq", None), P("fsdp", "sp", None)),
    (dict(dp=2, fsdp=2), ("batch", "seq", None),
     P(("dp", "fsdp"), None, None)),
])
def test_constrain_resolves_logical_dims_by_the_default_rules(axes, dims,
                                                              spec):
    mesh = ShardingConfig(**axes).build_mesh(jax.devices()[:4])
    x = jnp.zeros((BATCH, SEQ, 64))
    with use_mesh(mesh):
        y = jax.jit(lambda x: constrain(x, *dims))(x)
    assert y.sharding.is_equivalent_to(NamedSharding(mesh, spec), x.ndim)
    assert sharding.logical_spec(mesh, dims) == spec


def _pin_and_shard_map(mesh, shape):
    """(the spec `constrain` pins q to, the in/out spec of the `shard_map`
    the flash kernel runs under) for q, k, v of `shape` (B, S, H, D)."""
    q = jax.ShapeDtypeStruct(shape, jnp.float32)
    with use_mesh(mesh):
        eqns = jax.make_jaxpr(lambda q: attention(
            *[constrain(q, "batch", None, "heads", None)] * 3))(q).eqns
    pins = [e.params["sharding"].spec for e in eqns
            if e.primitive.name == "sharding_constraint"]
    (wrapped,) = [e.params for e in eqns if e.primitive.name == "shard_map"]
    (spec,) = set(wrapped["in_specs"] + wrapped["out_specs"])
    return (pins[0] if pins else None), spec


# what is left of the rules' spec where 6 sequences and 3 heads do not
# divide by an axis
UNEVEN = {"fsdp4": P(None, None, None, None),
          "tp2_fsdp2": P("fsdp", None, None, None),
          "sp2_fsdp2": P("fsdp", None, None, None),
          "pp2": P("dp", None, None, None)}


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_the_flash_shard_map_takes_its_axes_from_the_rules(layout):
    mesh = ShardingConfig(**LAYOUTS[layout][0]).build_mesh(jax.devices()[:4])
    by_rules = sharding.logical_spec(mesh, ("batch", None, "heads", None))
    assert _pin_and_shard_map(mesh, (BATCH, SEQ, 2, 32)) == (by_rules,
                                                             by_rules)
    # where a dim does not divide nothing is pinned, and the kernel's
    # shard_map gathers that dim over the axis and keeps the others cut
    uneven = UNEVEN[layout]
    assert _pin_and_shard_map(mesh, (6, SEQ, 3, 32)) == (
        uneven if uneven == by_rules else None, uneven)


def test_a_rule_moves_the_pin_and_the_shard_map_together(monkeypatch):
    mesh = ShardingConfig(tp=2, fsdp=2).build_mesh(jax.devices()[:4])
    cut = P("fsdp", None, "tp", None)
    assert _pin_and_shard_map(mesh, (BATCH, SEQ, 2, 32)) == (cut, cut)
    monkeypatch.setitem(sharding.DEFAULT_RULES, "heads", None)
    whole = P("fsdp", None, None, None)
    assert _pin_and_shard_map(mesh, (BATCH, SEQ, 2, 32)) == (whole, whole)

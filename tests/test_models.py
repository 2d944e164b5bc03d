"""Model tests: GPT-2 forward/train-step (sharded), MNIST learns, llama
decode-with-cache matches full forward."""

import ast
import pathlib
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import gpt2, llama, mnist
from ray_tpu.parallel.sharding import ShardingConfig, shard_params


def test_gpt2_forward_shapes():
    cfg = gpt2.GPT2_TINY
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((2, 32), jnp.int32)
    logits = gpt2.forward(params, tokens, cfg)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert jnp.isfinite(logits).all()


@pytest.mark.slow
def test_gpt2_train_step_learns():
    cfg = gpt2.GPT2_TINY
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)
    step = jax.jit(gpt2.make_train_step(cfg, opt))
    rng = jax.random.PRNGKey(1)
    tokens = jax.random.randint(rng, (4, 33), 0, 64)  # small token space
    first = None
    for i in range(20):
        params, opt_state, metrics = step(params, opt_state, {"tokens": tokens})
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first - 0.5, (first, float(metrics["loss"]))


def test_gpt2_sharded_train_step():
    """Full DP+FSDP+TP train step jitted over the 8-device mesh."""
    cfg = gpt2.GPT2_TINY
    scfg = ShardingConfig(dp=2, fsdp=2, tp=2)
    mesh = scfg.build_mesh()
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    params = shard_params(params, scfg, mesh)
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)
    step = gpt2.make_train_step(cfg, opt)
    batch_sharding = {"tokens": scfg.named_sharding(mesh, "batch", None)}
    jstep = jax.jit(step, in_shardings=(None, None, batch_sharding))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, 64)
    params2, opt_state, metrics = jstep(params, opt_state, {"tokens": tokens})
    assert jnp.isfinite(metrics["loss"])
    # param sharding preserved through the step
    emb = params2["wte"]["embedding"]
    assert emb.sharding.spec == P("tp", "fsdp")


def test_gpt2_ring_attention_matches_flash():
    cfg = gpt2.GPT2_TINY
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0, cfg.vocab_size)

    dense = gpt2.forward(params, tokens, cfg)

    from ray_tpu.parallel.context import use_mesh

    ring_cfg = replace(cfg, attention="ring")
    scfg = ShardingConfig(sp=8)
    mesh = scfg.build_mesh()

    spec_tok = NamedSharding(mesh, P(None, "sp"))
    with use_mesh(mesh):
        out = jax.jit(
            lambda p, t: gpt2.forward(p, t, ring_cfg),
            in_shardings=(None, spec_tok),
        )(params, jax.device_put(tokens, spec_tok))
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=5e-2)


def test_gpt2_mixture_under_remat_is_the_same_step():
    """The one loop over `jax.checkpoint(block)`: a mixture's loss, its
    auxiliary term and its gradients are those of the loop that keeps
    every activation."""
    cfg = replace(gpt2.GPT2_TINY, moe_experts=4, attention="dense",
                  compute_dtype=jnp.float32)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                          cfg.vocab_size)}

    def step(cfg):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: gpt2.loss_fn(p, batch, cfg)))(params)
        _, aux = jax.jit(lambda p: gpt2._trunk(
            p, batch["tokens"][:, :-1], cfg))(params)
        return loss, aux, grads

    with jax.default_matmul_precision("highest"):
        loss, aux, grads = step(cfg)
        rloss, raux, rgrads = step(replace(cfg, remat=True))
    assert float(aux) > 0.5          # a balanced router gives 1
    np.testing.assert_allclose(float(rloss), float(loss), atol=1e-6, rtol=0)
    np.testing.assert_allclose(float(raux), float(aux), atol=1e-6, rtol=0)
    for (path, want), got in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree.leaves(rgrads)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-6, rtol=0,
            err_msg=jax.tree_util.keystr(path))


def test_models_and_ops_reach_around_nothing():
    """A model file takes what it shares from `models/layers.py`, a kernel
    file from `ops/__init__.py`, never another module's underscore names;
    and no file of `models/` or `ops/` names a mesh axis:
    `parallel/sharding.py`'s rules do."""
    from ray_tpu.parallel.mesh import AXIS_ORDER

    package = pathlib.Path(gpt2.__file__).parents[1]
    files = sorted([*(package / "models").glob("*.py"),
                    *(package / "ops").glob("*.py")])
    assert len(files) > 6
    for path in files:
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.ImportFrom)
                    and (node.module or "").startswith("ray_tpu.")):
                private = [a.name for a in node.names
                           if a.name.startswith("_")]
                assert not private, (path.name, node.module, private)
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                assert node.value not in AXIS_ORDER, (
                    path.name, node.lineno, node.value)


@pytest.mark.slow
def test_mnist_learns():
    params = mnist.init_params(jax.random.PRNGKey(0))
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, batch):
        (loss, acc), grads = jax.value_and_grad(mnist.loss_fn, has_aux=True)(
            params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss, acc

    rng = jax.random.PRNGKey(0)
    for i in range(30):
        batch = mnist.synthetic_batch(jax.random.fold_in(rng, i), 64)
        params, opt_state, loss, acc = step(params, opt_state, batch)
    assert float(acc) > 0.5, float(acc)


@pytest.mark.slow
def test_llama_decode_matches_forward():
    cfg = llama.LLAMA_TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    B, S = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    full_logits, _ = llama.forward(params, tokens, cfg)

    # cached prefill of S-1 tokens then decode 1: last-position logits match
    caches = llama.init_cache(cfg, B, jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S - 1), (B, S - 1))
    _, caches = llama.forward(params, tokens[:, :-1], cfg, caches, 0, positions)
    pos = jnp.full((B, 1), S - 1, jnp.int32)
    step_logits, _ = llama.forward(params, tokens[:, -1:], cfg, caches, S - 1, pos)
    np.testing.assert_allclose(
        np.asarray(step_logits[:, 0]), np.asarray(full_logits[:, -1]),
        atol=5e-2,
    )


def test_llama_generate():
    cfg = llama.LLAMA_TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.ones((1, 4), jnp.int32)
    out = llama.generate(params, prompt, cfg, max_new_tokens=8)
    assert out.shape == (1, 12)
    assert (out[:, :4] == prompt).all()

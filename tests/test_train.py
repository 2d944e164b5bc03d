"""Train layer end-to-end: multi-process global mesh, MNIST DP, GPT-2
sharded, checkpoint/restore, worker-kill fault tolerance.

Mirrors the reference's Train test strategy
(`python/ray/train/tests/test_backend.py`, `test_data_parallel_trainer.py`,
`test_trainer_restore.py`) on the virtual-device CPU path: 2 worker
processes x 4 virtual CPU devices = one 8-device global mesh.
"""

import os

import numpy as np
import pytest


def _mnist_dp_loop(config):
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import mnist
    from ray_tpu.train import session

    ctx = session.get_context()
    rng = jax.random.PRNGKey(0)
    params = mnist.init_params(rng)
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    start_step = 0
    ckpt = session.get_checkpoint()
    if ckpt is not None:
        state = ckpt.to_dict()
        params = jax.tree.map(jnp.asarray, state["params"])
        opt_state = jax.tree.map(
            lambda t, x: jnp.asarray(x) if isinstance(x, np.ndarray) else x,
            opt.init(params), state["opt_state"],
        )
        start_step = state["step"]

    @jax.jit
    def step_fn(params, opt_state, batch):
        (loss, acc), grads = jax.value_and_grad(
            mnist.loss_fn, has_aux=True)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss, acc

    # Fixed held-out batch for the learning assertion: per-step TRAIN
    # losses are measured on different random batches, so over a 5-step
    # run batch-difficulty noise (~±0.01) can exceed the actual learning
    # progress and the last-vs-first comparison fails by luck of the
    # draw (observed on this host: 2.4618 vs 2.4583).  Evaluating on one
    # constant batch makes the drop deterministic.
    eval_batch = mnist.synthetic_batch(jax.random.PRNGKey(10**6),
                                       batch_size=256)
    eval_loss = jax.jit(lambda p: mnist.loss_fn(p, eval_batch)[0])

    for step in range(start_step, config["num_steps"]):
        # Per-worker shard of the global batch (data parallel over workers).
        batch = mnist.synthetic_batch(
            jax.random.PRNGKey(step * ctx.world_size + ctx.world_rank),
            batch_size=config["batch_size"] // ctx.world_size,
        )
        params, opt_state, loss, acc = step_fn(params, opt_state, batch)
        session.report(
            {"step": step + 1, "loss": float(eval_loss(params)),
             "train_loss": float(loss), "acc": float(acc),
             "rank": ctx.world_rank},
            checkpoint=session.Checkpoint.from_dict({
                "params": params, "opt_state": opt_state, "step": step + 1,
            }) if (step + 1) % config.get("ckpt_every", 10**9) == 0 else None,
        )


def _global_mesh_loop(config):
    """Forms the global 8-device mesh across 2 worker processes and runs a
    sharded computation verifying cross-process collectives."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.train import session

    ctx = session.get_context()
    devs = jax.devices()
    mesh = Mesh(np.array(devs).reshape(2, 4), ("dp", "tp"))
    local = np.full((4, 8), ctx.world_rank + 1.0, np.float32)
    arr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp", None)), local
    )
    total = jax.jit(
        lambda a: a.sum(), out_shardings=NamedSharding(mesh, P())
    )(arr)
    session.report({
        "global_devices": len(devs),
        "local_devices": len(jax.local_devices()),
        "process_index": jax.process_index(),
        "sum": float(total),
    })


def _gpt2_sharded_loop(config):
    """GPT-2 tiny with fsdp+tp sharding over the multi-process global mesh."""
    import jax
    import jax.numpy as jnp
    import optax
    from dataclasses import replace

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.context import use_mesh
    from ray_tpu.parallel.sharding import ShardingConfig, shard_params
    from ray_tpu.train import session

    cfg = replace(gpt2.GPT2_TINY, compute_dtype=jnp.float32)
    scfg = ShardingConfig(dp=1, fsdp=2, tp=4)
    mesh = scfg.build_mesh(devices=jax.devices())
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    params = shard_params(params, scfg, mesh)
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)
    step_fn = gpt2.make_train_step(cfg, opt)

    batch_sharding = {"tokens": scfg.named_sharding(mesh, "batch", None)}
    with use_mesh(mesh):
        jstep = jax.jit(step_fn, in_shardings=(None, None, batch_sharding))
        losses = []
        for step in range(config["num_steps"]):
            tokens = jax.random.randint(
                jax.random.PRNGKey(step), (4, 65), 0, cfg.vocab_size
            )
            tokens = jax.device_put(
                tokens, scfg.named_sharding(mesh, "batch", None)
            )
            params, opt_state, metrics = jstep(
                params, opt_state, {"tokens": tokens}
            )
            losses.append(float(metrics["loss"]))
            session.report({"step": step + 1, "loss": losses[-1]})


@pytest.fixture(scope="module")
def ray_train(request):
    import ray_tpu

    ray_tpu.init(num_cpus=8)
    yield ray_tpu
    ray_tpu.shutdown()


def _jax_cfg():
    from ray_tpu.train import JaxConfig

    return JaxConfig(platform="cpu", devices_per_worker=4)


@pytest.mark.slow
def test_global_mesh_bootstrap(ray_train, tmp_path):
    """2 worker processes form one 8-device mesh; collectives cross."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    trainer = JaxTrainer(
        _global_mesh_loop,
        train_loop_config={},
        jax_config=_jax_cfg(),
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="mesh", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["global_devices"] == 8
    assert result.metrics["local_devices"] == 4
    # sum of (4x8 of 1.0) + (4x8 of 2.0) = 32 + 64
    assert result.metrics["sum"] == 96.0


@pytest.mark.slow
def test_mnist_dp_two_workers(ray_train, tmp_path):
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    trainer = JaxTrainer(
        _mnist_dp_loop,
        train_loop_config={"num_steps": 5, "batch_size": 64, "ckpt_every": 5},
        jax_config=_jax_cfg(),
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="mnist", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 5
    assert len(result.metrics_history) == 5
    assert result.checkpoint is not None
    state = result.checkpoint.to_dict()
    assert state["step"] == 5
    # loss should drop on the synthetic separable data
    assert result.metrics_history[-1]["loss"] < result.metrics_history[0]["loss"]


@pytest.mark.slow
def test_gpt2_sharded_two_workers(ray_train, tmp_path):
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    trainer = JaxTrainer(
        _gpt2_sharded_loop,
        train_loop_config={"num_steps": 2},
        jax_config=_jax_cfg(),
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="gpt2", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 2
    assert np.isfinite(result.metrics["loss"])


def _crashy_loop(config):
    """Crashes rank 0 once at step 3 (before reporting it); after restart it
    resumes from the checkpoint and completes."""
    import os

    from ray_tpu.train import session

    ctx = session.get_context()
    start = 0
    ckpt = session.get_checkpoint()
    if ckpt is not None:
        start = ckpt.to_dict()["step"]
    marker = config["marker_file"]
    for step in range(start, config["num_steps"]):
        if (step == 3 and ctx.world_rank == 0
                and not os.path.exists(marker)):
            with open(marker, "w") as f:
                f.write("crashed")
            os._exit(1)
        session.report(
            {"step": step + 1, "resumed_from": start},
            checkpoint=session.Checkpoint.from_dict({"step": step + 1}),
        )


def test_worker_crash_restart_from_checkpoint(ray_train, tmp_path):
    from ray_tpu.train import (
        FailureConfig,
        JaxTrainer,
        RunConfig,
        ScalingConfig,
    )

    marker = str(tmp_path / "crash_marker")
    trainer = JaxTrainer(
        _crashy_loop,
        train_loop_config={"num_steps": 6, "marker_file": marker},
        jax_config=_jax_cfg(),
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(
            name="crashy", storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=1),
        ),
    )
    result = trainer.fit()
    assert result.error is None
    assert os.path.exists(marker), "the crash leg must have run"
    assert result.metrics["step"] == 6
    # restarted leg resumed from the step-2 (or later) checkpoint, not 0
    assert result.metrics["resumed_from"] >= 2
    assert result.checkpoint.to_dict()["step"] == 6


def test_max_failures_exhausted(ray_train, tmp_path):
    from ray_tpu.train import (
        FailureConfig,
        JaxTrainer,
        RunConfig,
        ScalingConfig,
        TrainingFailedError,
    )

    def always_crash(config):
        import os

        os._exit(1)

    trainer = JaxTrainer(
        always_crash,
        train_loop_config={},
        jax_config=_jax_cfg(),
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="dead", storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=1),
        ),
    )
    result = trainer.fit()
    assert isinstance(result.error, TrainingFailedError)


def test_user_error_propagates(ray_train, tmp_path):
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    def bad_loop(config):
        raise ValueError("boom in train loop")

    trainer = JaxTrainer(
        bad_loop,
        train_loop_config={},
        jax_config=_jax_cfg(),
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="err", storage_path=str(tmp_path)),
    )
    with pytest.raises(Exception, match="boom in train loop"):
        trainer.fit()


# ---------------------------------------------------------------------------
# compile-cache placement


def _compile_cache_seen_by_a_process(cwd, cache_env):
    """(what ensure_compile_cache returns, what jax then uses) in a fresh
    process started from ``cwd``."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(cache_env, PYTHONPATH=repo)
    out = subprocess.run(
        [sys.executable, "-c",
         "from ray_tpu.util.compile_cache import ensure_compile_cache\n"
         "import jax\n"
         "print(ensure_compile_cache())\n"
         "print(jax.config.jax_compilation_cache_dir)\n"],
        env=env, cwd=cwd, capture_output=True, text=True, timeout=120,
        check=True).stdout.split()
    return out[0], out[1]


def test_compile_cache_is_one_fixed_directory_in_the_checkout(tmp_path):
    from ray_tpu.util.compile_cache import CHECKOUT_CACHE_DIR

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert CHECKOUT_CACHE_DIR == os.path.join(repo, ".jax_cache")
    other = tmp_path / "elsewhere"
    other.mkdir()
    # two processes, two cwds, one directory: a cache that moves never hits
    for cwd in (tmp_path, other):
        assert _compile_cache_seen_by_a_process(cwd, {}) == (
            CHECKOUT_CACHE_DIR, CHECKOUT_CACHE_DIR)


def test_compile_cache_placed_from_outside_is_left_alone(
        ray_train, tmp_path, monkeypatch):
    placed = str(tmp_path / "placed")
    # jax reads the variable itself; the helper sets nothing on top of it
    assert _compile_cache_seen_by_a_process(
        tmp_path, {"JAX_COMPILATION_CACHE_DIR": placed}) == (placed, placed)

    # ... and a worker spawned from here on inherits it through its raylet
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)

    @ray_train.remote
    def seen():
        return os.environ.get("JAX_COMPILATION_CACHE_DIR")

    fresh_worker = {"env_vars": {"CACHE_PLACEMENT_TEST": "1"}}
    assert ray_train.get(
        seen.options(runtime_env=fresh_worker).remote(), timeout=60) == placed

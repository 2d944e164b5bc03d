"""Train GPT-2 124M data-parallel with JaxTrainer.

Run:  python examples/train_gpt2.py [--workers 2] [--steps 20]
      python examples/train_gpt2.py --tpu --full    (one worker, one chip)

Each worker joins one jax.distributed process group (the TPU-native
analogue of the reference's NCCL process-group bootstrap); the train step
is one jitted XLA program (fwd, bwd, adamw) with bf16 compute and the
Pallas flash-attention kernel.  Without ``--tpu`` the workers run on the
CPU with two virtual XLA host devices each; with it, one worker is granted
one TPU chip and the trainer refuses to start unless it holds it.  This
process never touches jax either way: a chip belongs to one process.
"""

import os
import sys

# allow running straight from a repo checkout without installation
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse


def train_loop(config):
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.models import gpt2

    cfg = gpt2.GPT2_TINY if config.get("tiny") else gpt2.GPT2_SMALL
    batch, seq = config.get("batch", 4), config.get("seq", 128)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(3e-4, weight_decay=0.1)
    opt_state = opt.init(params)
    step = jax.jit(gpt2.make_train_step(cfg, opt), donate_argnums=(0, 1))

    rng = jax.random.PRNGKey(train.get_world_rank())
    for i in range(config.get("steps", 20)):
        rng, sub = jax.random.split(rng)
        tokens = jax.random.randint(sub, (batch, seq + 1), 0, cfg.vocab_size)
        params, opt_state, metrics = step(params, opt_state,
                                          {"tokens": tokens})
        train.report({"loss": float(metrics["loss"]), "step": i})


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workers", type=int, default=2)
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--full", action="store_true",
                        help="train GPT-2 124M (default: the tiny config, "
                        "sized for CPU smoke runs)")
    parser.add_argument("--tpu", action="store_true",
                        help="one worker on one TPU chip instead of CPU "
                        "workers")
    args = parser.parse_args()
    args.tiny = not args.full

    import ray_tpu
    from ray_tpu.train import JaxConfig, JaxTrainer, RunConfig, ScalingConfig

    if args.tpu:
        # one process per chip; several chips of a host go to ONE worker
        # (resources_per_worker={"TPU": n}) and a ShardingConfig inside it
        args.workers = 1
        ray_tpu.init(num_cpus=2, num_tpus=1)
        jax_config = JaxConfig()
        scaling = ScalingConfig(num_workers=1, use_tpu=True)
    else:
        # provision a logical CPU per worker regardless of host core count
        ray_tpu.init(num_cpus=args.workers + 1)
        jax_config = JaxConfig(platform="cpu", devices_per_worker=2)
        scaling = ScalingConfig(num_workers=args.workers,
                                resources_per_worker={"CPU": 1})
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={"steps": args.steps, "tiny": args.tiny},
        jax_config=jax_config,
        scaling_config=scaling,
        run_config=RunConfig(name="gpt2_example"),
    )
    result = trainer.fit()
    print("final:", result.metrics)
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()

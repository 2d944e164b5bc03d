"""Does the Train path still start on the chip?

    python chip_smoke.py              GPT-2 124M on one TPU chip
    python chip_smoke.py --chips 4    the same, fsdp=4 over a four-chip host
    python chip_smoke.py --tiny       the same code at GPT2_TINY on the CPU
                                      (--chips N: on N virtual CPU devices)

The normal path and nothing else: this process never touches jax — it calls
``ray_tpu.init`` and runs a ``JaxTrainer`` whose one worker is granted the
chips; the worker holds them, trains ``GPT2_SMALL`` at batch 16 x sequence
1024 for one compiling step plus a few more, and reports what it saw from
inside.  Any phase that fails, fails the run; the last line of stdout is
the JSON verdict and is printed only when everything held.  ``--tiny`` is
for debugging this command where there is no chip: it prints platform=cpu
and its verdict says so.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

SEED = 0
STEPS = 6          # step 0 compiles; five more
SYNC_STEPS = 4     # the sync check times this many steps, then twice it
# Step-0 loss of GPT2_SMALL from SEED on one chip; a run on more chips
# starts from the same parameters and tokens and must land on it.
ONE_CHIP_LOSS0 = 10.98285  # (my chip run, PR 21)
LOSS_TOLERANCE = 0.02


def train_loop(config):
    """Runs in the worker that holds the chips."""
    import re
    import time
    import warnings
    from dataclasses import replace

    import jax
    import optax

    from ray_tpu import train
    from ray_tpu.core import protocol
    from ray_tpu.models import gpt2
    from ray_tpu.models.layers import cast_weights
    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.parallel.context import use_mesh
    from ray_tpu.parallel.sharding import ShardingConfig, param_shardings
    from tools.chip_kernels import compare_with_reference

    # a shape the kernels cannot tile would run the O(S^2) reference
    warnings.simplefilter("error", fa.AttentionFallbackWarning)
    cache_hits = []
    jax.monitoring.register_event_listener(
        lambda event, **_: event == "/jax/compilation_cache/cache_hits"
        and cache_hits.append(event))

    tiny = config["tiny"]
    # every chip of the grant (--tiny: as many virtual CPU devices)
    devices = jax.devices()[:config["fsdp"] * config["tp"]]
    cfg = gpt2.GPT2_TINY if tiny else gpt2.GPT2_SMALL
    batch, seq = (4, 128) if tiny else (16, 1024)
    report = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": jax.device_count(),
        "codec": "native" if protocol.NATIVE_CODEC_ACTIVE else "python",
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "model": {"n_layer": cfg.n_layer, "n_embd": cfg.n_embd,
                  "n_head": cfg.n_head, "vocab_size": cfg.vocab_size,
                  "batch": batch, "seq": seq},
    }

    # the attention kernels against the reference, at the model's shape
    report["attention_rel_err"], _ = compare_with_reference(
        (2, seq, cfg.n_head, cfg.head_dim), cfg.compute_dtype)

    # -- the train step, sharded over every chip of the grant --------------
    scfg = ShardingConfig(fsdp=config["fsdp"], tp=config["tp"])
    mesh = scfg.build_mesh(devices)
    report["mesh"] = dict(mesh.shape)
    opt = optax.adamw(1e-4, weight_decay=0.1)
    init = lambda key: gpt2.init_params(key, cfg)
    pshard = param_shardings(jax.eval_shape(init, jax.random.PRNGKey(SEED)),
                             scfg, mesh)
    # born sharded: no chip ever holds the whole tree
    params = jax.jit(init, out_shardings=pshard)(jax.random.PRNGKey(SEED))
    # the moments inherit the parameters' shardings; the step counter is
    # born on one device and belongs on all of them
    everywhere = scfg.named_sharding(mesh)
    opt_state = jax.tree.map(
        lambda x: x if x.ndim else jax.device_put(x, everywhere),
        opt.init(params))
    data = {"tokens": jax.device_put(
        jax.random.randint(jax.random.PRNGKey(SEED + 7), (batch, seq + 1), 0,
                           cfg.vocab_size),
        scfg.named_sharding(mesh, "batch", None))}
    jax.block_until_ready((params, opt_state, data))
    report["bytes_in_use_per_device"] = [
        (d.memory_stats() or {}).get("bytes_in_use") for d in devices]

    # the same loss on the pure-XLA path: dense attention, no Pallas, no
    # shard_map (taken now: the first step donates the parameters)
    dense = replace(cfg, attention="dense")
    report["reference_loss0"] = float(jax.jit(
        lambda p, b: gpt2.loss_fn(cast_weights(p, cfg.compute_dtype),
                                  b, dense))(params, data))

    with use_mesh(mesh):
        # parameters and optimizer state leave the step laid out as they
        # entered it (left to itself XLA re-shards the 1-D leaves, and the
        # next call's arguments no longer fit the compiled program)
        kept = jax.tree.map(lambda x: x.sharding, (params, opt_state))
        step = jax.jit(gpt2.make_train_step(cfg, opt), donate_argnums=(0, 1),
                       out_shardings=(*kept, None))
        t0 = time.perf_counter()
        lowered = step.lower(params, opt_state, data)
        t1 = time.perf_counter()
        hits_before = len(cache_hits)
        compiled = lowered.compile()
        report["step0"] = {
            "trace_and_lower_s": round(t1 - t0, 2),
            "compile_s": round(time.perf_counter() - t1, 2),
            "served_from_cache": len(cache_hits) > hits_before}

    # what attention the compiled step runs, from its own HLO: the Mosaic
    # kernels are tpu_custom_calls, and their q operand is (B, S, H*D) per
    # device
    hlo = compiled.as_text()
    kernel_lines = [l for l in hlo.splitlines()
                    if 'custom_call_target="tpu_custom_call"' in l]
    report["pallas_custom_calls"] = len(kernel_lines)
    shapes = {tuple(map(int, m)) for l in kernel_lines for m in re.findall(
        rf"bf16\[(\d+),{seq},(\d+)\]", l)}
    report["attention_operand_shapes"] = sorted(shapes)

    losses = []
    for i in range(STEPS):
        params, opt_state, metrics = compiled(params, opt_state, data)
        losses.append(float(metrics["loss"]))
        train.report({"step": i, "loss": losses[-1]})
    report["losses"] = losses

    # -- does block_until_ready wait for the device? ------------------------
    # If it does, wall time over 2N steps is twice that over N, and N steps
    # take as long as when the loss is fetched to the host at the end.
    def timed(n, sync):
        nonlocal params, opt_state
        t0 = time.perf_counter()
        for _ in range(n):
            params, opt_state, metrics = compiled(params, opt_state, data)
        sync(metrics["loss"])
        return time.perf_counter() - t0

    timed(1, float)
    t_n = timed(SYNC_STEPS, jax.block_until_ready)
    t_2n = timed(2 * SYNC_STEPS, jax.block_until_ready)
    t_host = timed(SYNC_STEPS, float)
    report["sync"] = {"steps": SYNC_STEPS, "block_until_ready_s": round(t_n, 4),
                      "twice_the_steps_s": round(t_2n, 4),
                      "host_transfer_s": round(t_host, 4)}
    report["steady_step_s"] = {
        "device_kind": report["device_kind"],
        "device_count": report["device_count"],
        "seconds": round(t_2n / (2 * SYNC_STEPS), 4)}
    report["peak_bytes_per_device"] = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    train.report({"step": STEPS, "loss": losses[-1], "report": report})


def check(report, args):
    """Every reason this run is not a pass."""
    bad = []
    want_platform = "cpu" if args.tiny else "tpu"
    if report["platform"] != want_platform:
        bad.append(f"platform is {report['platform']!r}, "
                   f"not {want_platform!r}")
    if not args.tiny and report["device_count"] != args.chips:
        bad.append(f"asked for {args.chips} chips, the worker sees "
                   f"{report['device_count']}")
    if not args.tiny and report["codec"] != "native":
        bad.append("the frame codec fell back to Python: the native "
                   "library did not build here")
    losses = report["losses"]
    if len(losses) != STEPS or not all(map(math.isfinite, losses)):
        bad.append(f"losses not finite: {losses}")
    elif not losses[-1] < losses[0]:
        bad.append(f"loss did not fall: {losses}")
    worst = max(report["attention_rel_err"].values())
    if not worst < 0.05:
        bad.append(f"attention kernels disagree with the reference: "
                   f"{report['attention_rel_err']}")
    if not abs(losses[0] - report["reference_loss0"]) < LOSS_TOLERANCE:
        bad.append(f"step-0 loss {losses[0]} is not within {LOSS_TOLERANCE} "
                   f"of the dense-attention reference "
                   f"{report['reference_loss0']}")
    if not args.tiny:
        # forward and fused backward for each of the layers
        n_layer = report["model"]["n_layer"]
        if report["pallas_custom_calls"] < 2 * n_layer:
            bad.append(f"{report['pallas_custom_calls']} compiled Pallas "
                       f"kernels in the step, expected {2 * n_layer}")
        m = report["model"]
        per_device = (m["batch"] // (report["device_count"] // args.tp),
                      m["n_embd"] // args.tp)
        if report["attention_operand_shapes"] != [per_device]:
            bad.append(f"attention operands per device are "
                       f"{report['attention_operand_shapes']} "
                       f"(batch, heads*head_dim), expected {per_device}")
        used = report["bytes_in_use_per_device"]
        if None in used or max(used) > 2 * min(used):
            bad.append(f"parameters and optimizer state are not spread "
                       f"over the devices: bytes in use {used}")
        sync = report["sync"]
        if not (1.6 < sync["twice_the_steps_s"] / sync["block_until_ready_s"]
                < 2.5 and 0.8 < sync["host_transfer_s"]
                / sync["block_until_ready_s"] < 1.25):
            bad.append(f"block_until_ready does not wait for the device: "
                       f"{sync}")
        if not abs(losses[0] - ONE_CHIP_LOSS0) < LOSS_TOLERANCE:
            bad.append(f"step-0 loss {losses[0]} is not within "
                       f"{LOSS_TOLERANCE} of the one-chip run's "
                       f"{ONE_CHIP_LOSS0}")
    return bad


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, default=1,
                        help="chips the one worker is granted (fsdp over "
                             "them)")
    parser.add_argument("--tp", type=int, default=1,
                        help="of those, how many ways tensor-parallel")
    parser.add_argument("--tiny", action="store_true",
                        help="GPT2_TINY on the CPU, kernels interpreted")
    args = parser.parse_args()
    if args.chips % args.tp:
        parser.error("--tp must divide --chips")

    import ray_tpu
    from ray_tpu.train import (JaxConfig, JaxTrainer, RunConfig,
                               ScalingConfig)

    ray_tpu.init(num_cpus=2, num_tpus=0 if args.tiny else args.chips)
    try:
        if args.tiny:
            scaling = ScalingConfig(num_workers=1)
        else:
            scaling = ScalingConfig(
                num_workers=1, use_tpu=True,
                resources_per_worker={"CPU": 1, "TPU": args.chips})
        trainer = JaxTrainer(
            train_loop,
            train_loop_config={"tiny": args.tiny,
                               "fsdp": args.chips // args.tp,
                               "tp": args.tp},
            # --tiny stands in virtual CPU devices for the chips
            jax_config=JaxConfig(
                devices_per_worker=args.chips if args.tiny else None),
            scaling_config=scaling,
            run_config=RunConfig(
                name="chip_smoke",
                storage_path=os.path.join(REPO, ".scratch", "chip_smoke")),
        )
        result = trainer.fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise result.error

    report = result.metrics["report"]
    for key, value in report.items():
        print(f"{key}={json.dumps(value)}")
    bad = check(report, args)
    if "jax" in sys.modules:
        bad.append("the parent process imported jax")
    if bad:
        sys.exit("chip_smoke FAILED:\n  " + "\n  ".join(bad))
    print(json.dumps({
        "ok": True,
        "device": {"platform": report["platform"],
                   "kind": report["device_kind"],
                   "count": report["device_count"]},
    }))


if __name__ == "__main__":
    main()

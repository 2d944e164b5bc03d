"""Headline benchmark: GPT-2 124M train-step throughput on TPU.

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "...", "vs_baseline": N}

Baseline: the reference stack's per-chip A100 throughput for GPT-2 124M
pretraining (torch + flash-attention ≈ 178k tokens/s on A100-40GB; the
BASELINE.json north star is >90% of that per chip).
"""

from __future__ import annotations

import json
import sys
import time

A100_TOKENS_PER_SEC = 178_000.0

# bf16 peak FLOP/s of one chip by jax's device_kind (Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s).  A device that is not here is an
# error, not a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def main():
    from ray_tpu.util.compile_cache import ensure_compile_cache

    ensure_compile_cache()

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import gpt2

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # no chip, no number
        sys.exit(f"bench.py measures a TPU; jax found {dev.platform!r} "
                 f"({dev.device_kind})")
    if dev.device_kind not in PEAK_BF16_FLOPS:
        sys.exit(f"no peak FLOP/s on record for device kind "
                 f"{dev.device_kind!r}")
    batch, seq, steps = 16, 1024, 10
    cfg = gpt2.GPT2_SMALL

    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(3e-4, weight_decay=0.1)
    opt_state = opt.init(params)
    step = jax.jit(gpt2.make_train_step(cfg, opt), donate_argnums=(0, 1))

    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1), 0,
                                cfg.vocab_size)
    batch_d = {"tokens": tokens}

    # warmup / compile
    params, opt_state, metrics = step(params, opt_state, batch_d)
    jax.block_until_ready(metrics)

    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, metrics = step(params, opt_state, batch_d)
        jax.block_until_ready(metrics)
        dt = time.perf_counter() - t0
        best = max(best, batch * seq * steps / dt)
    tokens_per_sec = best
    # MFU vs the chip's bf16 peak; count is full fwd+bwd already.
    flops_per_token = gpt2.count_flops_per_token(cfg, seq)
    mfu = tokens_per_sec * flops_per_token / PEAK_BF16_FLOPS[dev.device_kind]
    print(json.dumps({
        "metric": "gpt2_124m_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tokens_per_sec / A100_TOKENS_PER_SEC, 4),
        "mfu_v5e": round(mfu, 4),
    }))


if __name__ == "__main__":
    sys.exit(main())

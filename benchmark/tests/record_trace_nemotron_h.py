"""Records the small trace that `test_nemotron_h_cell.py` checks the
state-space readers against.  Run on the machine with the chip (not under
pytest):

    python benchmark/tests/record_trace_nemotron_h.py [out_dir]

One process, one chip: three steps of a `nemotron_h` step at small sizes
that keep every part of the full-size step (a Mamba-2 layer, an attention
layer and a mixture, one mixer each, recomputed; hidden 256; eight
state-space heads of 32 in two groups with a state of 64, four taps, chunks
of 128; four query heads on two key/value heads of 128; sixteen experts 160
wide of which this chip holds four, two a token, so that the buffer is
shorter than the routed rows, and a shared expert 320 wide; sequence 2,048:
sixteen chunks for the carry and two blocks for the flash kernels' long
form; batch 2), so the trace holds what the cell's trace holds in a few
hundred kilobytes.  It also writes `trace_dump_nemotron_h.txt`: device
seconds by scope and phase, as `tools/dump_trace_names.py` prints them.
"""

import glob
import gzip
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CONFIG = {
    "num_hidden_layers": 3, "hybrid_override_pattern": "M*E",
    "hidden_size": 256, "mamba_num_heads": 8, "mamba_head_dim": 32,
    "n_groups": 2, "ssm_state_size": 64, "conv_kernel": 4, "chunk_size": 128,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
    "moe_intermediate_size": 160,
    "moe_shared_expert_intermediate_size": 320, "n_routed_experts": 4,
    "experts_held": {"first": 4, "of": 16}, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "vocab_size": 1024, "layer_norm_epsilon": 1e-5, "renorm_eps": 1e-20,
    "bias_update_speed": 0.001, "published": {"num_hidden_layers": 52},
    "loss_chunk_rows": 2048, "compute_dtype": "bfloat16", "remat": True,
    "layout": {"fsdp": 1},
    "optimizer": {"learning_rate": 1e-4, "b1": 0.9, "b2": 0.999,
                  "eps": 1e-8, "weight_decay": 0.1}}
BATCH, SEQ = 2, 2048


def main():
    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        ROOT, "chiprun_out", "trace_fixture")
    os.makedirs(out, exist_ok=True)
    import jax
    import numpy as np

    from benchmark.families.nemotron_h import Family
    from benchmark.harness import scope_trace

    devices = jax.devices()[:1]
    family = Family(CONFIG)
    family.bind(devices)
    params, opt_state = family.init_state(0)
    batch = family.place_batch(np.random.default_rng(0).integers(
        0, 1024, (BATCH, SEQ + 1), dtype=np.int32))
    step = family.lower_step(params, opt_state, batch).compile()
    for _ in range(2):
        params, opt_state, m = step(params, opt_state, batch)
    float(m["loss"])
    raw = os.path.join(out, "raw")
    shutil.rmtree(raw, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(raw, profiler_options=options)
    for _ in range(3):
        params, opt_state, m = step(params, opt_state, batch)
        float(m["loss"])
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(raw, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    name = f"{devices[0].platform}1_nemotron_h.xplane.pb.gz"
    with open(path, "rb") as src, gzip.open(os.path.join(out, name),
                                            "wb") as dst:
        shutil.copyfileobj(src, dst)
    found = scope_trace.reduce(scope_trace.events(path),
                               *scope_trace.vocabulary())
    with open(os.path.join(out, "trace_dump_nemotron_h.txt"), "w") as f:
        if found is None:           # no device plane: not a chip's trace
            found = {"busy_s": None, "named_s": None, "scopes": {}}
        print(f"busy_s {found['busy_s']!r} named_s {found['named_s']!r}",
              file=f)
        for scope, seconds in sorted(found["scopes"].items()):
            print(f"{seconds!r} {scope} {found['in_scope'].get(scope, {})}",
                  file=f)
    shutil.rmtree(raw)
    print(name, os.path.getsize(os.path.join(out, name)), "bytes",
          "rows_held", int(m["rows_held"]), "overflowed",
          int(m["moe_overflow_layers"]))


if __name__ == "__main__":
    main()

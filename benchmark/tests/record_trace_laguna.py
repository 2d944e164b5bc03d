"""Records the small trace and timeline that `test_laguna_cell.py` checks the
two new readers against.  Run on the machine with the chip (not under
pytest):

    python benchmark/tests/record_trace_laguna.py [out_dir]

One process, one chip: three steps of a `laguna` step at small sizes that
keep every part of the full-size step (five layers, recomputed: full +
dense, three sliding + sparse, full + sparse; hidden 256; six and eight
query heads on two key/value heads of 128, so both kinds' gates and both
head counts are in one program; sixteen experts 256 wide of which this chip
holds four, two a token, beside a shared expert; one sequence of 2,048
tokens under a window of 384, so the flash kernels take their long form,
four 512-tiles a side, and the window is narrower than a pair of tiles and
neither divides one nor is divided by one; YaRN by 4 over 512 original
positions over half of each full layer's heads), so the trace holds what the
cell's trace holds in a few hundred kilobytes.  It also writes
`timeline_laguna.json`, the counters the step's trace left on the job
timeline, and `trace_dump_laguna.txt`: device seconds by scope and phase, as
`tools/dump_trace_names.py` prints them.
"""

import glob
import gzip
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

KINDS = ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
CONFIG = {
    "num_hidden_layers": 5, "layer_types": KINDS,
    "mlp_layer_types": ["dense"] + ["sparse"] * 4,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6], "gating": True,
    "sliding_window": 384, "hidden_size": 256, "intermediate_size": 1024,
    "num_key_value_heads": 2, "head_dim": 128, "moe_intermediate_size": 256,
    "shared_expert_intermediate_size": 256,
    "num_experts": 4, "experts_held": {"first": 4, "of": 16},
    "num_experts_per_tok": 2, "moe_routed_scaling_factor": 2.5,
    "moe_apply_router_weight_on_input": False, "bias_update_speed": 0.001,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 4,
            "original_max_position_embeddings": 512, "beta_fast": 32,
            "beta_slow": 1, "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "rms_norm_eps": 1e-6, "vocab_size": 1024,
    "published": {"num_hidden_layers": 40},
    "loss_chunk_rows": 2048, "compute_dtype": "bfloat16", "remat": True,
    "layout": {"fsdp": 1},
    "optimizer": {"learning_rate": 1e-4, "b1": 0.9, "b2": 0.999,
                  "eps": 1e-8, "weight_decay": 0.1}}
BATCH, SEQ = 1, 2048


def main():
    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        ROOT, "chiprun_out", "trace_fixture")
    os.makedirs(out, exist_ok=True)
    import jax
    import numpy as np

    from benchmark.families.laguna import Family
    from benchmark.harness import scope_trace
    from ray_tpu.util import tracing

    devices = jax.devices()[:1]
    family = Family(CONFIG)
    family.bind(devices)
    params, opt_state = family.init_state(0)
    batch = family.place_batch(np.random.default_rng(0).integers(
        0, 1024, (BATCH, SEQ + 1), dtype=np.int32))
    with tracing.timeline_span("train.fit", root=True) as job:
        step = family.lower_step(params, opt_state, batch).compile()
    with open(os.path.join(out, "timeline_laguna.json"), "w") as f:
        json.dump({"spans": [], "counters": tracing.timeline_take(
            job.trace_id)["counters"]}, f, indent=1, sort_keys=True)
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
    name = f"{devices[0].platform}1_laguna.xplane.pb.gz"
    with open(path, "rb") as src, gzip.open(os.path.join(out, name),
                                            "wb") as dst:
        shutil.copyfileobj(src, dst)
    found = scope_trace.reduce(scope_trace.events(path),
                               *scope_trace.vocabulary())
    with open(os.path.join(out, "trace_dump_laguna.txt"), "w") as f:
        if found is None:           # no device plane: not a chip's trace
            found = {"busy_s": None, "named_s": None, "scopes": {}}
        print(f"busy_s {found['busy_s']!r} named_s {found['named_s']!r}",
              file=f)
        for scope, seconds in sorted(found["scopes"].items()):
            print(f"{seconds!r} {scope} {found['in_scope'].get(scope, {})}",
                  file=f)
    shutil.rmtree(raw)
    print(name, os.path.getsize(os.path.join(out, name)), "bytes", "loss",
          float(m["loss"]), "overflowed layers", int(m["moe_overflow_layers"]))


if __name__ == "__main__":
    main()

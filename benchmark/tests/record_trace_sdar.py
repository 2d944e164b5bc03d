"""Records the small trace and timeline that `test_sdar_cell.py` checks block
diffusion's readers against.  Run on the machine with the chip (not under
pytest):

    python benchmark/tests/record_trace_sdar.py [out_dir]

One process, one chip: three steps of an `sdar` step at small sizes that
keep every part of the full-size step (two layers, recomputed; hidden 256;
eight query heads on two key/value heads of 128; sixteen experts 256 wide
of which this chip holds four, two a token, so that the buffer is shorter
than the routed rows; sequences of 1,024 tokens in blocks of 4, so 2,048
rows a sequence and the flash kernels take their long form under the rule,
two tiles a kind of row in the backward; batch 2), so the trace holds what
the cell's trace holds in a few hundred kilobytes.  It also writes
`timeline_sdar.json`, the counters the step's trace left on the job
timeline, and `trace_dump_sdar.txt`: device seconds by scope and phase, as
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

CONFIG = {
    "num_hidden_layers": 2, "hidden_size": 256, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 128, "moe_intermediate_size": 256,
    "num_experts": 4, "experts_held": {"first": 4, "of": 16},
    "num_experts_per_tok": 2, "norm_topk_prob": True, "rope_theta": 1000000,
    "rms_norm_eps": 1e-6, "router_aux_loss_coef": 0.1, "block_length": 4,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "vocab_size": 1000, "padded_vocab_size": 1024,
    "published": {"num_hidden_layers": 48},
    "loss_chunk_rows": 2048, "compute_dtype": "bfloat16", "remat": True,
    "layout": {"fsdp": 1},
    "optimizer": {"learning_rate": 1e-4, "b1": 0.9, "b2": 0.999,
                  "eps": 1e-8, "weight_decay": 0.1}}
BATCH, SEQ = 2, 1024


def main():
    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        ROOT, "chiprun_out", "trace_fixture")
    os.makedirs(out, exist_ok=True)
    import jax
    import numpy as np

    from benchmark.families.sdar import Family
    from benchmark.harness import scope_trace
    from ray_tpu.util import tracing

    devices = jax.devices()[:1]
    family = Family(CONFIG)
    family.bind(devices)
    params, opt_state = family.init_state(0)
    batch = family.place_batch(np.random.default_rng(0).integers(
        0, 1000, (BATCH, SEQ + 1), dtype=np.int32))
    with tracing.timeline_span("train.fit", root=True) as job:
        step = family.lower_step(params, opt_state, batch).compile()
    with open(os.path.join(out, "timeline_sdar.json"), "w") as f:
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
    name = f"{devices[0].platform}1_sdar.xplane.pb.gz"
    with open(path, "rb") as src, gzip.open(os.path.join(out, name),
                                            "wb") as dst:
        shutil.copyfileobj(src, dst)
    found = scope_trace.reduce(scope_trace.events(path),
                               *scope_trace.vocabulary())
    with open(os.path.join(out, "trace_dump_sdar.txt"), "w") as f:
        if found is None:           # no device plane: not a chip's trace
            found = {"busy_s": None, "named_s": None, "scopes": {}}
        print(f"busy_s {found['busy_s']!r} named_s {found['named_s']!r}",
              file=f)
        for scope, seconds in sorted(found["scopes"].items()):
            print(f"{seconds!r} {scope} {found['in_scope'].get(scope, {})}",
                  file=f)
    shutil.rmtree(raw)
    print(name, os.path.getsize(os.path.join(out, name)), "bytes", "loss",
          float(m["loss"]), "masked", float(m["masked_share"]),
          "overflowed layers", int(m["moe_overflow_layers"]))


if __name__ == "__main__":
    main()

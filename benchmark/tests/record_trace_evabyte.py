"""Records the small trace and timeline that `test_evabyte_cell.py` checks the
new readers against.  Run on the machine with the chip (not under pytest):

    python benchmark/tests/record_trace_evabyte.py [out_dir]

One process, one chip: three steps of an `evabyte` step at small sizes that
keep every part of the full-size step (two published layers, recomputed;
hidden 512, 2 of 4 heads of the published 128, so the flash kernels under
aligned windows, the remote pair and the pooling pair all run; chunks of the
published 16 in windows of 512, four windows a sequence of 2,048 bytes, so
three queries in four read summaries; the eight prediction heads over the 320
rows), so the trace holds what the cell's trace holds in a few hundred
kilobytes.  It also writes `timeline_evabyte.json`, the counters the step's
trace left on the job timeline, and `trace_dump_evabyte.txt`: device seconds
by scope and phase, as `tools/dump_trace_names.py` prints them.
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
    "num_hidden_layers": 2, "first_layer": 14, "hidden_size": 512,
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "published": {"num_attention_heads": 4}, "head_dim": 128,
    "intermediate_size": 1024, "chunk_size": 16, "window_size": 512,
    "num_pred_heads": 8, "vocab_size": 320, "rope_theta": 100000,
    "rms_norm_eps": 1e-5, "init_std": 0.01275, "norm_add_unit_offset": True,
    "fp32_skip_add": True, "loss_chunk_rows": 2048,
    "compute_dtype": "bfloat16", "remat": True, "layout": {"fsdp": 1},
    "optimizer": {"learning_rate": 1e-4, "b1": 0.9, "b2": 0.999,
                  "eps": 1e-8, "weight_decay": 0.1}}
BATCH, SEQ = 1, 2048


def main():
    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        ROOT, "chiprun_out", "trace_fixture")
    os.makedirs(out, exist_ok=True)
    import jax
    import numpy as np

    from benchmark.families.evabyte import Family
    from benchmark.harness import scope_trace
    from ray_tpu.util import tracing

    devices = jax.devices()[:1]
    family = Family(CONFIG)
    family.bind(devices)
    params, opt_state = family.init_state(0)
    batch = family.place_batch(np.random.default_rng(0).integers(
        0, 320, (BATCH, SEQ + 1), dtype=np.int32))
    with tracing.timeline_span("train.fit", root=True) as job:
        step = family.lower_step(params, opt_state, batch).compile()
    with open(os.path.join(out, "timeline_evabyte.json"), "w") as f:
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
    name = f"{devices[0].platform}1_evabyte.xplane.pb.gz"
    with open(path, "rb") as src, gzip.open(os.path.join(out, name),
                                            "wb") as dst:
        shutil.copyfileobj(src, dst)
    found = scope_trace.reduce(scope_trace.events(path),
                               *scope_trace.vocabulary())
    with open(os.path.join(out, "trace_dump_evabyte.txt"), "w") as f:
        if found is None:           # no device plane: not a chip's trace
            found = {"busy_s": None, "named_s": None, "scopes": {}}
        print(f"busy_s {found['busy_s']!r} named_s {found['named_s']!r}",
              file=f)
        for scope, seconds in sorted(found["scopes"].items()):
            print(f"{seconds!r} {scope} {found['in_scope'].get(scope, {})}",
                  file=f)
    shutil.rmtree(raw)
    print(name, os.path.getsize(os.path.join(out, name)), "bytes", "loss",
          float(m["loss"]))


if __name__ == "__main__":
    main()

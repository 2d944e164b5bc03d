"""Records the small trace that `test_ouro_cell.py` checks the loop's readers
against.  Run on the machine with the chip (not under pytest):

    python benchmark/tests/record_trace_ouro.py [out_dir]

One process, one chip: three steps of an `ouro` step at small sizes that
keep every part of the full-size step (two layers walked three times, each
call recomputed; hidden 256; two heads of 128; feed-forward 512; an exit
gate and three heads over a vocabulary of 1,024; sequence 2,048, so the
flash kernels take their long form; batch 2), so the trace holds what the
cell's trace holds in a few hundred kilobytes.  It also writes
`trace_dump_ouro.txt`: device seconds by scope and phase, as
`tools/dump_trace_names.py` prints them.
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
    "num_hidden_layers": 2, "layer_types": ["full_attention"] * 2,
    "total_ut_steps": 3, "hidden_size": 256, "num_attention_heads": 2,
    "num_key_value_heads": 2, "head_dim": 128, "intermediate_size": 512,
    "rope_theta": 1000000, "rms_norm_eps": 1e-6, "entropy_weight": 0.05,
    "vocab_size": 1024, "padded_vocab_size": 1024,
    "published": {"num_hidden_layers": 48},
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

    from benchmark.families.ouro import Family
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
    name = f"{devices[0].platform}1_ouro.xplane.pb.gz"
    with open(path, "rb") as src, gzip.open(os.path.join(out, name),
                                            "wb") as dst:
        shutil.copyfileobj(src, dst)
    found = scope_trace.reduce(scope_trace.events(path),
                               *scope_trace.vocabulary())
    with open(os.path.join(out, "trace_dump_ouro.txt"), "w") as f:
        if found is None:           # no device plane: not a chip's trace
            found = {"busy_s": None, "named_s": None, "scopes": {}}
        print(f"busy_s {found['busy_s']!r} named_s {found['named_s']!r}",
              file=f)
        for scope, seconds in sorted(found["scopes"].items()):
            print(f"{seconds!r} {scope} {found['in_scope'].get(scope, {})}",
                  file=f)
    shutil.rmtree(raw)
    print(name, os.path.getsize(os.path.join(out, name)), "bytes", "loss",
          float(m["loss"]), "exit", [float(p) for p in m["exit"]])


if __name__ == "__main__":
    main()

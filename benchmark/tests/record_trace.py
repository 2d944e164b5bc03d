"""Records the small trace that `test_xplane.py` checks the reduction
against.  Run on the machine with the chips (not under pytest):

    python benchmark/tests/record_trace.py [out_dir]

One process, every chip: three steps of a two-layer GPT-2 (width 256, four
heads of 64, sequence 256, batch 8) under fsdp over the chips, so the trace
holds what a cell's trace holds (fusions, the flash kernels' custom calls,
collectives where there is more than one chip) in a few hundred kilobytes.
It also writes `trace_dump.txt`: planes, lines and the first events of each
with their stats, which is how the reduction's names were chosen.
"""

import glob
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        ROOT, "chiprun_out", "trace_fixture")
    os.makedirs(out, exist_ok=True)
    import jax
    import numpy as np

    from benchmark.families.gpt2 import Family
    from benchmark.harness.spans import Spans

    devices = jax.devices()
    family = Family({
        "n_layer": 2, "n_head": 4, "n_embd": 256, "n_positions": 256,
        "vocab_size": 1000, "padded_vocab_size": 1024,
        "compute_dtype": "bfloat16", "remat": False,
        "layout": {"fsdp": len(devices)},
        "optimizer": {"learning_rate": 1e-4, "b1": 0.9, "b2": 0.999,
                      "eps": 1e-8, "weight_decay": 0.1}})
    family.bind(devices)
    params, opt_state = family.init_state(0)
    batch = family.place_batch(np.random.default_rng(0).integers(
        0, 1000, (8, 257), dtype=np.int32))
    step = family.lower_step(params, opt_state, batch).compile()
    for _ in range(2):
        params, opt_state, m = step(params, opt_state, batch)
    float(m["loss"])
    spans = Spans()
    raw = os.path.join(out, "raw")
    shutil.rmtree(raw, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(raw, profiler_options=options)
    for _ in range(3):
        with spans("dispatch"):
            params, opt_state, m = step(params, opt_state, batch)
        with spans("sync"):
            float(m["loss"])
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(raw, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    name = f"{devices[0].platform}{len(devices)}.xplane.pb"
    shutil.copy(path, os.path.join(out, name))
    shutil.rmtree(raw)

    data = jax.profiler.ProfileData.from_file(os.path.join(out, name))
    with open(os.path.join(out, f"trace_dump_{len(devices)}.txt"), "w") as f:
        for plane in data.planes:
            lines = list(plane.lines)
            print("PLANE", plane.name, len(lines), file=f)
            for line in lines:
                events = list(line.events)
                print("  LINE", line.name, len(events), file=f)
                for e in events[:40]:
                    print("    ", e.name, e.start_ns, e.duration_ns,
                          dict(e.stats), file=f)
    print(name, os.path.getsize(os.path.join(out, name)), "bytes")


if __name__ == "__main__":
    main()

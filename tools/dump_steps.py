"""A job's steps as the program recorded them.

    python tools/dump_steps.py <timeline.json>
    python tools/dump_steps.py --cell keye-vl-2.0-30b-a3b-ep8.resident-8k

Prints, for each process that ran a loop, the `train.step` records of a
`timeline.json` (`ray_tpu/train/session.py`: one per interval between two
`train.report`s): their count, median, p90, p99 and longest, the share of
one core the process burnt over them, what a profiler session took, and
every `train.stall` as the worker's warning words it, with its stacks.
`--cell` reads the file the cell's last run here left under
`.scratch/benchmark/<cell>/<cell>/`.  How a builder reads an untraced run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    import numpy as np

    from ray_tpu.train.session import stall_text

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("timeline", nargs="?", help="a timeline.json")
    parser.add_argument("--cell", help="a cell of BENCHMARK.json: its last "
                                       "run here")
    args = parser.parse_args()
    if bool(args.timeline) == bool(args.cell):
        parser.error("give a timeline file or --cell, one of them")
    path = args.timeline or os.path.join(
        ROOT, ".scratch", "benchmark", args.cell, args.cell, "timeline.json")
    with open(path) as f:
        doc = json.load(f)
    named = lambda name: [r for r in doc["spans"] if r["name"] == name]
    steps = named("train.step")
    print(f"{path}: {len(steps)} train.step, counters "
          + json.dumps({k: v for k, v in doc["counters"].items()
                        if k.startswith("train.")})
          + f", dropped {doc['dropped']}")
    for pid in sorted({r["pid"] for r in steps}):
        mine = [r for r in steps if r["pid"] == pid]
        profiled = [r for r in mine if r["attributes"]["profiled"]]
        clean = [r for r in mine if not r["attributes"]["profiled"]] or mine
        took = np.array([r["duration_us"] for r in clean]) / 1e3
        median = float(np.median([r["duration_us"] for r in mine])) / 1e3
        cpu = sum(r["attributes"]["process_cpu_us"] for r in clean)
        print(f"pid {pid} rank {mine[0]['attributes']['rank']}: steps "
              f"{mine[0]['attributes']['n']}..{mine[-1]['attributes']['n']}, "
              f"{len(clean)} outside a profiler session: median "
              f"{np.median(took):.3f} ms, p90 {np.percentile(took, 90):.3f}, "
              f"p99 {np.percentile(took, 99):.3f}, max {took.max():.3f} "
              f"(step {clean[int(took.argmax())]['attributes']['n']}); "
              f"loop_cpu_share {100 * cpu / (1e3 * took.sum()):.3f} %; "
              f"{len(profiled)} inside one, "
              f"{sum(r['duration_us'] / 1e3 - median for r in profiled):.1f} "
              f"ms over the median")
    stalls = named("train.stall")
    for stall in stalls:
        print(stall_text(stall))
        for stack, seen in sorted(stall["attributes"]["stack"].items(),
                                  key=lambda kv: -kv[1]):
            print(f"    {seen} x " + "\n        ".join(stack.split(";")))
    if not stalls:
        print("no train.stall")


if __name__ == "__main__":
    main()

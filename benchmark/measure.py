"""Runs of one cell, one after another, and how widely they spread.

    python benchmark/measure.py --workload <cell> --runs 6 --sets 2 \\
        [--seconds S] [--trace-runs 1] [--first-seed N]

Each run is `benchmark/run.py` in a process of its own, every run of a set
with another seed and both sets with the same seeds, as the builder's
instructions measure a bound.  Last lines go to
`chiprun_out/<cell>.jsonl`, whole outputs to `chiprun_out/<cell>.log`; the
spread printed for each metric is the distance between the first and third
quartile (`statistics.quantiles(values, n=4)`) over the median, the wider
of the sets'.  Not part of a run: a tool for whoever sets or checks a
bound.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace-runs", type=int, default=0)
    parser.add_argument("--trace-seconds", type=float, default=None)
    parser.add_argument("--first-seed", type=int, default=2147480000)
    args = parser.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    lines = open(os.path.join(out_dir, args.workload + ".jsonl"), "a")
    log = open(os.path.join(out_dir, args.workload + ".log"), "a")

    def run(seed, trace, label):
        window = args.trace_seconds if trace and args.trace_seconds \
            else seconds
        cmd = [sys.executable, *bench["command"][1:], "--workload",
               args.workload, "--seed", str(seed), "--seconds", str(window),
               "--trace", str(trace)]
        t0 = time.time()
        proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.time() - t0
        log.write(f"=== {label} seed {seed} trace {trace} rc "
                  f"{proc.returncode} wall {wall:.1f}\n{proc.stdout}\n"
                  f"--- stderr (end)\n{proc.stderr[-6000:]}\n")
        log.flush()
        if proc.returncode:
            # the next run would fail the same way and cost as much
            sys.exit(f"{label}: rc {proc.returncode}\n{proc.stderr[-3000:]}")
        result = json.loads(proc.stdout.strip().splitlines()[-1])
        result.update(label=label, seed=seed, trace=trace, wall_s=wall)
        lines.write(json.dumps(result) + "\n")
        lines.flush()
        values = {k: v["value"] for k, v in result["metrics"].items()}
        print(f"{label} seed {seed} wall {wall:.0f}s correct "
              f"{result['correct']} {json.dumps(values)}", flush=True)
        if not result["correct"]:
            print("\n".join(l for l in proc.stdout.splitlines()
                            if l.startswith("NOT CORRECT")))
        return result

    sets = []
    for s in range(args.sets):
        got = [run(args.first_seed + i, 0, f"set{s}.run{i}")
               for i in range(args.runs)]
        sets.append(got)
    for t in range(args.trace_runs):
        result = run(args.first_seed + 100 + t, 1, f"trace{t}")
        print(json.dumps(result.get("breakdown")))
        print(json.dumps(result["device"]))
    names = sorted({k for rs in sets for r in rs for k in r["metrics"]})
    for name in names:
        report = []
        for rs in sets:
            values = [r["metrics"][name]["value"] for r in rs]
            if len(values) < 2:
                continue
            q1, _, q3 = statistics.quantiles(values, n=4)
            med = statistics.median(values)
            report.append((med, (q3 - q1) / med, min(values), max(values)))
        print(name, " | ".join(
            f"median {m:.6g} spread {s:.5f} min {lo:.6g} max {hi:.6g}"
            for m, s, lo, hi in report))


if __name__ == "__main__":
    main()

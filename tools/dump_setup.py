"""A job's set-up as the program recorded it.

    python tools/dump_setup.py <timeline.json>
    python tools/dump_setup.py --cell ling-3.0-flash-ep64.resident-16k

Prints, from a `timeline.json`, what lies between `fit()` and the loop's
first report: the spans of the start in order from `train.fit` (placement,
the worker's spawn, the import of jax, the wait for the chips and their
claim, the session's start), then for each process that ran a loop its
`train.setup` (`ray_tpu/train/session.py`): what jax's tracer, lowering,
compiler and cache took for the step and for other functions and what was
left to running, the longest traces and lowerings by function (own time),
every function that compiled with its seconds and whether the cache served
it, and the line as the worker worded it.  `--cell` reads the file the cell's last run here
left under `.scratch/benchmark/<cell>/<cell>/`.  How a builder reads where
`setup_s` went.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

START = ("train.placement", "train.workers_up", "raylet.worker_spawn",
         "train.jax_distributed_init", "train.jax_import", "train.chip_wait",
         "train.chip_claim", "train.start_session", "train.loop")
JAX = ("jax.trace", "jax.lower", "jax.backend_compile")


def main():
    from ray_tpu.train.session import setup_text

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("timeline", nargs="?", help="a timeline.json")
    parser.add_argument("--cell", help="a cell of BENCHMARK.json: its last "
                                       "run here")
    parser.add_argument("--top", type=int, default=12,
                        help="rows of the table by function")
    args = parser.parse_args()
    if bool(args.timeline) == bool(args.cell):
        parser.error("give a timeline file or --cell, one of them")
    path = args.timeline or os.path.join(
        ROOT, ".scratch", "benchmark", args.cell, args.cell, "timeline.json")
    with open(path) as f:
        doc = json.load(f)
    named = lambda *names: [r for r in doc["spans"] if r["name"] in names]
    fit = named("train.fit")[0]
    at = lambda r: (r["start_us"] - fit["start_us"]) / 1e6
    s = lambda us: f"{us / 1e6:9.3f}"
    print(f"{path}: train.fit {fit['duration_us'] / 1e6:.3f} s, counters "
          + json.dumps({k: v for k, v in doc["counters"].items()
                        if k.startswith("jax.")})
          + f", dropped {doc['dropped']}")
    print("  at s     took s  span")
    for record in named(*START):
        a = record["attributes"]
        note = ", ".join(f"{k} {a[k]}" for k in ("rank", "pid", "waited_s",
                                                 "imported") if k in a)
        print(f"{at(record):8.3f} {s(record['duration_us'])}  "
              f"{record['name']} (pid {record['pid']}{', ' if note else ''}"
              f"{note})")
    setups = named("train.setup")
    if not setups:
        print("no train.setup: a program from before it, or a loop that "
              "never reported")
    for setup in setups:
        a, pid = setup["attributes"], setup["pid"]
        print(f"{at(setup):8.3f} {s(setup['duration_us'])}  train.setup "
              f"(pid {pid}, rank {a['rank']}): the loop's start to its "
              f"first report")
        rows = [(f"{kind} of {whose}", a["own_us"][f"{kind}/{key}"])
                for key, whose in (("step", "the step"),
                                   ("other", "other functions"))
                for kind in ("trace", "lower", "compile", "cache_read")]
        rows.append(("running: no trace, lowering, compile or cache read",
                     a["run_us"]))
        for what, us in rows:
            print(f"         {s(us)}  {100 * us / setup['duration_us']:5.1f} %"
                  f"  {what}")
        print(f"         the step's executable: cache {a['step_cache']}; "
              f"the listeners themselves took {a['listen_us'] / 1e6:.3f} s")
        end = setup["start_us"] + setup["duration_us"]
        mine = [r for r in named(*JAX) if r["pid"] == pid
                and setup["start_us"] <= r["start_us"] < end
                and "own_us" in r["attributes"]]
        by_function = {}
        for record in mine:
            if record["name"] == "jax.backend_compile":
                continue
            key = (record["name"], record["attributes"].get("fun_name", ""),
                   record["attributes"]["step"])
            count, own = by_function.get(key, (0, 0))
            by_function[key] = count + 1, own + record["attributes"]["own_us"]
        print("         own time of the traces of 5 ms or more and of the "
              "lowerings, by function:")
        for (name, fun, step), (count, own) in sorted(
                by_function.items(), key=lambda kv: -kv[1][1])[:args.top]:
            print(f"         {s(own)}  {name} {fun} x {count}"
                  f"{' (the step)' if step else ''}")
        print("         every function that compiled:")
        for record in mine:
            if record["name"] != "jax.backend_compile":
                continue
            b = record["attributes"]
            if record["duration_us"] >= 100_000 or b["step"]:
                print(f"{at(record):8.3f} {s(record['duration_us'])}  "
                      f"{b.get('fun_name', '')} cache {b['cache']}"
                      f"{' (the step)' if b['step'] else ''}")
        short = [r for r in mine if r["name"] == "jax.backend_compile"
                 and r["duration_us"] < 100_000
                 and not r["attributes"]["step"]]
        if short:
            print(f"         {s(sum(r['duration_us'] for r in short))}  "
                  f"{len(short)} more under 0.1 s each")
        print(setup_text(setup))


if __name__ == "__main__":
    main()

"""Scale-envelope benchmark — the `release/benchmarks` analogue
(reference: `release/benchmarks/README.md:27-34`: 1M queued tasks, 10k
args, 1k actors on multi-node clusters).

Scaled to the current host (the reference numbers come from 64-core
multi-node fleets); every row records its own size so results are
comparable across hosts.  Writes BENCH_SCALE.json and prints one JSON
line per metric.

Run: ``python bench_scale.py [--quick]``
"""

from __future__ import annotations

import argparse
import json
import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true")
    args = parser.parse_args()
    scale = 0.1 if args.quick else 1.0

    import ray_tpu

    results = {}

    def record(name, value, unit, **extra):
        digits = 4 if unit == "s" else 1
        results[name] = {"value": round(value, digits), "unit": unit, **extra}
        print(json.dumps({"metric": name, **results[name]}), flush=True)

    ray_tpu.init(num_cpus=max(4, os.cpu_count() or 4))

    @ray_tpu.remote
    def nop():
        return b"ok"

    @ray_tpu.remote
    def many_args(*args):
        return len(args)

    ray_tpu.get([nop.remote() for _ in range(8)])

    # ---- deep queue drain: every task is queued before the first worker
    # frees, so the scheduler sees the FULL backlog on every pass (the
    # O(queue)-rescan trap this suite exists to catch).
    n = int(100_000 * scale)
    t0 = time.perf_counter()
    refs = [nop.remote() for _ in range(n)]
    t_submit = time.perf_counter() - t0
    ray_tpu.get(refs, timeout=600)
    dt = time.perf_counter() - t0
    record("queued_tasks_drain_per_s", n / dt, "tasks/s", n=n,
           submit_per_s=round(n / t_submit, 1))
    del refs

    # ---- one task with many small args
    n_args = int(10_000 * scale) or 1000
    t0 = time.perf_counter()
    assert ray_tpu.get(many_args.remote(*range(n_args)), timeout=120) \
        == n_args
    record("args_10k_task_s", time.perf_counter() - t0, "s", n_args=n_args)

    # ---- get over many distinct objects
    n_obj = int(1_000 * scale) or 200
    objs = [ray_tpu.put(np.full(64, i)) for i in range(n_obj)]
    t0 = time.perf_counter()
    out = ray_tpu.get(objs, timeout=300)
    record("get_1k_objects_s", time.perf_counter() - t0, "s", n=n_obj)
    assert int(out[-1][0]) == n_obj - 1
    del objs, out

    # ---- actor fleet: create N max_concurrency actors in few processes
    # is cheating, so these are real single-threaded actors (each a
    # process) — bounded well below the reference's 1k on a 1-vCPU host.
    n_actors = max(4, int(64 * scale))

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.v = 0

        def bump(self):
            self.v += 1
            return self.v

    t0 = time.perf_counter()
    actors = [Counter.remote() for _ in range(n_actors)]
    ray_tpu.get([a.bump.remote() for a in actors], timeout=600)
    t_create = time.perf_counter() - t0
    t0 = time.perf_counter()
    calls = [a.bump.remote() for a in actors for _ in range(10)]
    ray_tpu.get(calls, timeout=600)
    t_call = time.perf_counter() - t0
    record("actors_created_per_s", n_actors / t_create, "actors/s",
           n=n_actors)
    record("actor_fleet_calls_per_s", len(calls) / t_call, "calls/s",
           n_calls=len(calls))
    for a in actors:
        ray_tpu.kill(a)

    ray_tpu.shutdown()

    with open(os.path.join(os.path.dirname(__file__) or ".",
                           "BENCH_SCALE.json"), "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()

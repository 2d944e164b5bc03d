"""One run of one benchmark cell.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> [--rehearse]

Prints what it saw on earlier lines and, as the last line of stdout, the
JSON object the contract asks for.  Without a TPU, or with fewer chips than
the cell asks for, it exits with code 3 and prints no result.  `--rehearse`
runs the same code at the `rehearsal` sizes of the cell's files on virtual
CPU devices, to find faults where there is no chip: it prints platform=cpu,
marks its line `"rehearsal": true` and puts no metric's value on it.

This process never imports jax: the chips belong to the worker that
`JaxTrainer` starts.  Cells, configurations, traffic, loops, families and
metric readers are found by name (`harness/registry.py`).
"""

from __future__ import annotations

T_START = __import__("time").time()

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny sizes on virtual CPU devices; never a "
                             "device metric")
    args = parser.parse_args()

    # the program's session directories go under this run's TMPDIR
    os.environ.setdefault("RAY_TPU_TEMP_DIR", os.path.join(
        tempfile.gettempdir(), "ray_tpu_bench"))
    from benchmark.harness import registry, verdict
    from ray_tpu.core.worker import count_local_tpu_chips

    cell = registry.cell(args.workload)
    chips = cell["chips"]
    if not args.rehearse and count_local_tpu_chips() < chips:
        print(f"{cell['name']} needs {chips} TPU chip(s); this machine "
              f"offers {count_local_tpu_chips()}", file=sys.stderr)
        sys.exit(3)
    config = registry.config(cell["config"], args.rehearse)
    traffic = registry.traffic(cell["traffic"], args.rehearse)
    spec = {
        "cell": cell["name"], "chips": chips, "config": config,
        "traffic": traffic, "seed": args.seed, "seconds": args.seconds,
        "trace": bool(args.trace), "rehearse": args.rehearse,
        "work_dir": os.path.join(ROOT, ".scratch", "benchmark",
                                 cell["name"]),
    }
    obs = registry.loop(traffic).drive(spec)
    obs.update(t_start=T_START, chips=chips, config=config, traffic=traffic,
               family=registry.family(config))
    device = obs["device"]
    print(f"platform={device['platform']} kind={device['kind']!r} "
          f"count={device['count']}")
    # a rehearsal has no chip and so no peaks: readers that need them
    # find nothing to read
    obs["peaks"] = None if args.rehearse else registry.peaks(device["kind"])

    bad = verdict.reasons(obs, config, chips, args.rehearse,
                          "jax" in sys.modules)
    for line in describe(obs):
        print(line)
    for reason in bad:
        print("NOT CORRECT:", reason)

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for entry in registry.metrics_of(cell["name"], kind):
        value = registry.metric(entry["name"]).read(obs)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    result = {
        "correct": not bad,
        "attempted": obs["window_steps"],
        "failed": obs["window_nonfinite"],
    }
    if args.rehearse:
        # which readers found something to read, never what they read
        result.update(rehearsal=True, metrics={}, read=sorted(metrics),
                      device=device)
    else:
        result.update(metrics=metrics, device=dict(
            device, memory_peak_bytes=obs["memory_peak_bytes"]))
        trace = obs.get("trace")
        if trace:
            result["device"].update(busy_s=trace["busy_s"],
                                    window_s=trace["window_s"])
            result["breakdown"] = {"device_ops": trace["device_ops"],
                                   "idle_gaps": trace["idle_gaps"]}
    print(json.dumps(result))


def describe(obs) -> list:
    """The earlier lines: what the numbers on the last line stand on."""
    from numpy import median

    steps = obs["step_intervals_s"]
    lines = [
        f"losses of the first steps: system {obs['losses_first']} "
        f"reference {obs['reference_losses']} (float32, "
        f"{obs['reference_s']:.1f} s)",
        f"window: {obs['window_steps']} steps in {obs['window_s']:.3f} s "
        f"after {obs['warmup_steps']} warm-up steps; loss "
        f"{obs['loss_open']} -> {obs['loss_close']}",
        f"step intervals: n={len(steps)} median "
        f"{1e3 * median(steps) if steps else float('nan'):.3f} ms",
        f"spans per step (median ms, n): " + ", ".join(
            f"{name} {1e3 * median(d):.3f} ({len(d)})"
            for name, d in sorted(obs["spans"].items())),
        f"set-up: spawn {obs['t_enter'] - obs['t_fit']:.1f} s, reference "
        f"{obs['reference_s']:.1f} s, init {obs['init_s']:.1f} s, lower + "
        f"compile {obs['lower_compile_s']:.1f} s (served from cache: "
        f"{obs['step_served_from_cache']}; cache at "
        f"{obs['compile_cache_dir']})",
        f"peak bytes of live arrays per device: after the reference "
        f"{obs['reference_peak_bytes']}, after the window "
        f"{obs['peak_bytes']}; the step's scratch space "
        f"{obs['step_temp_bytes']}",
        f"train.report calls seen by the trainer: {obs['reports_seen']}",
    ]
    trace = obs.get("trace")
    if trace:
        lines.append(
            "trace: {steps} steps on {devices} device(s), window "
            "{window_s:.4f} s, busy {busy_s:.4f} s, collectives "
            "{collective_s:.4f} s ({collective_exposed_s:.4f} s exposed), "
            "attention kernels {kernel_s:.4f} s {kernels}".format(**trace))
    return lines


if __name__ == "__main__":
    main()

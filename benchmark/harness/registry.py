"""Everything the harness finds by name.

`BENCHMARK.json` names cells, configurations and metrics; their files are
looked up here by those names and nowhere listed in code, so that a later
PR adds a configuration, a traffic mix, a loop, a family or a metric as
new files and new entries, and edits nothing that is there.
"""

from __future__ import annotations

import functools
import importlib
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def benchmark() -> dict:
    return load_json("BENCHMARK.json")


def cell(name: str) -> dict:
    for entry in benchmark()["workloads"]:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"BENCHMARK.json has no workload {name!r}")


def with_rehearsal(sizes: dict, rehearse: bool) -> dict:
    """A configuration or traffic file as it is run: whole, or, for a
    rehearsal on the CPU, with its `rehearsal` sizes laid over it."""
    sizes = dict(sizes)
    tiny = sizes.pop("rehearsal", {})
    if rehearse:
        for key, value in tiny.items():
            if isinstance(value, dict):
                sizes[key] = {**sizes.get(key, {}), **value}
            else:
                sizes[key] = value
    return sizes


def config(name: str, rehearse: bool = False) -> dict:
    for entry in benchmark()["configs"]:
        if entry["name"] == name:
            return with_rehearsal(load_json(entry["file"]), rehearse)
    raise SystemExit(f"BENCHMARK.json has no configuration {name!r}")


def traffic(name: str, rehearse: bool = False) -> dict:
    return with_rehearsal(
        load_json("benchmark", "traffic", f"{name}.json"), rehearse)


def family(config: dict):
    """The adapter a configuration's `family` names."""
    module = importlib.import_module(
        f"benchmark.families.{config['family']}")
    return module.Family(config)


def loop(traffic: dict):
    """The module a traffic file's `loop` names."""
    return importlib.import_module(f"benchmark.loops.{traffic['loop']}")


def metric(name: str):
    """The module `benchmark/metrics/<name>.py`, whose `read(obs)` gives
    the metric or None; names may hold dots, so the file is loaded by its
    path."""
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of(cell_name: str, kind: str) -> list:
    """The entries of `end_to_end` or `per_layer` that this cell reports."""
    return [m for m in benchmark()[kind]
            if cell_name in m.get("workloads", [cell_name])]


def peaks(device_kind: str) -> dict:
    table = load_json("benchmark", "harness", "peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(f"no peaks on record for device kind "
                         f"{device_kind!r}: add it to "
                         f"benchmark/harness/peaks.json with its source")
    return table[device_kind]

"""The job's own timeline, as the program wrote it.

Every `JaxTrainer.fit` leaves `<run directory>/timeline.json`: the spans
and counters that `ray_tpu` records of itself (`ray_tpu/util/tracing.py`,
"job timeline": `train.fit` at the root; beneath it placement, worker
start, chip claim, JAX's trace / lower / compile, `train.report`, Data's
block waits).  The metric readers that stand on it find it here.  A
program that writes no such file (the parent of the PR that brought it),
a file left by another run, or a span that is not there: the reader finds
nothing and returns None.
"""

from __future__ import annotations

import json
import os

from benchmark.harness import registry, xplane


def run_dir(obs: dict) -> str:
    """The run directory of the cell whose configuration and traffic `obs`
    carries (`run.py` puts a cell's job under `.scratch/benchmark/<cell>`
    and names the job after the cell)."""
    config, traffic = obs["config"]["name"], obs["traffic"]["name"]
    for cell in registry.benchmark()["workloads"]:
        if (cell["config"], cell["traffic"]) == (config, traffic):
            return os.path.join(registry.ROOT, ".scratch", "benchmark",
                                cell["name"], cell["name"])
    raise SystemExit(f"BENCHMARK.json has no workload of {config!r} under "
                     f"{traffic!r}")


class Timeline:
    def __init__(self, doc: dict, obs: dict):
        self.spans = {}                 # name -> records, by start time
        for record in doc["spans"]:
            self.spans.setdefault(record["name"], []).append(record)
        self.counters = doc.get("counters", {})
        self.dropped = doc.get("dropped", 0)
        # the measured window on the wall clock, in microseconds
        self.open_us = 1e6 * obs["t_open"]
        self.close_us = 1e6 * (obs["t_open"] + obs["window_s"])

    def named(self, *names: str) -> list:
        return [r for name in names for r in self.spans.get(name, ())]

    def in_window(self, name: str) -> list:
        """The spans of this name that ended inside the window."""
        return [r for r in self.spans.get(name, ())
                if self.open_us < end_us(r) <= self.close_us]

    def loop_pid(self):
        """The process that ran `train.loop` (rank 0's)."""
        loops = self.spans.get("train.loop")
        return loops[0]["pid"] if loops else None

    def set_up(self, *names: str) -> list:
        """The spans of these names that the loop's process ended before
        the window opened."""
        pid = self.loop_pid()
        return [r for r in self.named(*names)
                if r["pid"] == pid and end_us(r) <= self.open_us]


def end_us(record: dict) -> int:
    return record["start_us"] + record["duration_us"]


def covered_s(records: list) -> float:
    """Seconds covered by at least one of the spans: jax reports a
    function traced inside another's trace, and a cache read inside its
    compile, as spans of their own, so a plain sum would count those
    seconds twice."""
    return xplane.total(xplane.union(
        (r["start_us"], end_us(r)) for r in records)) / 1e6


def of(obs: dict):
    """This run's timeline, or None: no file, or a file whose `train.fit`
    did not start within a second after this run called `fit()`."""
    try:
        with open(os.path.join(run_dir(obs), "timeline.json")) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    fits = [r for r in doc.get("spans", ()) if r["name"] == "train.fit"]
    if not fits or not 0 <= fits[0]["start_us"] / 1e6 - obs["t_fit"] <= 1:
        return None
    return Timeline(doc, obs)


def read(obs: dict, value):
    """A reader's `read(obs)`: nothing in a rehearsal (no chip, so never a
    metric's value) and nothing without this run's timeline, else
    `value(timeline)`."""
    if obs["peaks"] is None:
        return None
    timeline = of(obs)
    return None if timeline is None else value(timeline)

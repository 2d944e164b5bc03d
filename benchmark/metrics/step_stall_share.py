"""Train: of the window's step time as the program records it (`train.step`:
one record per `train.report`-to-`train.report` interval of the loop's
process), the share that overran: the sum of d - m over the steps with
d > 1.5 m and d - m >= 50 ms, m the median of the window's steps, over the
sum of d.  Steps that held a profiler session (`profiled`) are the
measurement's own pause and are left out; `profiler_pause_ms` has them.
0 in a clean run; a window that loses 3 s of 35 reads 8.6."""

import numpy as np

from benchmark.harness import timeline

FACTOR, OVER_US = 1.5, 50_000


def steps(tl):
    """(the not-`profiled` steps, the `profiled` ones, the median duration
    in us of both together) of the `train.step` records that the loop's
    process ended inside the window; None where there is none."""
    pid = tl.loop_pid()
    records = [r for r in tl.in_window("train.step") if r["pid"] == pid]
    if not records:
        return None
    median = float(np.median([r["duration_us"] for r in records]))
    profiled = [r for r in records if r["attributes"]["profiled"]]
    clean = [r for r in records if not r["attributes"]["profiled"]]
    return clean, profiled, median


def value(tl):
    found = steps(tl)
    if found is None or not found[0]:
        return None
    clean, _, m = found
    over = sum(r["duration_us"] - m for r in clean
               if r["duration_us"] > FACTOR * m
               and r["duration_us"] - m >= OVER_US)
    return 100.0 * over / sum(r["duration_us"] for r in clean)


def read(obs):
    return timeline.read(obs, value)

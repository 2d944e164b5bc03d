"""A traced run's routed-expert operations, from the run's own trace.

The loop's reduction (`harness/xplane.py:reduce`, in `obs["trace"]`) keeps
the ten longest operations and the attention kernels; the readers of the
mixture-of-experts metrics need every operation, so they go back to the
`.xplane.pb` the run left beside its timeline (`<work dir>/trace`, found as
`harness/timeline.py:run_dir` finds the run directory) and reduce it with
the family's own predicates.  A family without routed experts, a run that
was not traced, a rehearsal, or a trace left by another run: nothing to
read, and the reader returns None.
"""

from __future__ import annotations

import functools
import os

from benchmark.harness import timeline, xplane


def trace_path(obs: dict):
    """This run's `.xplane.pb`, or None."""
    if not obs.get("trace") or obs.get("peaks") is None:
        return None
    trace_dir = os.path.join(os.path.dirname(timeline.run_dir(obs)), "trace")
    try:
        path = xplane.newest_trace(trace_dir)
    except FileNotFoundError:
        return None
    # written after this run called fit()
    return path if os.path.getmtime(path) >= obs["t_fit"] else None


def reduce(planes, is_moe_op, is_moe_matmul) -> dict:
    """Seconds per device (means over the devices that ran operations):
    busy, in routed-expert operations, in the grouped matmuls among them;
    and the steps traced."""
    busy = moe = matmul = 0.0
    steps = devices = 0
    for name, lines in planes:
        if not xplane.DEVICE_PLANE.match(name):
            continue
        lines = dict(lines)
        segments = xplane.leaves(lines.get(xplane.OP_LINE, []))
        if not segments:
            continue
        devices += 1
        steps = max(steps, len(lines.get(xplane.MODULE_LINE, [])))
        busy += xplane.total(xplane.union((s, e) for _, s, e in segments))
        for op, start, end in segments:
            if is_moe_op(op):
                moe += end - start
                if is_moe_matmul(op):
                    matmul += end - start
    if not devices:
        return None
    ns = 1e-9 / devices
    return {"steps": steps, "busy_s": busy * ns, "moe_s": moe * ns,
            "moe_matmul_s": matmul * ns}


@functools.lru_cache(maxsize=4)
def _reduce_file(path: str, mtime: float, family, tokens: int):
    return reduce(xplane.load(path),
                  functools.partial(family.is_moe_op, tokens=tokens),
                  family.is_moe_matmul)


def of(obs: dict):
    """{"steps", "busy_s", "moe_s", "moe_matmul_s"} of this run's trace,
    or None."""
    family = obs["family"]
    if not hasattr(family, "is_moe_op"):
        return None
    path = trace_path(obs)
    if path is None:
        return None
    tokens = obs["traffic"]["batch"] * obs["traffic"]["seq"]
    found = _reduce_file(path, os.path.getmtime(path), family, tokens)
    return found if found and found["busy_s"] and found["moe_s"] else None

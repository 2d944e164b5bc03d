"""A traced run's conv-operator operations (the gated short convolutions of
an LFM2-shaped model), from the run's own trace: the same re-reduction of
the run's `.xplane.pb` as `harness/moe_trace.py` makes for the routed
experts, with the family's `is_shortconv_op`.  A family without such an
operator, a run that was not traced, a rehearsal, or a trace left by
another run: nothing to read, and the reader returns None.
"""

from __future__ import annotations

import functools
import os

from benchmark.harness import moe_trace, xplane


@functools.lru_cache(maxsize=4)
def _reduce_file(path: str, mtime: float, family):
    return moe_trace.reduce(xplane.load(path), family.is_shortconv_op,
                            lambda op: False)


def of(obs: dict):
    """{"steps", "busy_s", "shortconv_s"} of this run's trace, or None."""
    family = obs["family"]
    if not hasattr(family, "is_shortconv_op"):
        return None
    path = moe_trace.trace_path(obs)
    if path is None:
        return None
    found = _reduce_file(path, os.path.getmtime(path), family)
    if not found or not found["busy_s"] or not found["moe_s"]:
        return None
    return {"steps": found["steps"], "busy_s": found["busy_s"],
            "shortconv_s": found["moe_s"]}

"""A traced run's latent-attention operations outside the kernels, from the
run's own trace: the same re-reduction of the run's `.xplane.pb` as
`harness/moe_trace.py` makes for the routed experts, with the family's
`is_mla_op`.  A family without latent attention, a run that was not
traced, a rehearsal, or a trace left by another run: nothing to read, and
the reader returns None.
"""

from __future__ import annotations

import functools
import os

from benchmark.harness import moe_trace, xplane


@functools.lru_cache(maxsize=4)
def _reduce_file(path: str, mtime: float, family, tokens: int):
    return moe_trace.reduce(
        xplane.load(path), functools.partial(family.is_mla_op, tokens=tokens),
        lambda op: False)


def of(obs: dict):
    """{"steps", "busy_s", "mla_s"} of this run's trace, or None."""
    family = obs["family"]
    if not hasattr(family, "is_mla_op"):
        return None
    path = moe_trace.trace_path(obs)
    if path is None:
        return None
    tokens = obs["traffic"]["batch"] * obs["traffic"]["seq"]
    found = _reduce_file(path, os.path.getmtime(path), family, tokens)
    if not found or not found["busy_s"] or not found["moe_s"]:
        return None
    return {"steps": found["steps"], "busy_s": found["busy_s"],
            "mla_s": found["moe_s"]}

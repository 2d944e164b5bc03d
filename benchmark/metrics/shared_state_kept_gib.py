"""Model: what the layers hand on to later layers (a scan's result, a
layer's keys and values), held on one chip from its maker's pass to its last
reader's backward whatever the recomputed stack's budget decides: the
timeline's counter `shared.bytes_kept` (`ray_tpu/models/layers.py:trunk`,
counted as the step is traced), in GiB.  A program that counts no such
thing (the parent of the PR that brought it): nothing to read."""

from benchmark.harness import timeline


def value(tl):
    kept = tl.counters.get("shared.bytes_kept")
    return None if kept is None else kept / 2 ** 30


def read(obs):
    return timeline.read(obs, value)

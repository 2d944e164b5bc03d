"""Model: device time under the scope `eva/summary` (the pooling of k and v
into a summary a chunk, `ray_tpu/ops/eva.py:_pool`: its forward kernel, a
replay's where the summaries were not kept, the backward kernel and the sums
of d phi and d mu behind it) over device busy time, from the run's trace
(`harness/scope_trace.py`).  None for a family without EVA layers, and for a
program whose vocabulary has no such scope."""

from benchmark.harness import scope_trace

SCOPE = "eva/summary"


def read(obs):
    scopes, _ = scope_trace.vocabulary()
    if not hasattr(obs["family"], "eva_summary_cost") \
            or SCOPE not in (scopes or ()):
        return None
    return scope_trace.share(obs, SCOPE)

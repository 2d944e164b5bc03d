"""Model: device time of everything under the scope `eva` (an EVA mixer
whole: its three projections with RoPE, the chunk summaries, the flash
kernels of the local half, the remote half's kernels, the merge, W_o; forward,
replay and backward) over device busy time, from the run's trace
(`harness/scope_trace.py`).  None for a family without EVA layers, and for a
program whose vocabulary has no such scope."""

from benchmark.harness import scope_trace

SCOPE = "eva"


def read(obs):
    scopes, _ = scope_trace.vocabulary()
    if not hasattr(obs["family"], "eva_summary_cost") \
            or SCOPE not in (scopes or ()):
        return None
    return scope_trace.share(obs, SCOPE)

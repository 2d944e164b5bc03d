"""Model: device time of everything under the scope `kda` (a Kimi Delta
Attention mixer whole: its projections, the convolution, the L2 norms and the
gates' maps, the rule, the head's norm and output gate, W_o; forward, replay
and backward) over device busy time, from the run's trace
(`harness/scope_trace.py`).  None for a family without delta-rule layers, and
for a program whose vocabulary has no such scope."""

from benchmark.harness import scope_trace

SCOPE = "kda"


def read(obs):
    scopes, _ = scope_trace.vocabulary()
    if not hasattr(obs["family"], "kda_cost") or SCOPE not in (scopes or ()):
        return None
    return scope_trace.share(obs, SCOPE)

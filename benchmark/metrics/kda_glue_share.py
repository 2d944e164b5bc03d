"""Model: device time under the scope `kda` and under none of `kda/proj`,
`kda/rule` and `kda/out_proj` (what lies between a delta-rule mixer's matmuls
and its rule: the causal convolution with its SiLU, the L2 norms, the decay's
and beta's maps, the head's norm and the output gate) over device busy time,
from the run's trace (`harness/scope_trace.py`).  None for a family without
delta-rule layers, and for a program whose vocabulary has no such scope."""

from benchmark.harness import scope_trace

SCOPE = "kda"
APART = ("kda/proj", "kda/rule", "kda/out_proj")


def read(obs):
    scopes, _ = scope_trace.vocabulary()
    if not hasattr(obs["family"], "kda_cost") or SCOPE not in (scopes or ()):
        return None
    whole = scope_trace.share(obs, SCOPE)
    if whole is None:
        return None
    return whole - sum(scope_trace.share(obs, scope) for scope in APART)

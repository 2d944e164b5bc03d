"""Model: device time under the scope `gmu` (a gated memory unit's mixer:
W_1, the gate over the handed-on scan result, W_2; forward, replay and
backward) over device busy time, from the run's trace
(`harness/scope_trace.py`).  None for a family without such a layer, and
for a program whose vocabulary has no such scope."""

from benchmark.harness import scope_trace

SCOPE = "gmu"


def read(obs):
    scopes, _ = scope_trace.vocabulary()
    if not hasattr(obs["family"], "shared_bytes") \
            or SCOPE not in (scopes or ()):
        return None
    return scope_trace.share(obs, SCOPE)

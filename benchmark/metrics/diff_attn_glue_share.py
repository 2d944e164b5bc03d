"""Model: device time under the scope `attention/diff` (what differential
attention adds behind its two kernel calls: lambda, the subtraction, the
RMSNorm over a pair's 2 d, the gain and the scale; forward, replay and
backward) over device busy time, from the run's trace
(`harness/scope_trace.py`).  None for a family without differential
attention, and for a program whose vocabulary has no such scope."""

from benchmark.harness import scope_trace

SCOPE = "attention/diff"


def read(obs):
    scopes, _ = scope_trace.vocabulary()
    if not hasattr(obs["family"], "lambda_init") \
            or SCOPE not in (scopes or ()):
        return None
    return scope_trace.share(obs, SCOPE)

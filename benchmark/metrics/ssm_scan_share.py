"""Model: device time under the scope `ssm/scan` (the softplus of dt and
the chunked state-space scan, forward, replay and backward) over device
busy time, from the run's trace (`harness/scope_trace.py`).  None for a
family without state-space layers."""

from benchmark.harness import scope_trace


def read(obs):
    if not hasattr(obs["family"], "ssd_cost"):
        return None
    return scope_trace.share(obs, "ssm/scan")

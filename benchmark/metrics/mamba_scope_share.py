"""Model: device time of everything under the scope `mamba` (a Mamba-1
mixer whole: W_in, the convolution, W_x and W_dt, the scan, the gate, W_out;
forward, replay and backward) over device busy time, from the run's trace
(`harness/scope_trace.py`).  None for a family without Mamba-1 layers, and
for a program whose vocabulary has no such scope."""

from benchmark.harness import scope_trace

SCOPE = "mamba"


def read(obs):
    scopes, _ = scope_trace.vocabulary()
    if not hasattr(obs["family"], "selective_scan_cost") \
            or SCOPE not in (scopes or ()):
        return None
    return scope_trace.share(obs, SCOPE)

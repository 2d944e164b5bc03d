"""Model: device time of everything under the scope `ssm` (a Mamba-2 mixer
whole: W_in, the convolution, the scan, the gated norm, W_out; forward,
replay and backward) over device busy time, from the run's trace
(`harness/scope_trace.py`).  None for a family without state-space
layers."""

from benchmark.harness import scope_trace


def read(obs):
    if not hasattr(obs["family"], "ssd_cost"):
        return None
    return scope_trace.share(obs, "ssm")

"""Model: device time under the scopes `ssm/conv` and `ssm/gate_norm` (what
lies between a Mamba-2 mixer's matmuls and its scan: the causal convolution
with its SiLU, the gate and the grouped norm) over device busy time, from
the run's trace (`harness/scope_trace.py`).  None for a family without
state-space layers."""

from benchmark.harness import scope_trace


def read(obs):
    if not hasattr(obs["family"], "ssd_cost"):
        return None
    conv = scope_trace.share(obs, "ssm/conv")
    norm = scope_trace.share(obs, "ssm/gate_norm")
    return None if conv is None or norm is None else conv + norm

"""Model: device time under the scope `mamba/scan` (the softplus of dt and
the Mamba-1 selective scan, `ray_tpu/ops/selective_scan.py`: forward, a
recomputed layer's replay and backward) over device busy time, from the
run's trace (`harness/scope_trace.py`).  None for a family without Mamba-1
layers, and for a program whose vocabulary has no such scope."""

from benchmark.harness import scope_trace

SCOPE = "mamba/scan"


def read(obs):
    scopes, _ = scope_trace.vocabulary()
    if not hasattr(obs["family"], "selective_scan_cost") \
            or SCOPE not in (scopes or ()):
        return None
    return scope_trace.share(obs, SCOPE)

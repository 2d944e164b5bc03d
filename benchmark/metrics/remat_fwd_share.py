"""Model: device time of the recomputed forward (`rematted_computation` in
the `tf_op`: a checkpointed layer's replay, and the chunked loss's logits
made again) over device busy time, from the run's trace
(`harness/scope_trace.py`)."""

from benchmark.harness import scope_trace


def read(obs):
    return scope_trace.phase_share(obs, "remat_fwd")

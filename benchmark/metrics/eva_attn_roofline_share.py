"""Kernels: the least time one chip could take for a step's REMOTE halves of
EVA attention and their merge (the family's `eva_remote_cost`: the operations
over the (query, summary) pairs attended at the bf16 peak, or its bytes at the
HBM peak if that is more) over the device time under the scopes `eva/remote`
and `eva/merge`, from the run's trace (`harness/scope_trace.py`).

EVA runs as two forward calls merged by their row statistics
(`ray_tpu/ops/eva.py`).  The LOCAL half is the flash kernels under
`BlockRule(aligned=window)`, which `attn_kernel_share` and
`attn_roofline_share` read (the family's `is_attention_kernel` and
`attention_cost` are of them: the windows' triangles).  This metric reads the
other source: the remote kernel pair, forward and backward, and the merge of
the two results (with delta and the sum of the two dq's, which stand under
`eva/merge` too).  Operations over attended pairs of BOTH sources are the sum
of the two costs; their times are `attn_kernel_share`'s and these scopes'."""

from benchmark.harness import registry

SCOPES = ("eva/remote", "eva/merge")


def read(obs):
    return registry.metric("eva_summary_roofline_share").read(
        obs, SCOPES, "eva_remote_cost")

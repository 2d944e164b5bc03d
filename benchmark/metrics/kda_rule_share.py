"""Model: device time under the scope `kda/rule` (the gated delta rule itself,
`ray_tpu/ops/kda.py`: its kernels, forward, the backward's two, and what XLA
does around them: beta k and beta v and their cotangents taken apart; or the
plain chunked form where a shape is declined) over device busy time, from the
run's trace (`harness/scope_trace.py`).  None for a family without delta-rule
layers, and for a program whose vocabulary has no such scope."""

from benchmark.harness import scope_trace

SCOPE = "kda/rule"


def read(obs):
    scopes, _ = scope_trace.vocabulary()
    if not hasattr(obs["family"], "kda_cost") or SCOPE not in (scopes or ()):
        return None
    return scope_trace.share(obs, SCOPE)

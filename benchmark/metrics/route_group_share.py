"""Model: device time under the scope `ffn/moe/route` of a router that picks
in groups (`ray_tpu/ops/moe.py:sigmoid_route` with `n_group` > 1: the scores
over all the experts, a top-2 a group, the groups' top-k, the mask, the top-k
among them; forward, replay and backward) over device busy time, from the
run's trace (`harness/scope_trace.py`).  None for a family whose router picks
in one group (one that states no `n_group` above 1)."""

from benchmark.harness import scope_trace

SCOPE = "ffn/moe/route"


def read(obs):
    if obs["family"].config.get("n_group", 1) <= 1:
        return None
    return scope_trace.share(obs, SCOPE)

"""Model: device time under the scope `attention/gate` (a gated attention's
product of the layer's normed input with W_g, its sigmoid and the multiply
over the kernels' (B, S, H, D) result, before W_o: `ray_tpu/models/
layers.py:attention_out`; forward, a recomputed layer's replay and
backward) over device busy time, from the run's trace
(`harness/scope_trace.py`).  None for a family without a gate, and for a
program whose vocabulary has no such scope."""

from benchmark.harness import scope_trace

SCOPE = "attention/gate"


def read(obs):
    scopes, _ = scope_trace.vocabulary()
    if not obs["config"].get("gating") or SCOPE not in (scopes or ()):
        return None
    return scope_trace.share(obs, SCOPE)

"""Model: device time under the scope `diffusion` (everything block
diffusion adds to a step outside the kernels, the trunk's products and the
head: the draw of the step's noise, the masking of the tokens, the two
kinds of row laid side by side and taken apart, the rows' weights; forward
and backward) over device busy time, from the run's trace
(`harness/scope_trace.py`).  None for a family that trains on no noised
copy, and for a program whose vocabulary has no such scope."""

from benchmark.harness import scope_trace


def read(obs):
    scopes, _ = scope_trace.vocabulary()
    if not hasattr(obs["family"], "attended_pairs") or "diffusion" not in (
            scopes or ()):
        return None
    return scope_trace.share(obs, "diffusion")

"""Kernels: device time of the windowed layers' attention kernels over
device time of all attention kernels, from the run's trace by the kernels'
own names (`harness/scope_trace.py`: the forms `fwd_rows_window` and
`bwd_fused_window` under `attention/kernel`, which
`ops/flash_attention.py:_form` gives a kernel under a rule with a window,
over every form under `attention/kernel`; the transposes and the groups'
sums around the kernels, which stand under `attention/kernel` itself, are
in neither).  By the counts 27 % at 16,384
tokens under a window of 1,024 in three layers of four, if both kinds ran
at one share of their roofline: what it reads above that is what the
window's crossed tiles cost.  None for a family without windowed layers,
and for a program whose vocabulary has no such forms."""

from benchmark.harness import scope_trace

KERNELS = "attention/kernel"
WINDOWED = tuple(f"{KERNELS}/{form}"
                 for form in ("fwd_rows_window", "bwd_fused_window"))


def read(obs):
    scopes, _ = scope_trace.vocabulary()
    if not hasattr(obs["family"], "attended_pairs_a_pass") \
            or not set(WINDOWED) <= set(scopes or ()):
        return None
    found = scope_trace.of(obs)
    if found is None:
        return None
    forms = {scope: seconds for scope, seconds in found["scopes"].items()
             if scope.startswith(KERNELS + "/")}
    if not sum(forms.values()):
        return None
    return 100.0 * sum(forms.get(scope, 0.0) for scope in WINDOWED) \
        / sum(forms.values())

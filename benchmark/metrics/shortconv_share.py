"""Model: device time of the conv operators (the gated short convolutions)
as far as the family's `is_shortconv_op` can tell them from the rest by
shape (W_in's product, forward and recomputed, its weight gradient with
AdamW's update, the taps' gradient; not the operator's (B, S, E) results:
W_out, the gates and taps, W_in's gradient towards u) over device busy
time, from the run's trace."""

from benchmark.harness import shortconv_trace


def read(obs):
    found = shortconv_trace.of(obs)
    return None if found is None else \
        100.0 * found["shortconv_s"] / found["busy_s"]

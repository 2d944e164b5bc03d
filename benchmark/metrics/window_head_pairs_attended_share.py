"""Kernels: of the (query, key, head) products the WINDOWED layers' attention
kernels multiply at the tiles they take, the share the model attends there,
each layer weighted by its own query heads: the family's
`attended_head_pairs_a_pass` over its sliding layers (a window's W latest
keys a row, times the layer's heads) over what those layers' kernels visit,
from the timeline's counters (counted once a kernel as the step is traced,
and a recomputed stack traces a kernel a SHAPE of layer, not a layer, so a
windowed layer visits the mean of `attention.window_pairs_visited` over the
`attention.window_kernels`, forward and backward alike, a head).  What the
tile costs under a window narrower than a pair of tiles: at W = 512 and
16,384 tokens 512-tiles read 50 (a q tile visits two k tiles, both crossed
by both bounds), 256-tiles 67, 128-tiles 80.  A family whose layers have one
head count, or a program that does not count the window's pairs: nothing to
read."""

from benchmark.harness import timeline

SLIDING = "sliding_attention"


def read(obs):
    family = obs["family"]
    if not hasattr(family, "attended_head_pairs_a_pass"):
        return None
    attended = family.attended_head_pairs_a_pass(obs["traffic"]["seq"],
                                                 kinds=(SLIDING,))

    def value(tl):
        kernels = tl.counters.get("attention.window_kernels", 0)
        pairs = tl.counters.get("attention.window_pairs_visited", 0)
        if not kernels or not pairs or not attended:
            return None
        visited = pairs / kernels * sum(
            heads for kind, heads in zip(family.layer_types, family.heads)
            if kind == SLIDING)
        return 100.0 * attended / visited

    return timeline.read(obs, value)

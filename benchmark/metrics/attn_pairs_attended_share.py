"""Kernels: of the (query, key) pairs the attention kernels multiply, the
share the model's rule attends: the family's `attended_pairs` a sequence
and head, times the kernels the step holds, over the timeline's
`attention.pairs_visited` (the tiles each kernel visits x block_q x
block_k, a head and sequence, counted once a kernel as the step is traced;
the kernels are counted by `attention.q_heads`, the family's heads each).
At least 88 for a kernel of 512-tiles that visits only what a block rule
over clean and noised rows leaves, 25 for one that visits the square.  A
family without such a rule, or a program that does not count the pairs
(the parent of the PR that brought the counter): nothing to read."""

from benchmark.harness import timeline


def read(obs):
    family = obs["family"]
    if not hasattr(family, "attended_pairs"):
        return None
    attended = family.attended_pairs(obs["traffic"]["seq"])

    def value(tl):
        visited = tl.counters.get("attention.pairs_visited")
        if not visited:
            return None
        kernels = tl.counters.get("attention.q_heads", 0) / family.n_head
        return 100.0 * attended * kernels / visited

    return timeline.read(obs, value)

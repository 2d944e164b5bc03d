"""Kernels: of the (query, key) pairs the attention kernels multiply, the
share the model's layers attend, each layer by its own kind (a window of
the latest keys, or every earlier key): the family's
`attended_pairs_a_pass`, a sequence and head over one pass of the stack,
over what a pass of the stack's kernels visits, from the timeline's
counters (counted once a kernel as the step is traced, and a recomputed
stack traces a kernel a KIND of layer, not a layer): a windowed layer
visits the mean of `attention.window_pairs_visited` over the
`attention.window_kernels`, a full layer the mean of the other kernels'
`attention.pairs_visited` (the kernels are counted by `attention.q_heads`,
the family's heads each); a program whose kernels know no window visits in
every layer what its kernels visit.  With 512-tiles everywhere 86.5 at
16,384 tokens under a window of 1,024 in three layers of four; 33 for
kernels that know the diagonal alone.  A family whose layers are of one
kind, or a program that does not count the pairs: nothing to read."""

from benchmark.harness import timeline


def read(obs):
    family = obs["family"]
    if not hasattr(family, "attended_pairs_a_pass"):
        return None
    attended = family.attended_pairs_a_pass(obs["traffic"]["seq"])

    def value(tl):
        count = lambda name: tl.counters.get(f"attention.{name}", 0)
        kernels = count("q_heads") / family.n_head
        windowed = count("window_kernels")
        if not count("pairs_visited") or not kernels:
            return None
        under = count("window_pairs_visited") / windowed if windowed else None
        beside = (count("pairs_visited") - count("window_pairs_visited")) \
            / (kernels - windowed) if kernels > windowed else under
        visited = sum(
            under if under and kind == "sliding_attention" else beside
            for kind in family.layer_types)
        return 100.0 * attended / visited

    return timeline.read(obs, value)

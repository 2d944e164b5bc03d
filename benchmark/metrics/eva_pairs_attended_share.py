"""Kernels: of the (query, key) and (query, summary) pairs the EVA forward
kernels multiply, the share the model's rule attends: the timeline's
`eva.pairs_attended` (|A_i| + |B_i| summed over a sequence's queries, a head)
over `eva.pairs_visited` (the local tiles `BlockRule(aligned=window)` visits,
whole, and the remote pairs, every one of which is attended), both counted
once a layer as the step is traced (`ray_tpu/ops/eva.py:eva_attention`).  85.2
at S = 16,384 with tiles of 512 in windows of 2,048: a window's triangle is
0.80 of its ten tiles, the remote tiles are whole.  A program that counts
neither (the parent of the PR that brought them): nothing to read."""

from benchmark.harness import timeline


def value(tl):
    visited = tl.counters.get("eva.pairs_visited")
    if not visited:
        return None
    return 100.0 * tl.counters.get("eva.pairs_attended", 0) / visited


def read(obs):
    return timeline.read(obs, value)

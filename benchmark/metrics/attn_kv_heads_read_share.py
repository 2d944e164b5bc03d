"""Kernels: of the heads the attention kernels are given queries for, the
share they read keys and values for from HBM (timeline counters
`attention.kv_heads` over `attention.q_heads`, counted once a kernel as the
step is traced): 25 where four query heads share a key/value head and the
kernels read it through their index maps, 100 where k and v have q's heads
(multi-head attention, or grouped queries repeated before the call).  A
program that counts neither (the parent of the PR that brought them):
nothing to read."""

from benchmark.harness import timeline


def value(tl):
    q_heads = tl.counters.get("attention.q_heads")
    if not q_heads:
        return None
    return 100.0 * tl.counters.get("attention.kv_heads", 0) / q_heads


def read(obs):
    return timeline.read(obs, value)

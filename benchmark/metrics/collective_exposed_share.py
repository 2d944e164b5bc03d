"""Parallel: share of the traced window in which a collective runs on a
device and nothing computes there."""


def read(obs):
    trace = obs.get("trace")
    if not trace:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]

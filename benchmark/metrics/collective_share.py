"""Parallel: device time in collective operations over device busy time,
from the trace."""


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * trace["collective_s"] / trace["busy_s"]

"""Device: busy time per step on one chip (mean over the chips), from the
trace of whole steps."""


def read(obs):
    trace = obs.get("trace")
    if not trace:
        return None
    return 1e3 * trace["busy_s"] / trace["steps"]

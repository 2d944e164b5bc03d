"""Kernels: device time of the attention kernels (the step's Mosaic
custom calls) over device busy time, from the trace."""


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace["kernel_s"]:
        return None
    return 100.0 * trace["kernel_s"] / trace["busy_s"]

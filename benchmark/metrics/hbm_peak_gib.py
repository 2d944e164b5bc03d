"""Model: the fullest chip's peak of device memory through the run, in
GiB: `peak_bytes_in_use` after the window (on this runtime: live arrays
only; the float32 reference's state lives inside its one program and
leaves 0.1 GB here) plus the scratch space the compiled step holds while
it runs (`memory_analysis().temp_size_in_bytes`)."""


def read(obs):
    peak = obs.get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None

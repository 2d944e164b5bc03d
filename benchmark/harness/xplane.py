"""From a profiler trace (`.xplane.pb`) to what the metric readers use.

Per device: the seconds in which an operation ran (busy), the idle gaps
and what the host was doing in each, the seconds in collectives and the
part of those in which nothing computed (exposed), and the seconds of each
operation and of the attention kernels by name.  Averaged over the devices
of the trace.  `load` is the only function that touches jax; everything
after it works on plain tuples and is checked in `tests/test_xplane.py`
against a trace recorded on the chips and against hand-made events.

How the trace is laid out (TPU v5e, jax 0.9.0; `tests/record_trace.py`
dumps it): one plane per chip named `/device:TPU:<n>`.  Its line `XLA Ops`
holds every operation the core ran, one event each, named by the
operation's whole HLO text; the collectives the core waits in are events
there too.  Its line `Async XLA Ops` holds operations in flight beside the
core (`copy-start`, and collectives where XLA made them asynchronous).  Its
line `XLA Modules` holds one event per execution of a program.  The host's
spans are events on the lines of the plane `/host:CPU`, on the same clock.
"""

from __future__ import annotations

import functools
import glob
import gzip
import os
import re

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_LINE, ASYNC_LINE, MODULE_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
NO_SPAN = "_no_span_of_the_loop_"
HLO = re.compile(r"^%\S+ = (?P<shape>.*?) (?P<op>[\w\-]+)\(")


def newest_trace(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str) -> list:
    """[(plane name, [(line name, [(event name, start_ns, end_ns)])])] of
    the device planes and the host plane, from a `.xplane.pb` or a gzipped
    one."""
    import jax

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    planes = []
    for plane in data.planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        if not on_device and not plane.name.startswith("/host:CPU"):
            continue
        lines = []
        for line in plane.lines:
            name = op_name if on_device and line.name != MODULE_LINE \
                else (lambda text: text)
            lines.append((line.name, [
                (name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events]))
        planes.append((plane.name, lines))
    return planes


@functools.lru_cache(maxsize=None)     # a step's texts recur every step
def op_name(text: str) -> str:
    """An operation's HLO text as a name that every instance shares:
    opcode (a fusion's kind, and what it calls where that is not a plain
    `fused_computation`; a custom call's target) and result shape,
    `%fusion.12 = bf16[16,1024]{1,0} fusion(...), kind=kLoop` ->
    `fusion:kLoop_bf16_16_1024_`, at most 64 characters."""
    found = HLO.match(text)
    if not found:
        return re.sub(r"\.\d+$", "", text)[:64]
    op = found["op"]
    if op == "fusion":
        kind = re.search(r"kind=(\w+)", text)
        op += f":{kind[1]}" if kind else ""
        # XLA:TPU fuses a reduce-scatter into `calls=%all-reduce-scatter.N`
        callee = re.search(r"calls=%([A-Za-z_\-]+)", text)
        if callee and not callee[1].startswith("fused_computation"):
            op += f":{callee[1]}"
    elif op == "custom-call":
        target = re.search(r'custom_call_target="([^"]+)"', text)
        op = target[1] if target else op
    shape = re.sub(r"\{[^}]*\}", "", found["shape"])
    return (op + "_" + re.sub(r"[^A-Za-z0-9]", "_", shape))[:64]


def leaves(events) -> list:
    """Nested events of one line as segments that do not overlap: each
    stretch of time goes to the innermost event that covers it."""
    out = []
    stack = []            # (name, end)
    cursor = None

    def emit(until):
        nonlocal cursor
        if stack and until > cursor:
            out.append((stack[-1][0], cursor, until))
        cursor = max(cursor, until) if cursor is not None else until

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            emit(stack[-1][1])
            stack.pop()
        if stack:
            emit(start)
        cursor = start if cursor is None else max(cursor, start)
        stack.append((name, end))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return [s for s in out if s[2] > s[1]]


def union(intervals) -> list:
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def total(intervals) -> float:
    return sum(end - start for start, end in intervals)


def subtract(intervals, holes) -> list:
    """The parts of merged `intervals` not covered by merged `holes`."""
    out = []
    holes = list(holes)
    for start, end in intervals:
        at = start
        for h0, h1 in holes:
            if h1 <= at or h0 >= end:
                continue
            if h0 > at:
                out.append([at, h0])
            at = max(at, h1)
        if at < end:
            out.append([at, end])
    return out


def is_collective(name: str) -> bool:
    """A collective operation, or a fusion that calls one."""
    if name.startswith("fusion:"):
        name = name.split(":")[-1]
    return name.lower().startswith(COLLECTIVES)


def reduce_file(path: str, spans=(), is_kernel=lambda name: False) -> dict:
    return reduce(load(path), spans, is_kernel)


def reduce(planes, spans=(), is_kernel=lambda name: False) -> dict:
    """See the module's docstring.  Seconds throughout; per-device
    quantities are means over the devices that ran operations."""
    host = [e for name, lines in planes if not DEVICE_PLANE.match(name)
            for _, events in lines for e in events if e[0] in spans]
    devices = []        # (core's segments, collectives in flight, runs)
    for name, lines in planes:
        if not DEVICE_PLANE.match(name):
            continue
        lines = dict(lines)
        segments = leaves(lines.get(OP_LINE, []))
        if segments:
            in_flight = [e for e in lines.get(ASYNC_LINE, [])
                         if is_collective(e[0])]
            devices.append((segments, in_flight,
                            len(lines.get(MODULE_LINE, []))))
    if not devices:
        raise ValueError("the trace holds no device operation")
    everything = [s for segs, flying, _ in devices for s in segs + flying]
    first = min(s[1] for s in everything)
    last = max(s[2] for s in everything)
    n = len(devices)
    ops, kernels, gaps = {}, {}, {}
    busy = collective = exposed = 0.0
    for segments, in_flight, _ in devices:
        for name, start, end in segments:
            ops[name] = ops.get(name, 0.0) + (end - start) / n
            if is_kernel(name):
                kernels[name] = kernels.get(name, 0.0) + (end - start) / n
        coll = union((s, e) for name, s, e in segments + in_flight
                     if is_collective(name))
        work = union((s, e) for name, s, e in segments
                     if not is_collective(name))
        ran = union(coll + work)
        busy += total(ran) / n
        collective += total(coll) / n
        exposed += total(subtract(coll, work)) / n
        for g0, g1 in subtract([[first, last]], ran):
            # a gap goes to the spans of the loop by how much of it each
            # covers, and what none covers to NO_SPAN
            left = g1 - g0
            for span, s, e in host:
                cover = min(g1, e) - max(g0, s)
                if cover > 0:
                    gaps[span] = gaps.get(span, 0.0) + cover / n
                    left -= cover
            if left > 0:
                gaps[NO_SPAN] = gaps.get(NO_SPAN, 0.0) + left / n
    ns = 1e-9
    top = lambda d: [[k, v * ns] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "devices": n,
        "steps": max(runs for _, _, runs in devices),
        "window_s": (last - first) * ns,
        "busy_s": busy * ns,
        "collective_s": collective * ns,
        "collective_exposed_s": exposed * ns,
        "kernel_s": sum(kernels.values()) * ns,
        "kernels": {k: v * ns for k, v in kernels.items()},
        "device_ops": top(ops),
        "idle_gaps": top(gaps),
    }

"""A traced run's device time under the program's own names.

A scope is the device's span: a name, the scope that contains it (its
path) and, through the trace, its start, end and self time.  The program
owns the names (`ray_tpu/models/layers.py:SCOPES`, plain
`jax.named_scope`s); this file only reads them.

Where the names are.  jax writes an operation's `op_name` (the named
scopes and the transform stack, `jit(train_step)/jvp(ffn)/moe/route/...`)
into the HLO's metadata, and the chip's profiler copies it into the trace
as the stat `tf_op` of the operation's `XEventMetadata` (the plane's field
4, keyed by the event's `metadata_id`).  `jax.profiler.ProfileData` shows
an event's OWN stats only, so `harness/xplane.py:load` never saw it.  Here
is a decoder of the five messages that carry it, by their field numbers in
`xplane.proto`, with no dependency: it skips every sub-message it does not
need by its length and reads only the `/device:TPU:<n>` planes' `XLA Ops`
line.

    XSpace          planes 1
    XPlane          name 2, lines 3, event_metadata 4, stat_metadata 5
                    (maps: key 1, value 2)
    XLine           name 2, timestamp_ns 3, events 4
    XEvent          metadata_id 1, offset_ps 2, duration_ps 3
    XEventMetadata  name 2, stats 5
    XStat           metadata_id 1, str_value 5, ref_value 7 (a string that
                    is the name of that `stat_metadata`)
    XStatMetadata   name 2

`events` yields what `xplane.load` yields for that line, with the
operation's `tf_op` beside its name, so `xplane.leaves` (an operation
nested in a conditional goes to the innermost event: PR 33's buffer),
`union` and `total` do the rest.

Two limits.  A fusion carries ONE `tf_op`, its root's: a weight gradient
with AdamW's update fused behind it is counted once, wherever XLA's
metadata puts it, so `optimizer_share` is a floor.  An operation with no
`tf_op` (copies, `copy-done`, `slice-done`, a few fusions) belongs to no
scope and is not guessed at: it is `unnamed`, and the ten longest are
listed by `xplane.op_name`.

A third: XLA:TPU replaces `jax.lax.ragged_dot` by a kernel of its own whose
`tf_op` is the compiler's (`ragged-dot-none:`), not the caller's.  The
program says which scope such names belong to (`layers.COMPILER_NAMED`); the
pass they ran in nobody says, so the grouped matmuls are in phase `other`.

A program without `layers.SCOPES` (the parent of the PR that brought it)
still has phases, which need no vocabulary; its scopes read None.
"""

from __future__ import annotations

import functools
import gzip
import os
import re

from benchmark.harness import moe_trace, xplane

PHASES = ("fwd", "remat_fwd", "bwd", "optimizer", "other", "unnamed")
OPTIMIZER_SCOPES = ("optimizer_update", "routing_bias_update")
TRANSFORM = re.compile(r"^(\w+)\((.*)\)$")


# -- the wire format ---------------------------------------------------------

def _varint(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf, at, end):
    """(field number, value) of one message's fields: a varint's value, or
    the (start, end) of a length-delimited field; fixed-width fields are
    passed over."""
    while at < end:
        key, at = _varint(buf, at)
        wire = key & 7
        if wire == 0:
            value, at = _varint(buf, at)
            yield key >> 3, value
        elif wire == 2:
            size, at = _varint(buf, at)
            yield key >> 3, (at, at + size)
            at += size
        elif wire == 1:
            at += 8
        elif wire == 5:
            at += 4
        else:
            raise ValueError(f"wire type {wire} at byte {at}: not an XSpace")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    """(key, the value's span) of one entry of a map<int64, message>."""
    key, value = 0, None
    for field, got in _fields(buf, *span):
        if field == 1:
            key = got
        elif field == 2:
            value = got
    return key, value


def _named(buf, span) -> str:
    """The `name` (field 2) of an XLine or an XStatMetadata."""
    for field, got in _fields(buf, *span):
        if field == 2:
            return _text(buf, got)
    return ""


def _plane(buf, span):
    """(name, [lines], [event_metadata entries], [stat_metadata entries])
    of one XPlane, each a span with nothing inside it decoded."""
    name, lines, event_meta, stat_meta = "", [], [], []
    for field, got in _fields(buf, *span):
        if field == 2:
            name = _text(buf, got)
        elif field == 3:
            lines.append(got)
        elif field == 4:
            event_meta.append(got)
        elif field == 5:
            stat_meta.append(got)
    return name, lines, event_meta, stat_meta


def _operations(buf, event_meta, stat_meta) -> dict:
    """{metadata_id: (the operation's HLO text, its tf_op or "")}."""
    stat_names = {}
    for entry in stat_meta:
        key, value = _map_entry(buf, entry)
        if value is not None:
            stat_names[key] = _named(buf, value)
    tf_op_ids = {k for k, name in stat_names.items() if name == "tf_op"}
    out = {}
    for entry in event_meta:
        key, value = _map_entry(buf, entry)
        if value is None:
            continue
        name, tf_op = "", ""
        for field, got in _fields(buf, *value):
            if field == 2:
                name = _text(buf, got)
            elif field == 5:
                stat_id, text = 0, ""
                for f, v in _fields(buf, *got):
                    if f == 1:
                        stat_id = v
                    elif f == 5:
                        text = _text(buf, v)
                    elif f == 7:
                        text = stat_names.get(v, "")
                if stat_id in tf_op_ids:
                    tf_op = text
        out[key] = (name, tf_op)
    return out


def _line_events(buf, span, operations) -> list:
    """[((name, tf_op), start_ns, end_ns)] of one XLine."""
    timestamp_ns, events = 0, []
    for field, got in _fields(buf, *span):
        if field == 3:
            timestamp_ns = got
        elif field == 4:
            events.append(got)
    out = []
    for event in events:
        meta = offset_ps = duration_ps = 0
        for field, got in _fields(buf, *event):
            if field == 1:
                meta = got
            elif field == 2:
                offset_ps = got
            elif field == 3:
                duration_ps = got
        start_ps = timestamp_ns * 1000 + offset_ps
        out.append((operations.get(meta, ("", "")), start_ps / 1000.0,
                    (start_ps + duration_ps) / 1000.0))
    return out


def decode(data: bytes) -> list:
    """[(device plane's name, [((HLO text, tf_op), start_ns, end_ns)] of
    its `XLA Ops` line)] of a serialized XSpace."""
    buf = memoryview(data)
    out = []
    for field, got in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, lines, event_meta, stat_meta = _plane(buf, got)
        if not xplane.DEVICE_PLANE.match(name):
            continue
        for line in lines:
            if _named(buf, line) == xplane.OP_LINE:
                out.append((name, _line_events(
                    buf, line, _operations(buf, event_meta, stat_meta))))
    return out


def events(path: str) -> list:
    """`decode` of a `.xplane.pb` or a gzipped one."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return decode(f.read())


# -- from a tf_op to a phase and a scope -------------------------------------

def components(tf_op: str) -> list:
    """A `tf_op`'s path with jax's transforms taken off: `jit(...)`
    components dropped (a function's name is no scope), `jvp(ffn)` ->
    `ffn` (jax wraps the FIRST component of what a transform covers),
    `transpose(jvp(jvp()))` -> nothing; the primitive, which comes last,
    dropped."""
    out = []
    for part in tf_op.rstrip(":").split("/")[:-1]:
        while True:
            found = TRANSFORM.match(part)
            if not found:
                break
            part = "" if found[1] in ("jit", "pjit") else found[2]
        if part:
            out.append(part)
    return out


@functools.lru_cache(maxsize=None)     # a step's tf_ops recur every step
def scope_of(tf_op: str, vocabulary: tuple, compiler_named=()) -> str:
    """The longest path of ``vocabulary`` that ``tf_op`` lies under, or
    "": from the first component that is one of the vocabulary's top-level
    names (a prefix like `operator/` and jax's `checkpoint`, `cond`,
    `branch_*`, `while`, `body`, `shard_map` are passed over), extended by
    each later component that names a scope inside the one reached.
    ``compiler_named``: ((how a name the compiler gave starts, the scope the
    program says it belongs to), ...)."""
    for start, scope in compiler_named:
        if tf_op.startswith(start):
            return scope
    scope = ""
    for part in components(tf_op):
        longer = f"{scope}/{part}" if scope else part
        if longer in vocabulary:
            scope = longer
    return scope


@functools.lru_cache(maxsize=None)
def phase_of(tf_op: str) -> str:
    """Which pass of the step an operation belongs to, from jax's
    transform stack in its `tf_op`."""
    if not tf_op:
        return "unnamed"
    parts = components(tf_op)
    if any(part in OPTIMIZER_SCOPES for part in parts):
        return "optimizer"
    if "rematted_computation" in parts:
        return "remat_fwd"
    if "transpose(" in tf_op:
        return "bwd"
    if "jvp(" in tf_op:
        return "fwd"
    return "other"


# -- the reduction -----------------------------------------------------------

def reduce(planes, vocabulary=None, compiler_named=()) -> dict:
    """Seconds per device (means over the devices that ran operations),
    from `decode`'s planes: `busy_s`; `phases` {phase: s}, which sum to
    busy; `scopes` {every scope of ``vocabulary`` that ran, a scope's time
    holding its sub-scopes': s} and `named_s`, the time under any of them
    (None and None without a vocabulary); `in_scope` {scope: {phase: s}}
    of the time under a scope and under none of its sub-scopes;
    `unnamed_ops`, the ten longest operations without a `tf_op`;
    `unscoped_ops`, the ten longest with one that lies under no scope."""
    busy = named = 0.0
    phases = dict.fromkeys(PHASES, 0.0)
    scopes, in_scope, unnamed, unscoped = {}, {}, {}, {}
    devices = 0
    for _, line in planes:
        segments = xplane.leaves(line)
        if not segments:
            continue
        devices += 1
        busy += xplane.total(xplane.union((s, e) for _, s, e in segments))
        for (text, tf_op), start, end in segments:
            took = end - start
            phase = phase_of(tf_op)
            phases[phase] += took
            if not tf_op:
                op = xplane.op_name(text)
                unnamed[op] = unnamed.get(op, 0.0) + took
                continue
            if vocabulary is None:
                continue
            scope = scope_of(tf_op, vocabulary, compiler_named)
            if not scope:
                op = xplane.op_name(text)
                unscoped[op] = unscoped.get(op, 0.0) + took
                continue
            named += took
            by_phase = in_scope.setdefault(scope, {})
            by_phase[phase] = by_phase.get(phase, 0.0) + took
            while scope:
                scopes[scope] = scopes.get(scope, 0.0) + took
                scope = scope.rpartition("/")[0]
    if not devices:
        return None
    ns = 1e-9 / devices
    top = lambda d: [[k, v * ns] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:10]]
    scoped = vocabulary is not None
    return {
        "devices": devices,
        "busy_s": busy * ns,
        "phases": {k: v * ns for k, v in phases.items()},
        "named_s": named * ns if scoped else None,
        "scopes": {k: v * ns for k, v in scopes.items()} if scoped else None,
        "in_scope": {k: {p: v * ns for p, v in d.items()}
                     for k, d in in_scope.items()},
        "unnamed_ops": top(unnamed),
        "unscoped_ops": top(unscoped),
    }


def vocabulary() -> tuple:
    """(the program's scopes, `ray_tpu/models/layers.py:SCOPES`, or None
    for a program that states none; what it says of the names the compiler
    gives, `COMPILER_NAMED`)."""
    from ray_tpu.models import layers

    found = getattr(layers, "SCOPES", None)
    return (None if found is None else tuple(found),
            tuple(getattr(layers, "COMPILER_NAMED", ())))


@functools.lru_cache(maxsize=4)
def reduce_file(path: str, mtime: float = 0.0):
    return reduce(events(path), *vocabulary())


def of(obs: dict):
    """`reduce` of this run's trace, or None: a run that was not traced, a
    rehearsal, or a trace left by another run."""
    path = moe_trace.trace_path(obs)
    if path is None:
        return None
    found = reduce_file(path, os.path.getmtime(path))
    return found if found and found["busy_s"] else None


def share(obs: dict, scope: str):
    """The device time under ``scope`` over busy time, in %, or None where
    there is no trace or the program states no scopes."""
    found = of(obs)
    if found is None or found["scopes"] is None:
        return None
    return 100.0 * found["scopes"].get(scope, 0.0) / found["busy_s"]


def phase_share(obs: dict, phase: str):
    found = of(obs)
    return None if found is None else \
        100.0 * found["phases"][phase] / found["busy_s"]

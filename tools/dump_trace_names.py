"""A profiler trace's device time by the program's own names.

    python tools/dump_trace_names.py <trace.xplane.pb[.gz]> [--ops N]
    python tools/dump_trace_names.py --cell gpt2-medium.resident

Prints what `benchmark/harness/scope_trace.py` reduces a trace to: device
busy seconds, the time by phase (forward, recomputed forward, backward,
optimizer, other, unnamed), by scope of `ray_tpu/models/layers.py:SCOPES`
with each scope's phases, the longest operations that no scope names, and
(`--ops N`) the N longest operations with the phase and scope of each.
`--cell` reads the newest trace a traced run of that cell left under
`.scratch/benchmark/<cell>/trace`.  Needs no chip and holds none: the
decoder is plain Python.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def longest_operations(planes, vocabulary, compiler_named, count) -> list:
    """[(seconds per device, times it ran, op, phase, scope)] of the
    ``count`` longest operations, one of a name (`xplane.op_name`), phase
    and scope: a step's layers run the same operation under the same
    names."""
    from benchmark.harness import scope_trace, xplane

    took = {}
    for _, line in planes:
        for (text, tf_op), start, end in xplane.leaves(line):
            key = (xplane.op_name(text), scope_trace.phase_of(tf_op),
                   scope_trace.scope_of(tf_op, vocabulary or (),
                                        compiler_named) or "-")
            ns, times = took.get(key, (0.0, 0))
            took[key] = (ns + end - start, times + 1)
    rows = sorted(took.items(), key=lambda kv: -kv[1][0])[:count]
    return [(ns * 1e-9 / len(planes), times // len(planes), *key)
            for key, (ns, times) in rows]


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("trace", nargs="?", help="a .xplane.pb or .pb.gz")
    parser.add_argument("--cell", help="a cell of BENCHMARK.json: its "
                                       "newest traced run here")
    parser.add_argument("--ops", type=int, default=0, metavar="N",
                        help="also list the N longest operations")
    args = parser.parse_args()

    from benchmark.harness import registry, scope_trace, xplane

    if bool(args.trace) == bool(args.cell):
        parser.error("give a trace file or --cell, one of them")
    path = args.trace or xplane.newest_trace(os.path.join(
        registry.ROOT, ".scratch", "benchmark", args.cell, "trace"))
    began = time.perf_counter()
    vocabulary, compiler_named = scope_trace.vocabulary()
    imported = time.perf_counter() - began
    planes = scope_trace.events(path)
    found = scope_trace.reduce(planes, vocabulary, compiler_named)
    took = time.perf_counter() - began
    if found is None:
        sys.exit(f"{path} holds no device operation")
    busy = found["busy_s"]
    pct = lambda s: f"{100.0 * s / busy:7.3f} %"
    print(f"{path}: {sum(len(line) for _, line in planes)} operations on "
          f"{found['devices']} device(s), busy {busy:.6f} s a device; "
          f"decoded and reduced in {took:.2f} s, {imported:.2f} of them "
          f"importing the program's vocabulary")
    print("by phase")
    for phase in scope_trace.PHASES:
        print(f"  {pct(found['phases'][phase])}  {phase}")
    print(f"by scope ({pct(found['named_s'] or 0.0).strip()} under a "
          f"scope; a scope's time holds its sub-scopes')")
    for scope in vocabulary or ():
        seconds = found["scopes"].get(scope)
        if seconds:
            phases = ", ".join(
                f"{phase} {100.0 * s / busy:.3f}"
                for phase, s in sorted(found["in_scope"].get(
                    scope, {}).items(), key=lambda kv: -kv[1]))
            print(f"  {pct(seconds)}  {'  ' * scope.count('/')}{scope}"
                  + (f"   [its own: {phases}]" if phases else ""))
    for title, key in (("with a tf_op that lies under no scope",
                        "unscoped_ops"), ("without a tf_op", "unnamed_ops")):
        print(f"longest operations {title}")
        for op, seconds in found[key]:
            print(f"  {pct(seconds)}  {seconds:.6f} s  {op}")
    if args.ops:
        print(f"the {args.ops} longest operations: seconds, share, times "
              f"run, phase, scope, operation")
        for seconds, times, op, phase, scope in longest_operations(
                planes, vocabulary, compiler_named, args.ops):
            print(f"  {seconds:.6f}  {pct(seconds)}  {times:5d}  {phase:9s} "
                  f"{scope:32s} {op}")


if __name__ == "__main__":
    main()

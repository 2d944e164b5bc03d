"""A benchmark configuration's train step, compiled for the v5e without a
chip: what each chip holds and which collectives the partitioner put in.

    python tools/aot_collectives.py gpt2-xl-fsdp4 [--layers 2]

Reads `benchmark/configs/<name>.json`, binds the configuration's family to
the described devices of `v5e:2x2` (a one-chip configuration: the first of
them), gives `lower_step` the state's shapes with the shardings `init_state`
would give the arrays (`models/layers.py:placed_shapes`), and compiles.
Prints arguments and scratch space per chip, for each recomputed stack what
its budget kept and declined (the static sum, then what the compiled step's
own account made of it, `models/layers.py:keep_plan`: the path a chip run
takes, two more traces and compiles of the step where a name is declined),
then every collective once per `channel_id` (XLA prints an
asynchronous one several times) grouped by kind and result shape, with the
bytes of one and of all.  `--layers` compiles a shallower model: the
collectives of one layer are those of every layer, in a tenth of the time
(all 48 of XL take four minutes here).  `--conditionals` lists every
`conditional` with the largest arrays each of its branches makes (a share
of the experts: `ops/moe.py` runs over its buffer in one branch and over
all the routed rows in the other; XLA puts the false branch first).
Nothing runs: no time, no result.
"""

from __future__ import annotations

import argparse
import collections
import json
import math
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

KINDS = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
         "collective-permute")
_WIDTH = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8}
_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_OP = re.compile(r"=\s*(\(?[^=]*?)\s(" + "|".join(KINDS) + r")(-start)?\(")
_COMMENT = re.compile(r"/\*.*?\*/")
# groups of one member each, in either notation: nothing crosses a wire
_ALONE = re.compile(r"replica_groups=(\{\{\d+\}(,\{\d+\})*\}|\[\d+,1\]<=)")


def collectives(hlo_text: str) -> dict:
    """{(kind, "dtype[shape], ..."): (count, bytes of one)} over the distinct
    `channel_id`s of a compiled module's text (one whose groups have one
    member each is left out: `shard_map`'s `psum` over an axis of size 1
    on the CPU).  The shape is the
    instruction's result; an asynchronous gather or permute carries its
    operands beside its results (and two `u32[]` of context), so only the
    second half of what is left of its tuple counts."""
    seen, found = set(), {}
    for line in hlo_text.splitlines():
        line = _COMMENT.sub("", line)
        op = _OP.search(line)
        channel = re.search(r"channel_id=(\d+)", line)
        if (not op or not channel or channel.group(1) in seen
                or _ALONE.search(line)):
            continue
        seen.add(channel.group(1))
        shapes = [s for s in _SHAPE.findall(op.group(1)) if s != ("u32", "")]
        if op.group(3) and op.group(2) != "all-reduce" and len(shapes) > 1:
            shapes = shapes[len(shapes) // 2:]
        size = sum(_WIDTH.get(t, 4) * math.prod(int(n) for n in d.split(",")
                                                 if n) for t, d in shapes)
        # a combined collective's tuple repeats one shape many times over
        key = (op.group(2), ", ".join(
            f"{t}[{d}]" + (f"*{n}" if n > 1 else "")
            for (t, d), n in collections.Counter(shapes).items()))
        count, _ = found.get(key, (0, size))
        found[key] = (count + 1, size)
    return found


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def conditional_branches(hlo_text: str, largest: int = 4) -> list:
    """[(the conditional's name, [(branch, [(bytes, "dtype[shape]"), ...])])]
    over a compiled module's text: for each branch, the ``largest`` distinct
    result shapes of its instructions, those of the computations it calls
    (fusions, loops, nested conditionals) included."""
    bodies, name = {}, None
    for line in hlo_text.splitlines():
        start = _COMPUTATION.match(line)
        if start:
            name = start.group(1)
            bodies[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            bodies[name].append(_COMMENT.sub("", line))

    def called(line):
        names = _CALLED.findall(line)
        for group in _BRANCHES.findall(line):
            names += [b.strip().lstrip("%") for b in group.split(",")]
        return names

    def reach(name, seen):
        if name in bodies and name not in seen:
            seen.add(name)
            for line in bodies[name]:
                for other in called(line):
                    reach(other, seen)
        return seen

    found = []
    for lines in bodies.values():
        for line in lines:
            if " conditional(" not in line:
                continue
            branches = []
            for group in _BRANCHES.findall(line) or [",".join(
                    _CALLED.findall(line))]:
                for branch in group.split(","):
                    branch = branch.strip().lstrip("%")
                    shapes = {
                        (_WIDTH.get(t, 4) * math.prod(
                            int(n) for n in d.split(",") if n), f"{t}[{d}]")
                        for body in reach(branch, set())
                        for made in bodies[body] if "=" in made
                        for t, d in _SHAPE.findall(
                            made.split("=", 1)[1].split("(", 1)[0])}
                    branches.append(
                        (branch, sorted(shapes, reverse=True)[:largest]))
            found.append((line.split("=")[0].strip(), branches))
    return found


# what a v5e states as `memory_stats()["bytes_limit"]`: 15.75 GiB
V5E_LIMIT_GIB = 15.75


def compile_step(config: dict, traffic: dict, limit_gib=V5E_LIMIT_GIB):
    """(the configuration's step compiled for the described chips, the
    `keep_plan` of each recomputed stack it traced).  A described device
    states no memory limit, so ``limit_gib`` stands for it."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from benchmark.harness import registry

    chips = config["chips"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    family = registry.family(config)
    family.bind(topo.devices[:chips])
    from ray_tpu.models.layers import assume_memory_limit, placed_shapes
    from ray_tpu.parallel.context import use_mesh

    # the state's and the batch's shapes, placed as `Family.init_state` and
    # `place_batch` place the arrays: the step's own reckoning of it
    params = jax.eval_shape(family._init, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct(
        (traffic["batch"], traffic["seq"] + 1), jnp.int32)
    with use_mesh(family.mesh):
        (params, opt, batch), _ = placed_shapes(
            params, jax.eval_shape(family.optimizer().init, params),
            {"tokens": tokens})
    plans = []
    with assume_memory_limit(int(limit_gib * 2 ** 30), plans):
        lowered = family.lower_step(params, opt, batch)
    return lowered.compile(), plans


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("config", help="a name under benchmark/configs/")
    parser.add_argument("--traffic", default=None,
                        help="a name under benchmark/traffic/ (default: "
                             "that of the configuration's first cell)")
    parser.add_argument("--layers", type=int, default=None)
    parser.add_argument("--conditionals", action="store_true",
                        help="list each conditional's branches with the "
                             "largest arrays they make")
    parser.add_argument("--hlo", default=None,
                        help="write the compiled module's text here")
    parser.add_argument("--memory-limit", type=float, default=V5E_LIMIT_GIB,
                        help="the device's memory limit in GiB, which a "
                             "recomputed stack's budget starts from "
                             "(default: the v5e's; 0: a device that states "
                             "none)")
    args = parser.parse_args()

    from benchmark.harness import registry

    config = registry.config(args.config)
    traffic = args.traffic or next(
        w["traffic"] for w in registry.benchmark()["workloads"]
        if w["config"] == args.config)
    if args.layers:
        depth = "n_layer" if "n_layer" in config else "num_hidden_layers"
        config[depth] = args.layers
    compiled, plans = compile_step(config, registry.traffic(traffic),
                                   args.memory_limit)
    text = compiled.as_text()
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(text)
    memory = compiled.memory_analysis()
    gib = 2.0 ** 30
    print(f"{args.config} x {traffic}, {config['chips']} chip(s), per chip: "
          f"arguments {memory.argument_size_in_bytes / gib:.3f} GiB, scratch "
          f"{memory.temp_size_in_bytes / gib:.3f} GiB, output "
          f"{memory.output_size_in_bytes / gib:.3f} GiB (aliased "
          f"{memory.alias_size_in_bytes / gib:.3f})")
    for plan in plans:
        size = lambda name: f"{name} {plan['marked'][name] / gib:.3f}"
        print(f"recomputed stack, per chip: keeps "
              f"{plan['bytes_kept'] / gib:.3f} GiB of names "
              f"({', '.join(map(size, plan['names'])) or 'none'}); declined "
              f"{', '.join(map(size, plan['declined'])) or 'none'}; room "
              f"{plan['room'] / gib:.3f} GiB after {plan['already'] / gib:.3f}"
              f" kept whatever the room, {plan['reserve'] / gib:.3f} of "
              f"reserve and {(plan['state'] or 0) / gib:.3f} of training "
              f"state (the static sum)")
        if plan["measured"]:
            print(f"    measured: room {plan['measured_room'] / gib:.3f} GiB "
                  f"under the compiled step; admitted "
                  f"{', '.join(map(size, plan['admitted'])) or 'none'}; "
                  f"compiled peak {plan['peak'] / gib:.3f} GiB"
                  + (" (from a record)" if plan["recorded"] else ""))
        else:
            print("    not measured: the static plan stands")
    found = collectives(text)
    for (kind, shape), (count, size) in sorted(
            found.items(), key=lambda kv: -kv[1][0] * kv[1][1]):
        print(f"{count:5d} x {kind:18s} {shape:60s} {size / 1e6:10.2f} MB "
              f"each {count * size / 1e6:10.1f} MB")
    if args.conditionals:
        for name, branches in conditional_branches(text):
            print(name)
            for branch, shapes in branches:
                print(f"    {branch:28s}", ", ".join(
                    f"{shape} {size / 1e6:.1f} MB" for size, shape in shapes))
    print(json.dumps({"collectives": sum(c for c, _ in found.values()),
                      "bytes": sum(c * s for c, s in found.values())}))


if __name__ == "__main__":
    main()

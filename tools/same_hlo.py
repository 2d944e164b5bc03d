"""Whether two compiled steps are the same program: the modules that
`tools/aot_collectives.py <config> --hlo FILE` writes for two trees,
compared instruction for instruction.

    python tools/same_hlo.py parent.hlo change.hlo [--root DIR DIR]

What may differ and is set aside: the checkouts' paths (``--root``: the
two trees' directories, read as one), where in the source an instruction
was traced (the module's tables of files, functions, lines and stack frames
and each instruction's `stack_frame_id`: code that moved to another
function or file is the same program), and inside each Mosaic kernel's
serialised body the locations alone: every body is parsed and printed
without them, and compared with the rest of its `backend_config` (the
scratch it asks for, its grid's semantics).  Prints the kernels by scope
with how many calls of each, the lines that differ as printed, and the
lines that differ once every instruction and computation is named by its
kind and its place in order of appearance (XLA numbers them in the order
they were traced: two independent parts traced the other way round swap
their numbers and nothing else); exits 1 if any of the latter does.  Needs
no chip.  A change that is a move, or that leaves a cell's shapes on the
parent's path, compiles to the parent's step; one that does not owes the
cell a measurement (PERF.md §6, PRs 30, 36-39 and 45).
"""

from __future__ import annotations

import argparse
import base64
import collections
import hashlib
import re
import sys

_BODY = re.compile(r'"body":"([^"]*)"')
# an entry of `FileNames`, `FunctionNames`, `FileLocations`, `StackFrames`
_SOURCE = re.compile(r'^\d+ (".*"|\{.*\})$')
_FRAME = re.compile(r" ?stack_frame_id=\d+")
# an instruction's or a computation's name: a kind and the compiler's numbers
_NAME = re.compile(r"%?\b[A-Za-z_][\w\-]*(?:\.[\w\-]+)*\.\d+\b")


def kernel_text(body: str) -> str:
    """A Mosaic kernel's serialised MLIR, printed without locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    from jax.experimental.mosaic.dialects import tpu

    context = mlir.make_ir_context()
    context.allow_unregistered_dialects = True
    # its memory spaces and semaphores parse only as the dialect's own
    tpu.register_dialect(context)
    with context:
        module = ir.Module.parse(base64.b64decode(body))
        return module.operation.get_asm(enable_debug_info=False)


def instructions(path: str, root: str = "") -> tuple[list, dict]:
    """(the module's lines with paths, source lines and kernel bodies
    normalised, {(kernel's scope, digest of its body): calls})."""
    lines, kernels, digests = [], collections.Counter(), {}
    with open(path) as module:
        text = module.readlines()
    for line in text:
        if _SOURCE.match(line):
            continue
        if root:
            line = line.replace(root.rstrip("/") + "/", "")
        line = _FRAME.sub("", line)
        found = _BODY.search(line)
        if found:
            body = found.group(1)
            if body not in digests:
                digests[body] = hashlib.sha256(
                    kernel_text(body).encode()).hexdigest()[:12]
            line = _BODY.sub(f'"body":"{digests[body]}"', line)
            name = re.search(r'op_name="([^"]*)"', line)
            # the compiler's own kernels (`ragged-dot-none`) have no path
            scope = name.group(1).split("/")[-2:][0] if name else "?"
            kernels[scope, digests[body]] += 1
        lines.append(line)
    return lines, dict(kernels)


def renamed(lines: list) -> list:
    """The lines with each numbered name replaced by its kind and its rank
    among all of them in order of first appearance: one substitution
    throughout, so the result is equal for two modules only if one is the
    other with its names changed."""
    ranks = {}

    def rank(found):
        name = found.group(0).lstrip("%")
        kind = re.sub(r"[\d.]+", "", name)
        return ranks.setdefault(name, f"%{kind}@{len(ranks)}")
    return [_NAME.sub(rank, line) for line in lines]


def report(a: list, b: list, how: str) -> bool:
    differ = [i for i, (x, y) in enumerate(zip(a, b)) if x != y]
    print(f"{len(a)} / {len(b)} lines, {len(differ)} differ {how}"
          + ("" if len(a) == len(b) else ", and the lengths"))
    for i in differ[:10]:
        print(f"  {i}: {a[i].strip()[:160]}\n  {i}: {b[i].strip()[:160]}")
    return bool(differ) or len(a) != len(b)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("parent")
    parser.add_argument("change")
    parser.add_argument("--root", nargs=2, default=("", ""),
                        metavar="DIR", help="the two checkouts")
    args = parser.parse_args()
    (a, ka), (b, kb) = (instructions(path, root) for path, root in zip(
        (args.parent, args.change), args.root))
    for key in sorted(set(ka) | set(kb)):
        print(f"kernel {key[0]:<18} body {key[1]}  calls "
              f"{ka.get(key, 0)} / {kb.get(key, 0)}")
    if report(a, b, "as printed"):
        return int(report(renamed(a), renamed(b),
                          "with names by order of appearance") or ka != kb)
    return int(ka != kb)


if __name__ == "__main__":
    sys.exit(main())

"""Whether two compiled steps are the same program: the modules that
`tools/aot_collectives.py <config> --hlo FILE` writes for two trees,
compared instruction for instruction.

    python tools/same_hlo.py parent.hlo change.hlo [--root DIR DIR]

What may differ and is set aside: the checkouts' paths (``--root``: the
two trees' directories, read as one), the table of source lines
(`file_name_id ... line=`), and inside each Mosaic kernel's serialised body
the locations alone: every body is parsed and printed without them, and
compared with the rest of its `backend_config` (the scratch it asks for,
its grid's semantics).  Prints the kernels by scope with how many calls of
each, then the first lines that differ; exits 1 if any does.  Needs no
chip.  A change that is a move, or that leaves a cell's shapes on the
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
_LINES = re.compile(r"\{file_name_id=\d+ function_name_id=\d+ line=.*\}")


def kernel_text(body: str) -> str:
    """A Mosaic kernel's serialised MLIR, printed without locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    context = mlir.make_ir_context()
    context.allow_unregistered_dialects = True
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
        if root:
            line = line.replace(root.rstrip("/") + "/", "")
        line = _LINES.sub("{source line}", line)
        found = _BODY.search(line)
        if found:
            body = found.group(1)
            if body not in digests:
                digests[body] = hashlib.sha256(
                    kernel_text(body).encode()).hexdigest()[:12]
            line = _BODY.sub(f'"body":"{digests[body]}"', line)
            name = re.search(r'op_name="([^"]*)"', line)
            scope = name.group(1).split("/")[-2] if name else "?"
            kernels[scope, digests[body]] += 1
        lines.append(line)
    return lines, dict(kernels)


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
    differ = [i for i, (x, y) in enumerate(zip(a, b)) if x != y]
    print(f"{len(a)} / {len(b)} lines, {len(differ)} differ"
          + ("" if len(a) == len(b) else ", and the lengths"))
    for i in differ[:10]:
        print(f"  {i}: {a[i].strip()[:160]}\n  {i}: {b[i].strip()[:160]}")
    return int(bool(differ) or len(a) != len(b) or ka != kb)


if __name__ == "__main__":
    sys.exit(main())

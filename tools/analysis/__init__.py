"""Concurrency & config static-analysis suite for the ray_tpu runtime.

Five AST passes over ``ray_tpu/`` (the Python stand-in for the
compiler-enforced thread-safety annotations the C++ reference gets from
absl/clang):

* **lock-discipline** — ``# guard: <lock>`` field annotations checked
  against lexical ``with`` blocks (plus ``# requires: <lock>`` helpers);
* **blocking-under-lock** — socket/subprocess/sleep/join/result calls
  made while a lock is held;
* **env-registry** — every ``RAY_TPU_*`` env var declared through the
  ``core/config.py`` registry, no direct reads, README table in sync;
* **thread-hygiene** — every thread named, and daemonized or joined;
* **direct-hot-path** — the direct transport's conn-thread lock budget
  is frozen: new locks on the per-call burst path need an audited
  allowlist entry or a ``# hotpath-ok:`` justification.

Run ``python -m tools.analysis`` (exit 0 = clean; any violation or
reason-less suppression = exit 1).  The runtime half of the tooling is
``ray_tpu/util/locks.py`` (``RAY_TPU_DEBUG_LOCKS=1`` lock-order
watchdog).
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

from tools.analysis import (blocking_under_lock, direct_hot_path,
                            env_registry, lock_discipline, thread_hygiene)
from tools.analysis.common import (SourceFile, Suppression, Violation,
                                   iter_py_files, load_files)

#: files outside ray_tpu/ also swept by the env-var completeness scan
EXTRA_SCAN = ("tests", "examples", "bench_core.py", "bench_scale.py")
#: fixture snippets in here intentionally contain violations
SCAN_EXCLUDE = ("tests/test_analysis.py",)


def analyze(repo_root: str) -> Tuple[List[Violation], List[Suppression],
                                     List[env_registry.FlagDef]]:
    pkg_files = load_files(
        iter_py_files(os.path.join(repo_root, "ray_tpu")), repo_root)

    violations: List[Violation] = []
    suppressions: List[Suppression] = []
    for sf in pkg_files:
        violations += lock_discipline.check(sf)
        violations += blocking_under_lock.check(sf)
        violations += thread_hygiene.check(sf)
        violations += direct_hot_path.check(sf)
        suppressions += sf.all_suppressions()

    defs = env_registry.collect_defines(pkg_files)
    violations += env_registry.check_duplicates(defs)
    violations += env_registry.check_rogue_reads(pkg_files)

    scan_files = list(pkg_files)
    for entry in EXTRA_SCAN:
        path = os.path.join(repo_root, entry)
        if os.path.isdir(path):
            scan_files += load_files(
                [p for p in iter_py_files(path)
                 if os.path.relpath(p, repo_root).replace("\\", "/")
                 not in SCAN_EXCLUDE], repo_root)
        elif os.path.isfile(path):
            scan_files += load_files([path], repo_root)
    violations += env_registry.check_completeness(scan_files, defs)

    readme = os.path.join(repo_root, "README.md")
    if os.path.isfile(readme):
        with open(readme, encoding="utf-8") as f:
            violations += env_registry.check_readme("README.md", f.read(),
                                                    defs)

    # reason-less suppressions are themselves violations
    for sup in suppressions:
        if not sup.reason:
            violations.append(Violation(
                sup.path, sup.line, "suppression",
                f"'# {sup.kind}:' without a reason — every escape hatch "
                f"must say why"))

    violations.sort(key=lambda v: (v.path, v.line))
    return violations, suppressions, defs

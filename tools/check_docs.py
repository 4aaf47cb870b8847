"""Documentation checker: doctests + executable docs snippets.

Two guarantees, so documentation can't silently rot:

1. every docstring example (``>>>``) in the audited modules passes
   (``doctest`` over the imported module, so relative imports work);
2. every ``python`` fenced code block in README.md / docs/*.md executes
   (blocks are run top-to-bottom per file in one shared namespace, so a
   snippet may build on the previous one; mark illustrative-only blocks as
   ```text or ```bash and they are skipped).

    PYTHONPATH=src python tools/check_docs.py
"""
from __future__ import annotations

import doctest
import importlib
import importlib.util
import re
import sys
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

# modules whose docstring examples are contractual (the core/device/apps
# public surface; extend as examples are added)
DOCTEST_MODULES = [
    "repro.core.compile",
    "repro.core.crossbar",
    "repro.core.engine",
    "repro.core.replay_kernel",
    "repro.core.latency",
    "repro.core.plan",
    "repro.core.tiling",
    "repro.core.matvec",
    "repro.core.binary_matvec",
    "repro.core.conv",
    "repro.core.binary_conv",
    "repro.device.energy",
    "repro.device.faults",
    "repro.apps.pipeline",
    "repro.apps.imaging",
    "repro.obs.trace",
    "repro.obs.metrics",
]

# scripts outside the package tree (tools/ is not a package) whose module
# docstrings carry contractual examples; loaded by file path
DOCTEST_FILES = ["tools/trace_report.py"]

SNIPPET_FILES = ["README.md", "docs/ARCHITECTURE.md", "docs/ALGORITHMS.md"]

FENCE = re.compile(r"^```python\s*$(.*?)^```\s*$", re.M | re.S)


def run_doctests() -> tuple:
    failed = attempted = 0
    mods = [(name, importlib.import_module(name))
            for name in DOCTEST_MODULES]
    for rel in DOCTEST_FILES:
        spec = importlib.util.spec_from_file_location(
            Path(rel).stem, ROOT / rel)
        mod = importlib.util.module_from_spec(spec)
        # dataclasses (and pickling) resolve the module through sys.modules
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        mods.append((rel, mod))
    for name, mod in mods:
        res = doctest.testmod(mod, verbose=False)
        failed += res.failed
        attempted += res.attempted
        status = "ok" if res.failed == 0 else "FAIL"
        print(f"doctest {name}: {res.attempted} examples, "
              f"{res.failed} failed [{status}]")
    return failed, attempted


def run_snippets() -> tuple:
    failed = attempted = 0
    for rel in SNIPPET_FILES:
        path = ROOT / rel
        if not path.exists():
            print(f"snippets {rel}: MISSING FILE")
            failed += 1
            continue
        blocks = FENCE.findall(path.read_text())
        ns: dict = {}
        for i, block in enumerate(blocks):
            attempted += 1
            try:
                exec(compile(block, f"{rel}[block {i}]", "exec"), ns)
            except Exception:
                failed += 1
                print(f"snippets {rel} block {i}: FAILED")
                traceback.print_exc()
        print(f"snippets {rel}: {len(blocks)} python blocks executed")
    return failed, attempted


def main() -> int:
    df, da = run_doctests()
    sf, sa = run_snippets()
    print(f"docs check: {da} doctest examples + {sa} snippets, "
          f"{df + sf} failures")
    return 1 if (df + sf) else 0


if __name__ == "__main__":
    sys.exit(main())

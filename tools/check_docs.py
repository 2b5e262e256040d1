"""Docs lint: fail when README/docs reference symbols or files that no
longer exist.

Scans the prose docs (README.md, docs/*.md, ROADMAP.md) and the module
docstrings of the kernel package (``src/repro/kernels/*.py`` — the modules
whose prose makes cross-module claims about layouts and test anchors) for

  * dotted ``repro...`` references (``repro.core.kvcache``,
    ``repro.models.attention.decode_attention_packed``, ...): the longest
    importable module prefix is imported and the remainder resolved with
    getattr — a renamed function or deleted module fails the lint;
  * repo-relative file references (``docs/FORMATS.md``,
    ``benchmarks/serve_throughput.py``, ``tests/test_engine.py``, ...):
    the path must exist;
  * quantization-policy preset references (``--policy paper-iv``,
    backticked ``uniform:<fmt>`` spellings, and backticked hyphenated
    names on lines that mention a policy/preset, scenario-matrix cell
    names excepted): the name must resolve in the ``repro.core.policy``
    preset registry — docs advertising a renamed or deleted preset fail
    CI;
  * matrix perf-gate references (the ``gate:`name``` spelling): the name
    must be declared in ``benchmarks.matrix.GATE_NAMES`` — docs
    documenting a gate ``check_matrix_gates`` does not enforce fail CI;
  * serve-status references (the ``status:`name``` spelling): the name
    must be declared in ``repro.runtime.guard.STATUS_NAMES`` — the
    failure-semantics docs promise per-request terminal statuses, and a
    doc naming a status the scheduler never emits fails CI;
  * fault-class references (the ``fault:`name``` spelling): the name
    must be declared in ``repro.runtime.faults.FAULT_CLASSES`` — the
    failure-semantics and crash-recovery docs enumerate the injectable
    fault/crash classes, and a doc naming one the injector cannot fire
    fails CI.

Runs as a section of ``benchmarks/run.py`` and as the tier-1 test
``tests/test_docs.py``, so stale docs break CI instead of readers.

    PYTHONPATH=src python -m tools.check_docs
"""
from __future__ import annotations

import importlib
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# CHANGES.md is deliberately excluded: it is an append-only historical log
# whose old entries legitimately name since-renamed symbols.
DOC_FILES = ["README.md", "ROADMAP.md", "docs"]

# repro.a.b or repro.a.b.symbol — at least one dotted component
SYMBOL_RE = re.compile(r"\brepro(?:\.[A-Za-z_][A-Za-z0-9_]*)+")
# repo-relative paths with a known top-level dir and a file extension
PATH_RE = re.compile(
    r"\b(?:docs|tests|benchmarks|examples|tools|src)/[\w./-]+\.(?:py|md|json)\b"
)

# policy-preset references: `--policy <name>` CLI spellings anywhere, plus
# backticked preset-shaped tokens (`uniform:<fmt>` always; hyphenated
# names only on lines that talk about a policy/preset, so `--kv-format`
# prose doesn't false-positive). JSON paths are policy files, not presets.
POLICY_FLAG_RE = re.compile(r"--policy[ =]+([A-Za-z0-9_:.\-/]+)")
POLICY_UNIFORM_RE = re.compile(r"`(uniform:[A-Za-z0-9_]+)`")
POLICY_NAME_RE = re.compile(r"`([a-z0-9]+(?:-[a-z0-9]+)+)`")

# matrix perf-gate references: docs spell them gate:`name` so the lint
# can tell a gate claim from ordinary backticked code
GATE_RE = re.compile(r"gate:`([A-Za-z0-9_]+)`")

# per-request serve statuses: docs spell them status:`name` so the
# failure-semantics vocabulary stays pinned to the scheduler's enum
STATUS_RE = re.compile(r"status:`([A-Za-z0-9_]+)`")

# injectable fault/crash classes: docs spell them fault:`name` so the
# recovery-matrix vocabulary stays pinned to the injector's enum
FAULT_RE = re.compile(r"fault:`([A-Za-z0-9_]+)`")


def _policy_candidates(text: str) -> set:
    cands = set(POLICY_FLAG_RE.findall(text))
    cands |= set(POLICY_UNIFORM_RE.findall(text))
    for line in text.splitlines():
        if "policy" in line.lower() or "preset" in line.lower():
            for name in POLICY_NAME_RE.findall(line):
                if not name.startswith("--"):
                    cands.add(name)
    return {c for c in cands
            if not c.endswith(".json") and "/" not in c and "<" not in c}


# Code packages whose MODULE DOCSTRINGS are linted like prose docs: kernel
# modules document payload layouts and name their test/doc anchors, and a
# renamed anchor must fail CI the same way a stale README does.
DOCSTRING_DIRS = ["src/repro/kernels"]


def _doc_paths() -> list[str]:
    out = []
    for entry in DOC_FILES:
        full = os.path.join(REPO, entry)
        if os.path.isdir(full):
            out.extend(
                os.path.join(full, f) for f in sorted(os.listdir(full))
                if f.endswith(".md")
            )
        elif os.path.exists(full):
            out.append(full)
    return out


def _docstring_paths() -> list[str]:
    out = []
    for entry in DOCSTRING_DIRS:
        full = os.path.join(REPO, entry)
        if os.path.isdir(full):
            out.extend(
                os.path.join(full, f) for f in sorted(os.listdir(full))
                if f.endswith(".py")
            )
    return out


def _resolve_symbol(dotted: str) -> str | None:
    """Return an error string, or None if the reference resolves."""
    parts = dotted.split(".")
    # find the longest importable module prefix
    mod, n_mod = None, 0
    for i in range(len(parts), 0, -1):
        try:
            mod = importlib.import_module(".".join(parts[:i]))
            n_mod = i
            break
        except ImportError:
            continue
        except Exception as e:  # import-time crash is a real doc problem too
            return f"importing {'.'.join(parts[:i])} raised {e!r}"
    if mod is None:
        return "no importable module prefix"
    obj = mod
    for attr in parts[n_mod:]:
        if not hasattr(obj, attr):
            return f"{'.'.join(parts[:n_mod])} has no attribute {attr!r}"
        obj = getattr(obj, attr)
    return None


def check_file(path: str, docstring_only: bool = False) -> list[str]:
    with open(path, encoding="utf-8") as f:
        text = f.read()
    rel = os.path.relpath(path, REPO)
    errors = []
    if docstring_only:
        import ast

        text = ast.get_docstring(ast.parse(text)) or ""
        # Kernel modules carry the payload-layout and test-anchor prose
        # this lint exists for: a NEW kernel module shipped without a
        # module docstring would otherwise pass vacuously.
        if not text.strip() and not os.path.basename(path).startswith("__"):
            return [f"{rel}: kernel module has no module docstring "
                    f"(layout/anchor prose is required, see DOCSTRING_DIRS)"]
    for dotted in sorted(set(SYMBOL_RE.findall(text))):
        err = _resolve_symbol(dotted)
        if err is not None:
            errors.append(f"{rel}: dead symbol `{dotted}` ({err})")
    for ref in sorted(set(PATH_RE.findall(text))):
        if not os.path.exists(os.path.join(REPO, ref)):
            errors.append(f"{rel}: dead file reference `{ref}`")
    from benchmarks.matrix import CELLS
    from repro.core.policy import known_policy_spec

    # a scenario-matrix cell name on a policy line names a cell, not a preset
    cell_names = {c.name for c in CELLS}
    for name in sorted(_policy_candidates(text) - cell_names):
        if not known_policy_spec(name):
            errors.append(
                f"{rel}: unknown policy preset `{name}` (not in the "
                f"repro.core.policy registry)")
    gate_refs = sorted(set(GATE_RE.findall(text)))
    if gate_refs:
        from benchmarks.matrix import GATE_NAMES

        for name in gate_refs:
            if name not in GATE_NAMES:
                errors.append(
                    f"{rel}: unknown matrix gate gate:`{name}` (not in "
                    f"benchmarks.matrix.GATE_NAMES)")
    status_refs = sorted(set(STATUS_RE.findall(text)))
    if status_refs:
        from repro.runtime.guard import STATUS_NAMES

        for name in status_refs:
            if name not in STATUS_NAMES:
                errors.append(
                    f"{rel}: unknown serve status status:`{name}` (not in "
                    f"repro.runtime.guard.STATUS_NAMES)")
    fault_refs = sorted(set(FAULT_RE.findall(text)))
    if fault_refs:
        from repro.runtime.faults import FAULT_CLASSES

        for name in fault_refs:
            if name not in FAULT_CLASSES:
                errors.append(
                    f"{rel}: unknown fault class fault:`{name}` (not in "
                    f"repro.runtime.faults.FAULT_CLASSES)")
    return errors


def run() -> list[str]:
    sys.path.insert(0, os.path.join(REPO, "src"))
    sys.path.insert(0, REPO)                   # for benchmarks.matrix
    errors = []
    for path in _doc_paths():
        errors.extend(check_file(path))
    for path in _docstring_paths():
        errors.extend(check_file(path, docstring_only=True))
    return errors


def main():
    errors = run()
    for e in errors:
        print(f"[check_docs] {e}")
    n_files = len(_doc_paths()) + len(_docstring_paths())
    assert not errors, f"{len(errors)} dead doc references (see above)"
    print(f"[check_docs] {n_files} doc files clean")


if __name__ == "__main__":
    main()

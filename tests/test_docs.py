"""Tier-1 guard for the docs lint (tools/check_docs.py): README and docs
must not reference symbols or files that no longer exist."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools import check_docs


def test_docs_reference_live_symbols():
    errors = check_docs.run()
    assert not errors, "\n".join(errors)


def test_lint_catches_dead_references(tmp_path):
    bad = tmp_path / "bad.md"
    bad.write_text("see `repro.core.kvcache.no_such_symbol` and "
                   "docs/NO_SUCH_FILE.md\n")
    errors = check_docs.check_file(str(bad))
    assert len(errors) == 2


def test_lint_checks_fault_class_names(tmp_path):
    """Documented fault/crash classes must exist in
    repro.runtime.faults.FAULT_CLASSES — a recovery matrix naming a
    class the injector cannot fire fails."""
    ok = tmp_path / "ok.md"
    ok.write_text("killed at fault:`crash_mid_decode`, torn by "
                  "fault:`journal_truncation`\n")
    assert check_docs.check_file(str(ok)) == []
    bad = tmp_path / "bad.md"
    bad.write_text("killed at fault:`power_loss`\n")
    errors = check_docs.check_file(str(bad))
    assert len(errors) == 1 and "power_loss" in errors[0]

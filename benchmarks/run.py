"""Paper-table driver — one module per paper table/figure, then the docs lint.

    PYTHONPATH=src python -m benchmarks.run [--skip-llm]

  format_table     -> Table I / II   (format constants)
  quant_error      -> Fig. 3         (Gaussian MSE sweep, 1 : 1.32 : 1.89)
  dot_product      -> §III.B / Fig.4 (fixed-point flow + multiplier counts)
  llm_accuracy     -> Tables III-V   (tiny-LM proxy incl. the NVFP4 crash)
  check_docs       -> repo lint      (README/docs must not reference dead
                                      symbols, files, presets, statuses
                                      or fault classes)

None of these measures speed. Serving speed is measured on the chip by
``bench/run.py``, against the contract in ``BENCHMARK.json``.
"""
import argparse
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-llm", action="store_true",
                    help="skip the (slow) tiny-LM accuracy proxy")
    args = ap.parse_args()

    from benchmarks import dot_product, format_table, quant_error

    sections = [
        ("format_table (Table I/II)", format_table.main),
        ("quant_error (Fig. 3)", quant_error.main),
        ("dot_product (§III.B / Fig. 4)", dot_product.main),
    ]
    if not args.skip_llm:
        from benchmarks import llm_accuracy
        sections.append(("llm_accuracy (Tables III-V proxy)", llm_accuracy.main))

    from tools import check_docs
    sections.append(("check_docs (repo lint)", check_docs.main))

    failures = 0
    for name, fn in sections:
        print("=" * 72)
        print(f"== {name}")
        print("=" * 72)
        t0 = time.time()
        try:
            fn()
            print(f"[ok] {name} ({time.time() - t0:.1f}s)\n")
        except AssertionError as e:
            failures += 1
            print(f"[FAIL] {name}: {e}\n")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()

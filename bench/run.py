"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload qwen1.5-0.5b.longctx --seed 7 \\
        --seconds 30 --trace 0

from the root of a checkout, on a machine whose TPU chips JAX sees. The
cell (``BENCHMARK.json``) names a configuration (``bench/configs``) and a
traffic mix (``bench/traffic``). ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` with ``--trace 1``), then ``checks``: each number the
output check compared, with its limit. The same numbers end standard error.

Without a TPU, without the chips the cell asks for, or outside a checkout
that holds the program (``src/repro``), it exits non-zero and prints no
result.

``--control 1`` scores the tokens of the float8-stored reference (the
check's control) in place of the served ones; such a run has to print
``"correct": false``. The benchmark's own runs leave it at 0.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}: run from a checkout "
              "of the repository", file=sys.stderr)
        return 2
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        print(f"bench: JAX_PLATFORMS={platforms} holds JAX off the TPU",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START,
                          control=bool(args.control))
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

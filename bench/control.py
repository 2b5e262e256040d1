"""Readings that set the limits of the output check, on the chip.

    python3 bench/control.py --workload qwen1.5-0.5b.interactive \\
        --seeds 1-12 --control-seeds 1-12 --seconds 8

For each seed, in one process: weights from the seed, a short window of
the cell's own traffic through the timed path, then the check's numbers
(``check.numbers``) for the sample the check draws: of the served tokens
(the program's reading); for the control seeds, of the tokens the
float8-stored reference puts first (the control's reading); and of the
served tokens with one token of every request altered where it is
produced, as ``bench/tests`` plants that fault (the fault's reading). One
JSON line per seed on standard output; each limit in
``limits/<workload>.json`` lies between the largest program reading and
the smallest control reading. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def seeds(spec: str) -> list[int]:
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def altered_tokens(tokens, vocab: int):
    """The served tokens with the sixth changed to the next id."""
    out = np.array(tokens, copy=True)
    out[5] = (out[5] + 1) % vocab
    return out


def readings(workload, seed_list, control_list, seconds, finder=None):
    """Yields one dict per seed (program and, where asked, control gaps)."""
    from bench import check, harness
    from bench.discover import Finder

    finder = finder or Finder()
    counter = harness.CompileCounter()
    for i, seed in enumerate(seed_list):
        t0 = time.perf_counter()
        r = harness.Run(finder, workload, seed)
        r.setup(warm=(i == 0))
        r.window(seconds, counter)
        attempted, failed = r.request_status()
        r.free_program_state()
        pairs = check.sample(r)
        logits, served = check.reference_logits(r, pairs)
        g = check.gaps(logits, served)
        row = {"seed": seed, "requests": attempted, "failed": failed,
               "calls": len(r.calls), "positions": int(g.size),
               "program": check.numbers(g),
               "program_request_gaps": [float(v) for v in g.mean(axis=1)],
               "program_max_gap": float(g.max()),
               "program_agree": float((g == 0).mean())}
        altered = [(p, altered_tokens(t, r.sizes.vocab)) for p, t in pairs]
        a_logits, a_served = check.reference_logits(r, altered)
        row["altered"] = check.numbers(check.gaps(a_logits, a_served))
        if seed in control_list:
            c = check.gaps(logits, check.control_tokens(r, pairs))
            row.update(control=check.numbers(c),
                       control_max_gap=float(c.max()),
                       control_agree=float((c == 0).mean()))
        row["seconds"] = time.perf_counter() - t0
        yield row
        del r, logits


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import configure_process

    configure_process()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    ctl = seeds(args.control_seeds) if args.control_seeds else []
    for row in readings(args.workload, seeds(args.seeds), ctl, args.seconds):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

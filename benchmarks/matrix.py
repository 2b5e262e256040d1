"""Scenario matrix: the serve perf-regression surface (BENCH_matrix.json).

    PYTHONPATH=src python -m benchmarks.matrix --cells smoke
    PYTHONPATH=src python -m benchmarks.matrix --cells all --update

Every cell is a :class:`repro.runtime.scenario.Scenario` declared below as
data: arch x impl x kv_format (bf16 / hif4 / paged-hif4) x policy preset x
batch x seqlen, with per-cell expected-dispatch assertions (which engine
route the cell MUST take — e.g. a paged cell must route through
``fused_paged_decode_attention``, never the chunked twin) and a per-cell
regression tolerance. Cells execute through the real serve stack
(``repro.runtime.scenario.run_scenarios``); each records measured decode /
prefill latency next to a roofline prediction from EXACT HiF4 payload byte
counts (0.5625 B/value packed weights; ``kvcache.kv_bytes_per_token`` KV)
against the measured stream bandwidth (``benchmarks.roofline``).

Gates (all named in GATE_NAMES; ``benchmarks/run.py check_matrix_gates``
enforces them against the committed trajectory, failing loudly — never
skipping — on a missing field, a failed dispatch assertion, a silent
hif4->bf16 fallback, or a ratio regression):

  cell_coverage            >= 30 cells over all 6 families, all 3 impls
  dispatch_ok              every cell passed its expected-dispatch asserts
  no_silent_fallback       kv_format_fallback only where the cell declared
                           it (ssm / hybrid expected-fallback cells)
  trajectory_regression    fresh decode_step_ms <= stored * rel_tol
                           (checked by `--cells` runs vs BENCH_matrix.json)
  packed_over_qdq_decode   packed decode >= 0.9x qdq (fused-matmul claim)
  hif4_over_bf16_kv_decode hif4-KV decode >= 0.9x bf16-KV (fused-attention
                           claim)
  guard_overhead           guarded decode (NaN sentinel + meta audit)
                           >= 0.98x unguarded (guards nearly free)
  journal_overhead         journaled paged decode (write-ahead journal,
                           one fsync per decode chunk)
                           >= 0.98x the chunk-matched unjournaled cell
  recovery_replay          the crash+resume cell recovered every request
                           bitwise-identical to its uninterrupted run and
                           recorded the recovery timings
  searched_policy_frontier the calibration-searched policy (repro
                           calibrate at the sensitive-fallback preset's
                           byte budget) serves the searched cell at
                           <= the preset's bytes AND <= its error on the
                           same calibration set (record["calibration"])

The two ratio gates moved here from ``benchmarks/serve_throughput.py``
(which still RECORDS its ratios in BENCH_serve.json, but no longer
asserts them) — this matrix is the single perf-regression surface.
"""
import argparse
import json
import os

from repro.runtime.scenario import Scenario, run_scenarios

OUT_PATH = os.path.join(os.path.dirname(__file__), "BENCH_matrix.json")
VERSION = 1

ARCHS = {
    "qwen": ("qwen1.5-0.5b", "dense"),
    "moe": ("granite-moe-1b-a400m", "moe"),
    "mamba": ("mamba2-1.3b", "ssm"),
    "hybrid": ("zamba2-2.7b", "hybrid"),
    "whisper": ("whisper-tiny", "audio"),
    "llava": ("llava-next-34b", "vlm"),
}

GATE_NAMES = frozenset({
    "cell_coverage", "dispatch_ok", "no_silent_fallback",
    "trajectory_regression", "packed_over_qdq_decode",
    "hif4_over_bf16_kv_decode", "guard_overhead", "journal_overhead",
    "recovery_replay", "searched_policy_frontier",
})

# the crash+resume cell recovery_replay inspects
RECOVERY_CELL = "qwen-packed-hif4-recovery"

# the calibration-searched policy cell searched_policy_frontier inspects:
# `repro calibrate` is run at the CALIBRATION_BASELINE preset's measured
# byte budget, the emitted policy lands at SEARCHED_POLICY, and the cell
# serves it through the normal --policy <file> path
CALIBRATION_CELL = "qwen-packed-hif4-searched"
CALIBRATION_BASELINE = "sensitive-fallback"
SEARCHED_POLICY = os.path.join(os.path.dirname(__file__),
                               "searched_policy.json")


def build_calibration(log=print) -> dict:
    """Run the calibrator for the searched cell: emit SEARCHED_POLICY at
    the baseline preset's byte budget and return the gate summary that
    lands in record["calibration"]."""
    from repro.calibrate import calibrate

    s = calibrate("qwen1.5-0.5b", reduced=True,
                  target_bpv=CALIBRATION_BASELINE, kv_format="hif4",
                  out=SEARCHED_POLICY, log=log)
    fb = s["baselines"][CALIBRATION_BASELINE]
    return {
        "cell": CALIBRATION_CELL,
        "policy": os.path.basename(SEARCHED_POLICY),
        "arch": s["arch"],
        "target": CALIBRATION_BASELINE,
        "budget_met": s["feasible"],
        "n_sites": s["n_sites"],
        "searched": {"total_bytes": s["total_bytes"],
                     "total_error": round(s["total_error"], 3),
                     "bpv": s["achieved_bpv"]},
        "baseline": {"total_bytes": fb["total_bytes"],
                     "total_error": round(fb["total_error"], 3),
                     "bpv": fb["achieved_bpv"]},
    }

# value = baseline decode_step_ms / subject decode_step_ms; the subject
# must hold >= min_ratio of the baseline's decode rate. Both sides of
# each ratio are timed interleaved in the same loop, so load phases
# cancel — these are the two hand-coded serve gates, now matrix cells.
RATIO_GATES = (
    {"name": "packed_over_qdq_decode", "subject": "qwen-packed-bf16",
     "baseline": "qwen-qdq-bf16", "min_ratio": 0.9},
    {"name": "hif4_over_bf16_kv_decode", "subject": "qwen-packed-hif4",
     "baseline": "qwen-packed-bf16", "min_ratio": 0.9},
    # guarded decode (NaN scan sentinel + per-chunk 0xFF meta audit) must
    # hold >= 0.98x of the unguarded cell's decode rate — the "guards are
    # nearly free" claim of the failure-semantics docs (<= ~1.02x cost)
    {"name": "guard_overhead", "subject": "qwen-packed-hif4-guarded",
     "baseline": "qwen-packed-hif4", "min_ratio": 0.98},
    # the write-ahead journal (record framing + one fsync per decode
    # chunk) must hold >= 0.98x of the chunk-matched unjournaled paged
    # cell's decode rate — durable bookkeeping is nearly free. Pool
    # checkpoints are a cadence knob timed by the recovery cell, not
    # ratio-gated here: at benchmark-cell scale (2-token chunks) any
    # cadence is absurdly dense relative to real serving.
    {"name": "journal_overhead",
     "subject": "qwen-packed-hif4-paged-journaled",
     "baseline": "qwen-packed-hif4-paged-chunked", "min_ratio": 0.98},
)


def _expect(family: str, impl: str, kv: str, paged: bool = False) -> tuple:
    """The dispatch assertions a (family, impl, kv_format) cell must pass —
    the single source of truth the cell declarations below draw from."""
    if kv == "hif4":
        if family == "ssm":
            e = ["kv:bf16", "kv:fallback", "attn:none"]
        elif family == "hybrid":
            e = ["kv:bf16", "kv:fallback", "attn:dense"]
        else:
            e = ["kv:hif4", "kv:no-fallback"]
            if paged:
                e.append("attn:fused_paged_decode_attention")
            elif impl in ("packed", "pallas") and family != "vlm":
                e.append("attn:fused_decode_attention")
            else:
                # qdq always takes the dense twin; so does the reduced vlm
                # arch, whose 1 kv-head x 32 d_head = 32 features/token is
                # below one 64-elem HiF4 group — the packed cache is
                # tail-only and the fused kernel is ineligible by design
                e.append("attn:twin")
    else:
        e = ["kv:bf16", "kv:no-fallback",
             "attn:none" if family == "ssm" else "attn:dense"]
    # hybrid's doubly-stacked blocks never pack; qdq fake-quants dense dots
    e.append("matmul:qdq" if (family == "hybrid" or impl == "qdq")
             else "matmul:fused")
    return tuple(e)


def _cells() -> tuple:
    cells = []
    # every family x every impl on the requested-hif4 column
    for short, (arch, family) in ARCHS.items():
        for impl in ("qdq", "packed", "pallas"):
            cells.append(Scenario(
                name=f"{short}-{impl}-hif4", arch=arch, impl=impl,
                kv_format="hif4", expect=_expect(family, impl, "hif4")))
    # every family on the bf16 column (packed impl), + the qdq baseline
    # the packed_over_qdq_decode ratio gate compares against
    for short, (arch, family) in ARCHS.items():
        cells.append(Scenario(
            name=f"{short}-packed-bf16", arch=arch, impl="packed",
            kv_format="bf16", expect=_expect(family, "packed", "bf16")))
    cells.append(Scenario(
        name="qwen-qdq-bf16", arch="qwen1.5-0.5b", impl="qdq",
        kv_format="bf16", expect=_expect("dense", "qdq", "bf16")))
    # mixed-policy presets on the packed path (dense + moe)
    for short in ("qwen", "moe"):
        arch, family = ARCHS[short]
        for policy in ("paper-iv", "sensitive-fallback"):
            cells.append(Scenario(
                name=f"{short}-packed-hif4-{policy}", arch=arch,
                impl="packed", kv_format="hif4", policy=policy,
                expect=_expect(family, "packed", "hif4")))
    # paged-hif4 page-pool cells (continuous-batching scheduler e2e)
    for short in ("qwen", "moe"):
        arch, family = ARCHS[short]
        cells.append(Scenario(
            name=f"{short}-packed-hif4-paged", arch=arch, impl="packed",
            kv_format="hif4", paged=True, rel_tol=4.0,
            expect=_expect(family, "packed", "hif4", paged=True)))
    # crash-safety column on the hot paged cell: a chunk-matched
    # unjournaled baseline, its journaled twin (journal_overhead gate),
    # and the crash+resume recovery cell (recovery_replay gate)
    cells.append(Scenario(
        name="qwen-packed-hif4-paged-chunked", arch="qwen1.5-0.5b",
        impl="packed", kv_format="hif4", paged=True, decode_chunk=2,
        rel_tol=4.0, expect=_expect("dense", "packed", "hif4", paged=True)))
    cells.append(Scenario(
        name="qwen-packed-hif4-paged-journaled", arch="qwen1.5-0.5b",
        impl="packed", kv_format="hif4", paged=True, journaled=True,
        decode_chunk=2, rel_tol=4.0,
        expect=_expect("dense", "packed", "hif4", paged=True)))
    cells.append(Scenario(
        name="qwen-packed-hif4-recovery", arch="qwen1.5-0.5b",
        impl="packed", kv_format="hif4", paged=True, journaled=True,
        recovery=True, decode_chunk=2, rel_tol=6.0,
        expect=_expect("dense", "packed", "hif4", paged=True)))
    # the calibration-searched policy on the hot dense cell: the emitted
    # file is regenerated by build_calibration() before this cell runs
    # (searched_policy_frontier gate). No matmul expectation: which sites
    # the search packs is DATA — plan.base (the attention-site config)
    # legitimately lands on bf16 when the probe measures wq/wk/wv as the
    # sensitive sites, while the mlp matmuls still serve PackedW fused.
    cells.append(Scenario(
        name=CALIBRATION_CELL, arch="qwen1.5-0.5b", impl="packed",
        kv_format="hif4", policy=SEARCHED_POLICY,
        expect=("kv:hif4", "kv:no-fallback",
                "attn:fused_decode_attention")))
    # the guarded twin of the hot dense cell (guard_overhead gate subject)
    cells.append(Scenario(
        name="qwen-packed-hif4-guarded", arch="qwen1.5-0.5b", impl="packed",
        kv_format="hif4", guarded=True,
        expect=_expect("dense", "packed", "hif4")))
    # batch / seqlen variation on the hot dense cell
    cells.append(Scenario(
        name="qwen-packed-hif4-b4", arch="qwen1.5-0.5b", impl="packed",
        kv_format="hif4", batch=4, expect=_expect("dense", "packed", "hif4")))
    cells.append(Scenario(
        name="qwen-packed-hif4-long", arch="qwen1.5-0.5b", impl="packed",
        kv_format="hif4", prompt_len=48, new_tokens=16,
        expect=_expect("dense", "packed", "hif4")))
    cells.append(Scenario(
        name="llava-packed-hif4-b4", arch="llava-next-34b", impl="packed",
        kv_format="hif4", batch=4, expect=_expect("vlm", "packed", "hif4")))
    return tuple(cells)


CELLS = _cells()

SMOKE = ("qwen-qdq-bf16", "qwen-packed-bf16", "qwen-packed-hif4",
         "whisper-packed-hif4", "mamba-packed-hif4", "qwen-packed-hif4-paged")


def compute_ratio_gates(by_name: dict) -> list:
    """Ratio gates prefer the subject cell's ``gate_timing`` entry for
    their baseline — the tight pairwise A/B interleave (see
    scenario.run_scenarios) that keeps both sides under identical
    machine conditions — and fall back to the global-rotation
    ``decode_step_ms`` when a run didn't produce one (subset runs,
    synthetic records)."""
    out = []
    for g in RATIO_GATES:
        sub, base = by_name.get(g["subject"]), by_name.get(g["baseline"])
        value = None
        if sub and base:
            gt = (sub.get("gate_timing") or {}).get(g["baseline"])
            if gt:
                value = round(gt["baseline_ms"] / gt["subject_ms"], 3)
            else:
                value = round(
                    base["decode_step_ms"] / sub["decode_step_ms"], 3)
        out.append({**g, "value": value})
    return out


def check(record: dict, *, min_cells: int = 30) -> None:
    """Static gates on a (committed) BENCH_matrix.json record — raises
    AssertionError on any violation, loudly naming the gate."""
    assert record.get("version") == VERSION, (
        f"BENCH_matrix.json version {record.get('version')!r} != {VERSION}")
    cells = record.get("cells")
    assert cells, "BENCH_matrix.json has no cells"
    names = [c["name"] for c in cells]
    assert len(set(names)) == len(names), f"duplicate cell names: {names}"
    by_name = {c["name"]: c for c in cells}

    # gate: cell_coverage
    families = {c["family"] for c in cells}
    impls = {c["impl"] for c in cells}
    assert len(cells) >= min_cells, (
        f"cell_coverage gate: {len(cells)} cells < {min_cells}")
    assert families >= {"dense", "moe", "ssm", "hybrid", "audio", "vlm"}, (
        f"cell_coverage gate: families {sorted(families)} miss a family")
    assert impls >= {"qdq", "packed", "pallas"}, (
        f"cell_coverage gate: impls {sorted(impls)} miss an impl")

    for c in cells:
        # every cell must carry measurement + prediction + assertions
        for field in ("decode_step_ms", "roofline", "dispatch", "expect",
                      "rel_tol"):
            assert c.get(field) is not None, (
                f"cell {c['name']}: missing `{field}`")
        for field in ("bytes_per_step", "predicted_ms", "achieved_fraction"):
            assert c["roofline"].get(field) is not None, (
                f"cell {c['name']}: roofline missing `{field}`")
        # gate: dispatch_ok
        assert c.get("dispatch_ok") is True, (
            f"dispatch_ok gate: cell {c['name']} failed its expected-"
            f"dispatch assertions: {c.get('dispatch_failures')}")
        # gate: no_silent_fallback — a narrowed kv_format is only legal
        # when the cell DECLARED itself an expected-fallback cell
        if c["dispatch"]["kv_format_fallback"]:
            assert "kv:fallback" in c["expect"], (
                f"no_silent_fallback gate: cell {c['name']} fell back "
                f"{c['kv_format']}->{c['kv_format_resolved']} without "
                f"declaring kv:fallback")
        # the enc-dec families must serve the real format (cross-attention
        # KV packs — the permanent-fallback cells are gone)
        if c["family"] in ("audio", "vlm") and c["kv_format"] == "hif4":
            assert not c["dispatch"]["kv_format_fallback"], (
                f"no_silent_fallback gate: enc-dec cell {c['name']} must "
                f"serve packed HiF4 KV, not fall back")

    # gates: packed_over_qdq_decode, hif4_over_bf16_kv_decode
    gates = {g["name"]: g for g in record.get("ratio_gates", [])}
    for g in RATIO_GATES:
        got = gates.get(g["name"])
        assert got is not None, (
            f"{g['name']} gate missing from BENCH_matrix.json ratio_gates")
        both = g["subject"] in by_name and g["baseline"] in by_name
        if got["value"] is None:
            assert not both, (
                f"{g['name']} gate: value null although both cells "
                f"({g['subject']}, {g['baseline']}) are in the matrix — "
                f"the gate was skipped, not inapplicable")
        else:
            assert got["value"] >= g["min_ratio"], (
                f"{g['name']} gate: {got['value']}x < {g['min_ratio']}x "
                f"({g['subject']} vs {g['baseline']})")

    # gate: recovery_replay — the crash+resume cell crashed for real,
    # recovered every request bitwise, and recorded its recovery timings
    rc = by_name.get(RECOVERY_CELL)
    assert rc is not None, (
        f"recovery_replay gate: cell {RECOVERY_CELL} missing from matrix")
    rec = rc.get("recovery")
    assert rec, (
        f"recovery_replay gate: cell {RECOVERY_CELL} has no recovery report")
    assert rec.get("crashed") is True, (
        f"recovery_replay gate: the injected crash never fired: {rec}")
    assert rec.get("bitwise") is True, (
        f"recovery_replay gate: recovered outputs are NOT bitwise "
        f"identical to the uninterrupted run: {rec}")
    for field in ("recovery_ms", "resume_ms", "verified"):
        assert rec.get(field) is not None, (
            f"recovery_replay gate: recovery report missing `{field}`: "
            f"{rec}")

    # gate: searched_policy_frontier — the calibration-searched policy
    # must Pareto-match the hand-written fallback preset: <= its bytes at
    # <= its error on the same calibration set, and the cell must have
    # actually served the searched file through the --policy path
    cal = record.get("calibration")
    assert cal, ("searched_policy_frontier gate: record has no "
                 "`calibration` section")
    cc = by_name.get(CALIBRATION_CELL)
    assert cc is not None, (
        f"searched_policy_frontier gate: cell {CALIBRATION_CELL} missing "
        f"from matrix")
    assert str(cc.get("policy", "")).endswith(".json"), (
        f"searched_policy_frontier gate: cell {CALIBRATION_CELL} did not "
        f"serve a policy FILE: {cc.get('policy')!r}")
    assert cal.get("budget_met") is True, (
        f"searched_policy_frontier gate: search missed the "
        f"{cal.get('target')!r} byte budget: {cal}")
    sr, fb = cal.get("searched"), cal.get("baseline")
    assert sr and fb, (
        f"searched_policy_frontier gate: calibration section incomplete: "
        f"{cal}")
    assert sr["total_bytes"] <= fb["total_bytes"], (
        f"searched_policy_frontier gate: searched policy resident bytes "
        f"{sr['total_bytes']} > {cal['target']} baseline "
        f"{fb['total_bytes']}")
    assert sr["total_error"] <= fb["total_error"], (
        f"searched_policy_frontier gate: searched policy calibration "
        f"error {sr['total_error']} > {cal['target']} baseline "
        f"{fb['total_error']} at <= its bytes")


def compare(stored: dict, fresh_cells: list) -> list:
    """gate: trajectory_regression — fresh measurements vs the stored
    trajectory, per-cell rel_tol. Returns failure strings (empty = pass)."""
    by_name = {c["name"]: c for c in stored.get("cells", [])}
    failures = []
    for c in fresh_cells:
        ref = by_name.get(c["name"])
        if ref is None:
            continue
        limit = ref["decode_step_ms"] * c["rel_tol"]
        if c["decode_step_ms"] > limit:
            failures.append(
                f"trajectory_regression gate: cell {c['name']} decode "
                f"{c['decode_step_ms']} ms/step > stored "
                f"{ref['decode_step_ms']} * rel_tol {c['rel_tol']} "
                f"= {round(limit, 4)} ms")
        for e in ref.get("expect", []):
            if e not in c["expect"]:
                failures.append(
                    f"cell {c['name']} dropped expectation {e!r} vs stored")
    return failures


def main(argv=None):
    import jax

    from benchmarks import roofline
    from repro.jax_setup import configure_jax

    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="smoke",
                    help="'all', 'smoke', or comma-separated cell names")
    ap.add_argument("--repeats", type=int, default=7)
    ap.add_argument("--update", action="store_true",
                    help="write BENCH_matrix.json (requires --cells all)")
    args = ap.parse_args(argv)
    configure_jax()

    if args.cells == "all":
        cells = CELLS
    elif args.cells == "smoke":
        cells = tuple(c for c in CELLS if c.name in SMOKE)
    else:
        wanted = set(args.cells.split(","))
        unknown = wanted - {c.name for c in CELLS}
        assert not unknown, f"unknown cells: {sorted(unknown)}"
        cells = tuple(c for c in CELLS if c.name in wanted)

    mem_bw = roofline.measure_stream_bandwidth()
    print(f"[matrix] backend={jax.default_backend()} "
          f"stream bandwidth {mem_bw / 2**30:.1f} GiB/s, "
          f"{len(cells)} cells")
    calibration = None
    if any(c.name == CALIBRATION_CELL for c in cells):
        # the searched cell serves a file the calibrator emits: (re)build
        # it now so the cell always serves THIS run's search
        calibration = build_calibration()
        print(f"[matrix] calibration: searched "
              f"{calibration['searched']['total_bytes']} B / err "
              f"{calibration['searched']['total_error']} vs "
              f"{calibration['target']} {calibration['baseline']['total_bytes']} "
              f"B / err {calibration['baseline']['total_error']}")
    gate_pairs = tuple((g["baseline"], g["subject"]) for g in RATIO_GATES)
    results = run_scenarios(cells, repeats=args.repeats,
                            gate_pairs=gate_pairs)
    for c in results:
        ro = c["roofline"]
        ro["mem_bw"] = round(mem_bw)
        ro["predicted_ms"] = round(
            roofline.predict_step_ms(ro["bytes_per_step"], mem_bw), 6)
        ro["achieved_fraction"] = round(
            ro["predicted_ms"] / c["decode_step_ms"], 6)

    bad = [c for c in results if not c["dispatch_ok"]]
    for c in results:
        ro = c["roofline"]
        print(f"{c['name']:28} decode {c['decode_step_ms']:9.3f} ms/step  "
              f"roofline {ro['predicted_ms']:8.4f} ms "
              f"({ro['achieved_fraction'] * 100:6.2f}% of stream bw)  "
              f"kv={c['kv_format_resolved']:5} "
              f"{'OK' if c['dispatch_ok'] else 'DISPATCH-FAIL'}")
    assert not bad, (
        "dispatch_ok gate: cells failed their expected-dispatch "
        "assertions: "
        + "; ".join(f"{c['name']}: {c['dispatch_failures']}" for c in bad))

    record = {
        "version": VERSION,
        "backend": jax.default_backend(),
        "mem_bw": mem_bw,
        "repeats": args.repeats,
        "ratio_gates": compute_ratio_gates({c["name"]: c for c in results}),
        "cells": results,
    }
    if calibration is not None:
        record["calibration"] = calibration
        assert calibration["budget_met"], (
            "searched_policy_frontier gate: search missed the "
            f"{calibration['target']!r} byte budget")
        assert (calibration["searched"]["total_bytes"]
                <= calibration["baseline"]["total_bytes"]), calibration
        assert (calibration["searched"]["total_error"]
                <= calibration["baseline"]["total_error"]), calibration
        print(f"[gate] searched_policy_frontier: "
              f"{calibration['searched']['total_bytes']} B <= "
              f"{calibration['baseline']['total_bytes']} B, err "
              f"{calibration['searched']['total_error']} <= "
              f"{calibration['baseline']['total_error']}")
    for g in record["ratio_gates"]:
        if g["value"] is not None:
            print(f"[gate] {g['name']}: {g['value']}x (min {g['min_ratio']}x)")
            assert g["value"] >= g["min_ratio"], (
                f"{g['name']} gate: {g['value']}x < {g['min_ratio']}x")

    if os.path.exists(OUT_PATH):
        with open(OUT_PATH) as f:
            stored = json.load(f)
        failures = compare(stored, results)
        if failures:
            raise AssertionError(
                "matrix regression vs stored trajectory:\n  "
                + "\n  ".join(failures))
        print(f"[matrix] {len(results)} cells within tolerance of the "
              f"stored trajectory")

    if args.update:
        assert args.cells == "all", "--update requires --cells all"
        check(record)
        with open(OUT_PATH, "w") as f:
            json.dump(record, f, indent=1)
        print(f"wrote {OUT_PATH}")


if __name__ == "__main__":
    main()

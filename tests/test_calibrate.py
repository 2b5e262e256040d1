"""Calibration subsystem: frontier-search properties + probe/emit e2e.

Three layers of pinning:

* pure search (no model): hypothesis property tests — raising the byte
  target never increases total error and never decreases total bytes
  (the greedy applies a PREFIX of one fixed move order), hull dominance,
  budget semantics, and ``assignment_cost`` agreement;
* policy JSON strictness: ``QuantPolicy.from_json_dict`` rejects unknown
  top-level and rule keys loudly (a typo'd key must never silently yield
  the default policy), and provenance survives the round-trip;
* probe + emit e2e (slow): one real calibration run on the reduced dense
  arch — tap capture through the real forward, searched policy emitted,
  reloaded via ``get_policy``, resolved, and served through a prefill +
  decode step with packed weights.
"""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro.calibrate.search import (
    FormatOption,
    SiteScore,
    _hull,
    assignment_cost,
    frontier_search,
)
from repro.core.policy import QuantPolicy, QuantRule, get_policy

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:          # property tests skip; the rest still run
    hypothesis = st = None


# ---------------------------------------------------------------------------
# search: property tests (satellite: frontier monotonicity)
# ---------------------------------------------------------------------------

FMTS = ("bf16", "hif4", "nvfp4", "mxfp4", "int8")
BPV = {"bf16": 2.0, "int8": 1.0, "nvfp4": 0.75, "mxfp4": 0.75,
       "hif4": 0.5625}

if hypothesis is not None:
    hypothesis.settings.register_profile(
        "ci", deadline=None, max_examples=60, derandomize=True)
    hypothesis.settings.load_profile("ci")

    @st.composite
    def site_tables(draw):
        n_sites = draw(st.integers(min_value=1, max_value=6))
        sites = []
        for i in range(n_sites):
            fmts = draw(st.sets(st.sampled_from(FMTS), min_size=1,
                                max_size=5))
            opts = tuple(
                FormatOption(f, BPV[f],
                             draw(st.floats(min_value=0.0, max_value=10.0,
                                            allow_nan=False)))
                for f in sorted(fmts))
            sites.append(SiteScore(
                path=f"site{i}",
                n_values=draw(st.integers(min_value=64, max_value=8192)),
                options=opts))
        return sites

    @hypothesis.given(site_tables(),
                      st.floats(min_value=0.4, max_value=2.2),
                      st.floats(min_value=0.0, max_value=0.8))
    def test_frontier_monotone_in_target(sites, t_lo, dt):
        """Raising --target-bpv never increases error nor shrinks bytes."""
        lo = frontier_search(sites, t_lo)
        hi = frontier_search(sites, t_lo + dt)
        assert hi.total_error <= lo.total_error + 1e-9
        assert hi.total_bytes >= lo.total_bytes - 1e-9

    @hypothesis.given(site_tables(), st.floats(min_value=0.4, max_value=2.2))
    def test_frontier_internal_consistency(sites, target):
        """Totals match the assignment, budget semantics hold, and the
        curve is monotone (bytes strictly down, error up)."""
        r = frontier_search(sites, target)
        b, e = assignment_cost(sites, r.assignment)
        assert abs(b - r.total_bytes) < 1e-6
        assert abs(e - r.total_error) < 1e-6
        n_total = sum(s.n_values for s in sites)
        if r.feasible:
            assert r.total_bytes <= target * n_total + 1e-6
        else:
            # infeasible = even the cheapest point exceeds the budget: the
            # returned assignment IS the cheapest (last curve point)
            assert abs(r.total_bytes - r.curve[-1]["total_bytes"]) < 1e-6
        for a, c in zip(r.curve, r.curve[1:]):
            assert c["total_bytes"] < a["total_bytes"]
            assert c["total_error"] >= a["total_error"] - 1e-9


def test_hull_dominance():
    h = _hull([
        FormatOption("bf16", 2.0, 0.0),
        FormatOption("worse-same-bytes", 2.0, 1.0),     # dominated
        FormatOption("bigger-and-worse", 3.0, 0.5),     # dominated
        FormatOption("hif4", 0.5625, 0.3),
        FormatOption("concave", 1.0, 0.29),             # off the hull
    ])
    assert [o.fmt for o in h] == ["bf16", "hif4"]
    # ratios non-decreasing as bytes shrink
    for a, b in zip(h, h[1:]):
        assert b.bytes_per_value < a.bytes_per_value
        assert b.error > a.error


def test_greedy_stops_at_budget():
    sites = [
        SiteScore("a", 1000, (FormatOption("bf16", 2.0, 0.0),
                              FormatOption("hif4", 0.5625, 1.0))),
        SiteScore("b", 1000, (FormatOption("bf16", 2.0, 0.0),
                              FormatOption("hif4", 0.5625, 5.0))),
    ]
    # budget allows quantizing only one site: the cheaper-error one moves
    r = frontier_search(sites, 1.3)
    assert r.feasible
    assert r.assignment == {"a": "hif4", "b": "bf16"}
    # full curve still walks both moves
    assert len(r.curve) == 3
    # generous budget: nothing moves
    r2 = frontier_search(sites, 2.0)
    assert r2.assignment == {"a": "bf16", "b": "bf16"}
    assert r2.total_error == 0.0


def test_assignment_cost_unknown_fmt_falls_back():
    s = SiteScore("a", 100, (FormatOption("bf16", 2.0, 0.5),
                             FormatOption("hif4", 0.5625, 1.0)))
    b, e = assignment_cost([s], {"a": "int8"})     # not offered
    assert (b, e) == (200.0, 0.5 * 100)            # min-error option


# ---------------------------------------------------------------------------
# policy JSON strictness (satellite: from_json_dict rejects unknown keys)
# ---------------------------------------------------------------------------

def test_from_json_dict_rejects_unknown_top_level_key():
    d = {"name": "x", "ruels": [{"pattern": "*", "fmt": "hif4"}]}
    with pytest.raises(ValueError, match="ruels"):
        QuantPolicy.from_json_dict(d)


def test_from_json_dict_rejects_unknown_rule_key():
    d = {"rules": [{"pattern": "*", "fmt": "hif4", "weights_onyl": True}]}
    with pytest.raises(ValueError, match="weights_onyl"):
        QuantPolicy.from_json_dict(d)


def test_from_json_dict_accepts_all_known_keys_and_roundtrips():
    pol = QuantPolicy(
        rules=(QuantRule("*", fmt="none"),
               QuantRule("blocks.mlp.wg", fmt="hif4", weights_only=True)),
        name="rt").with_provenance({"tool": "test", "n": 1})
    d = pol.to_json_dict()
    back = QuantPolicy.from_json_dict(json.loads(json.dumps(d)))
    assert back.rules == pol.rules
    assert back.name == "rt"
    assert back.provenance_dict() == {"tool": "test", "n": 1}


def test_get_policy_file_rejects_typo_key(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"rules": [], "kv_fromat": "hif4"}))
    with pytest.raises(ValueError, match="kv_fromat"):
        get_policy(str(p))


# ---------------------------------------------------------------------------
# emit: assignment -> policy file -> get_policy -> resolved plan
# ---------------------------------------------------------------------------

def test_emit_policy_roundtrip_resolves_to_assignment(tmp_path):
    from repro.calibrate.emit import emit_policy
    from repro.configs import get_arch
    from repro.models import lm

    cfg = get_arch("qwen1.5-0.5b").reduced()
    assignment = {"blocks.attn.wq": "bf16", "blocks.attn.wk": "hif4",
                  "blocks.attn.wv": "hif4", "blocks.attn.wo": "bf16",
                  "blocks.mlp.wg": "hif4", "blocks.mlp.wu": "bf16",
                  "blocks.mlp.wo": "hif4"}
    out = str(tmp_path / "policy.json")
    emit_policy(assignment, name="t", kv_format="hif4",
                provenance={"tool": "test"}, out=out)
    pol = get_policy(out, impl="packed")
    assert pol.provenance_dict()["tool"] == "test"
    assert pol.kv.kv_format == "hif4"
    plan = lm.quant_plan(cfg, pol)
    want_packed = {p for p, f in assignment.items() if f == "hif4"}
    assert plan.packed_paths == frozenset(want_packed)
    for path, fmt in assignment.items():
        got = plan.at(path).fmt
        assert got == ("none" if fmt == "bf16" else fmt), (path, got)


def test_measure_bandwidth_is_positive():
    """``--measure-bw``'s stream bandwidth: a finite positive rate."""
    from repro.calibrate.run import measure_bandwidth

    bw = measure_bandwidth(nbytes=1 << 16, repeats=2)
    assert 0 < bw < float("inf")


# ---------------------------------------------------------------------------
# probe + calibrate e2e on the real model (slow)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def calib(tmp_path_factory):
    from repro.calibrate import calibrate

    d = tmp_path_factory.mktemp("calib")
    out = str(d / "searched.json")
    report = str(d / "report.json")
    summary = calibrate("qwen1.5-0.5b", target_bpv=0.7, out=out,
                        report_out=report, log=lambda *_: None)
    return summary, out, report


@pytest.mark.slow
def test_probe_tap_captures_all_matmul_sites():
    from repro.calibrate.probe import probe_sites
    from repro.configs import get_arch

    cfg = get_arch("qwen1.5-0.5b").reduced()
    res = probe_sites(cfg, n_batches=1, batch=1, seq_len=32,
                      log=lambda *_: None)
    by_path = {r["path"]: r for r in res.rows}
    # every body matmul site scored with real activations; embed (a
    # gather, never consumed by the engine funnel) excluded from both
    # capture and budget; tied lm_head captured but out of budget
    body = {"blocks.attn.wq", "blocks.attn.wk", "blocks.attn.wv",
            "blocks.attn.wo", "blocks.mlp.wg", "blocks.mlp.wu",
            "blocks.mlp.wo"}
    for p in body:
        assert by_path[p]["captured"] and by_path[p]["in_budget"]
        errs = by_path[p]["errors"]
        assert errs["hif4"] > 0 and errs["bf16"] == 0.0
        # HiGPTQ rounding must not be WORSE than direct-cast on the
        # calibration set it optimizes (allow float-mean slack)
        assert errs["hif4"] <= errs["hif4_direct"] * 1.25
    assert not by_path["embed"]["in_budget"]
    assert not by_path["embed"]["captured"]
    assert by_path["lm_head"]["captured"]
    assert not by_path["lm_head"]["in_budget"]      # tied: no tensor
    assert res.n_calib_rows > 0


@pytest.mark.slow
def test_calibrate_emits_within_budget_and_beats_fallback(calib):
    summary, out, report_path = calib
    assert summary["feasible"]
    # (b) budget met as measured by the resolved plan's packed residency
    assert summary["achieved_bpv"] <= 0.7
    # (c) frontier claim on the same calibration set: at the fallback's
    # byte budget the searched assignment's error is <= the preset's
    # (checked by test_calibrate_at_fallback_budget_pareto_dominates;
    # here: the baseline entry exists and the searched-at-equal-bytes run
    # is reproducible)
    fb = summary["baselines"]["sensitive-fallback"]
    assert fb["total_bytes"] > summary["total_bytes"]    # 0.7 < 0.99375
    rep = json.load(open(report_path))
    assert rep["search"]["assignment"] == summary["assignment"]
    assert len(rep["pareto_curve"]) >= 2
    # curve bytes strictly decreasing, error non-decreasing
    curve = rep["pareto_curve"]
    assert all(b["total_bytes"] < a["total_bytes"]
               for a, b in zip(curve, curve[1:]))


@pytest.mark.slow
def test_calibrate_at_fallback_budget_pareto_dominates(calib):
    """The acceptance comparison: search AT the fallback preset's byte
    residency -> <= its bytes and <= its error on the same score table."""
    from repro.calibrate.search import frontier_search
    _, _, report_path = calib
    rep = json.load(open(report_path))
    sites = []
    for r in rep["sites"]:
        if not r["in_budget"]:
            continue
        opts = [FormatOption("bf16", 2.0, 0.0)]
        if r["packable"]:
            opts.append(FormatOption("hif4", 0.5625, r["errors"]["hif4"]))
        sites.append(SiteScore(r["path"], r["n_values"], tuple(opts)))
    fb = rep["baselines"]["sensitive-fallback"]
    f = frontier_search(sites, fb["total_bytes"]
                        / sum(s.n_values for s in sites))
    assert f.feasible
    assert f.total_bytes <= fb["total_bytes"]
    assert f.total_error <= fb["total_error"] + 1e-6


@pytest.mark.slow
def test_searched_policy_serves_end_to_end(calib):
    """The emitted file rides the real packed serve loop untouched."""
    import jax

    from repro.configs import get_arch
    from repro.core import kvcache
    from repro.models import lm
    from repro.models.common import ModelCtx
    from repro.runtime import serve_loop
    from repro.runtime.scenario import prefill_batch, probe_dispatch

    _, out, _ = calib
    cfg = get_arch("qwen1.5-0.5b").reduced()
    plan = lm.quant_plan(cfg, get_policy(
        out, impl="packed", kv=kvcache.KVCacheConfig("hif4")))
    ctx = ModelCtx(quant=plan.base, plan=plan, remat=False,
                   attn_q_chunk=8, attn_k_chunk=8)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    sp = serve_loop.prepare_params_for_serving(params, cfg, plan)
    sc = serve_loop.ServeConfig(max_new_tokens=4, kv_format="hif4")
    d = probe_dispatch(cfg, ctx.quant, sc, sp, batch=1, prompt_len=8)
    assert d["kv_format_resolved"] == "hif4"
    assert not d["kv_format_fallback"]
    toks = serve_loop.serve(cfg, sp, prefill_batch(cfg, 1, 8), ctx, sc)
    assert toks.shape == (1, sc.max_new_tokens)
    assert serve_loop.packed_weight_bytes(sp)[0] > 0

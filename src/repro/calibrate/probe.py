"""Sensitivity probe: per-site quantization-error scores from one bf16 pass.

Runs the calibration batches through the model ONCE in bf16 with the
per-site activation tap installed (``repro.core.tap`` — the capture hooks
ride ``ModelCtx.site_quant`` and the engine funnel, so every family's
dense/qbmm sites record without model changes), then scores every site
the resolved :class:`~repro.core.policy.QuantPlan` enumerates:

* **error per format** (``repro.core.metrics.QDQ_FORMATS`` + bf16):
  relative layer-output error ``||X(W - Wq)||_F / ||X W||_F``
  (``repro.core.metrics.rel_output_error``) against the site's captured
  activations, per layer, averaged over the stack. HiF4 is additionally
  scored with HiGPTQ offline rounding (``repro.core.higptq``) wherever
  the site structurally admits an offline artifact — that rounded score
  is what serving would actually ship, so it is the one the frontier
  search prices;
* **byte residency per format**: 0.5625 B/value for HiF4 on a packable
  site (the PackedW payload), 2 B/value (bf16 at rest) everywhere else —
  matching exactly what ``prepare_params_for_serving`` + the plan's
  ``packed_paths`` would make resident;
* **roofline latency contribution**: site bytes / measured stream
  bandwidth (``repro.calibrate.run.measure_bandwidth``), when a
  bandwidth is supplied.

The probe is model-agnostic: site enumeration, packability and
contraction axes all come from plan resolution, and activations come
from the tap, so any family ``lm._backbone`` serves (dense / moe / ssm /
hybrid / vlm / audio) probes through the same code path.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ArchConfig
from repro.core import tap as site_tap
from repro.core.higptq import higptq_quantize
from repro.core.metrics import QDQ_FORMATS, rel_output_error
from repro.core.policy import QuantPlan, get_policy
from repro.models import lm
from repro.models.common import ModelCtx

# byte costs at rest: the PackedW payload (4.5-bit codes + scale metadata,
# see repro.core.qlinear.PackedW) vs bf16
PACKED_BPV = 0.5625
DENSE_BPV = 2.0

# sites the byte budget governs are the matmul weight sites that own a
# resident tensor: "embed" is a gather table the policy clamps to
# fmt='none', and a tied "lm_head" owns no tensor of its own (it reads
# embed.T) — neither can trade bytes, so neither enters the budget
# (see _in_budget).


@dataclasses.dataclass(frozen=True)
class CalibrationResult:
    """Everything the search + emitter need, plus the audit rows."""

    arch: str
    family: str
    plan: QuantPlan              # uniform:hif4/packed reference resolution
    rows: tuple                  # per-site audit dicts (report schema)
    n_batches: int
    seq_len: int
    batch: int
    seed: int
    n_calib_rows: int            # activation rows captured per site (min)
    mem_bw: Optional[float]      # bytes/s, None = no roofline measurement

    def site_scores(self):
        """The searchable score table (``repro.calibrate.search``)."""
        from repro.calibrate.search import FormatOption, SiteScore

        out = []
        for r in self.rows:
            if not r["in_budget"]:
                continue
            opts = [FormatOption("bf16", DENSE_BPV, 0.0)]
            if r["packable"]:
                opts.append(FormatOption(
                    "hif4", PACKED_BPV, r["errors"]["hif4"]))
            out.append(SiteScore(path=r["path"], n_values=r["n_values"],
                                 options=tuple(opts)))
        return out


def _forward(params, batch, cfg, ctx):
    """One captured bf16 forward: prompt -> logits, any family."""
    if cfg.family == "audio":
        bos = jnp.zeros((batch["frames"].shape[0], 4), jnp.int32)
        x = lm.embed_tokens(params, bos, cfg, ctx)
        x = x + lm.sinusoid(jnp.arange(x.shape[1]), cfg.d_model).astype(x.dtype)
        h, _ = lm._backbone(params, x, cfg, ctx, mode="train",
                            frames=batch["frames"])
    elif cfg.embeds_input:
        x = batch["embeds"].astype(ctx.compute_dtype)
        h, _ = lm._backbone(params, x, cfg, ctx, mode="train")
    else:
        x = lm.embed_tokens(params, batch["tokens"], cfg, ctx)
        h, _ = lm._backbone(params, x, cfg, ctx, mode="train")
    return lm.lm_logits(params, h, cfg, ctx)


def _in_budget(site, params) -> bool:
    if site.path == "embed":
        return False
    if site.path == "lm_head" and "lm_head" not in params:
        return False                                  # tied: reads embed.T
    return True


def _site_k(site) -> Optional[int]:
    """Contraction width K of one (stacked) site, from its plan record."""
    if site.contract_axes:
        return int(np.prod([site.shape[a] for a in site.contract_axes]))
    if len(site.shape) >= 2:
        return int(site.shape[0])    # tied lm_head: (d, V) contracts d
    return None


def _stacked(site) -> bool:
    return site.path.split(".")[0] in ("blocks", "shared", "enc_blocks")


def _weight_matrices(params, site) -> list:
    """Per-layer (K, N) contraction-major matrices for one site."""
    node = params
    for part in site.path.split("."):
        if part not in node:
            if site.path == "lm_head":          # tied: reads embed.T
                return [np.asarray(params["embed"], np.float32).T]
            raise KeyError(f"no param tensor at site {site.path!r}")
        node = node[part]
    w = np.asarray(node, np.float32)
    if not _stacked(site):
        ca = site.contract_axes or (0,)
        m = np.moveaxis(w, ca, range(len(ca)))
        return [m.reshape(int(np.prod(m.shape[:len(ca)])), -1)]
    out = []
    ca = tuple(a - 1 for a in site.contract_axes) or (0,)
    for l in range(w.shape[0]):
        m = np.moveaxis(w[l], ca, range(len(ca)))
        out.append(m.reshape(int(np.prod(m.shape[:len(ca)])), -1))
    return out


def _score_site(site, w_layers, x_layers, n_samples: int) -> dict:
    """Per-format mean layer-output error for one site."""
    errors = {f: [] for f in QDQ_FORMATS}
    higptq_errs = []
    from repro.core.formats import get_format

    for w_l, x_l in zip(w_layers, x_layers):
        x_l = x_l[:n_samples]
        for f in QDQ_FORMATS:
            wq = np.asarray(get_format(f).qdq(jnp.asarray(w_l.T))).T
            errors[f].append(rel_output_error(w_l, wq, x_l))
        if site.quantize_offline and w_l.shape[0] % 64 == 0:
            wg = higptq_quantize(jnp.asarray(w_l), jnp.asarray(x_l))
            higptq_errs.append(rel_output_error(w_l, np.asarray(wg), x_l))
    out = {f: float(np.mean(errors[f])) for f in QDQ_FORMATS}
    out["bf16"] = 0.0
    out["hif4_direct"] = out["hif4"]
    if higptq_errs:
        # what serving ships for a packed site: the HiGPTQ-rounded weight
        out["hif4"] = float(np.mean(higptq_errs))
    return out


def probe_sites(cfg: ArchConfig, *, params: Optional[dict] = None,
                n_batches: int = 2, batch: int = 2, seq_len: int = 64,
                seed: int = 0, n_samples: int = 256,
                mem_bw: Optional[float] = None,
                log=print) -> CalibrationResult:
    """Run the calibration pass and score every plan site (see module
    docstring). ``params`` defaults to a seeded random init."""
    from repro.runtime.scenario import prefill_batch

    if params is None:
        params = lm.init_params(cfg, jax.random.PRNGKey(seed))
    plan = lm.quant_plan(cfg, get_policy("uniform:hif4", impl="packed"))
    ctx = ModelCtx(remat=False, attn_q_chunk=8, attn_k_chunk=8)

    expect_k = {}
    for s in plan.sites:
        k = _site_k(s)
        if k is not None and s.path != "embed":
            expect_k[s.path] = k
    t = site_tap.ActivationTap(expect_k=expect_k)
    with jax.disable_jit(), site_tap.capture(t):
        for i in range(n_batches):
            out = _forward(params, prefill_batch(cfg, batch, seq_len,
                                                 seed=seed + i), cfg, ctx)
            jax.block_until_ready(out)
    log(f"[calibrate] probe: {n_batches} batches of ({batch}, {seq_len}) "
        f"through {cfg.family} forward; {len(t.paths())} sites captured")

    rows = []
    n_min = None
    for s in sorted(plan.sites, key=lambda s: s.path):
        in_budget = _in_budget(s, params)
        row = {
            "path": s.path,
            "n_values": s.n_values,
            "shape": list(s.shape),
            "packable": bool(s.packed),
            "in_budget": in_budget,
            "captured": s.path in t.records,
        }
        if s.path == "embed" or s.path not in t.records:
            # no matmul consumed this site this pass (embed is a gather);
            # keep the row for the audit but give the search nothing to
            # trade — scoring without real inputs would be fiction
            row.update({"errors": None, "bytes": None, "roofline_ms": None})
            rows.append(row)
            continue
        L = s.shape[0] if _stacked(s) else 1
        w_layers = _weight_matrices(params, s)
        x_layers = [t.rows(s.path, layer=l, n_layers=L) for l in range(L)]
        n_min = min(n_min or 10 ** 9, min(x.shape[0] for x in x_layers))
        row["errors"] = _score_site(s, w_layers, x_layers, n_samples)
        bpv = {f: DENSE_BPV for f in list(QDQ_FORMATS) + ["bf16"]}
        if s.packed:
            bpv["hif4"] = PACKED_BPV
        row["bytes"] = {f: round(b * s.n_values) for f, b in bpv.items()}
        if mem_bw:
            row["roofline_ms"] = {
                f: round(b / mem_bw * 1e3, 6) for f, b in row["bytes"].items()}
        else:
            row["roofline_ms"] = None
        rows.append(row)

    return CalibrationResult(
        arch=cfg.name, family=cfg.family, plan=plan, rows=tuple(rows),
        n_batches=n_batches, seq_len=seq_len, batch=batch, seed=seed,
        n_calib_rows=int(n_min or 0), mem_bw=mem_bw)

"""One run of one cell: set-up, the measured window, the output check.

The window drives the program's normal serving entry,
``repro.runtime.serve_loop.serve_requests``, on the paged HiF4 pool, call
after call, one batch of requests per call, until ``seconds`` have passed;
only whole calls count. Set-up makes the weights from the seed on the
device, packs them with the program's ``prepare_params_for_serving`` under
the configuration's policy, and runs one warm-up call that visits every
shape the window will use. After the window the program's state is freed
and a sample of the served requests is scored by the plain reference
(``bench/check.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import os
import sys
import time

from bench import check as check_mod
from bench.discover import Finder, metrics_of
from bench.generator import Traffic

# A --trace 1 run traces the window's first calls, up to this many seconds
# of them: enough whole calls of every mix, and a trace the profiler holds
# whole and the run reads in seconds.
TRACE_SECONDS = 10.0
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts lowerings and backend compilations while armed."""

    def __init__(self):
        import jax.monitoring as mon

        self.armed = False
        self.lowered = 0
        self.compiled = 0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw):
        if not self.armed:
            return
        if event == COMPILE_EVENT:
            self.lowered += 1
        elif event == BACKEND_COMPILE_EVENT:
            self.compiled += 1


def program_config(conf: dict):
    """The program's ArchConfig as the configuration file states it, checked
    key by key against the published sizes."""
    from repro.configs import get_arch

    prog = conf["program"]
    cfg = get_arch(prog["arch"])
    if prog.get("attn_replace"):
        cfg = dataclasses.replace(
            cfg, attn=dataclasses.replace(cfg.attn, **prog["attn_replace"]))
    cfg = dataclasses.replace(cfg, **prog.get("replace", {}))
    published = dict(conf["published"], **conf.get("architecture", {}))
    for key, attr in prog["matches"].items():
        got = cfg
        for part in attr.split("."):
            got = getattr(got, part)
        if got != published[key]:
            raise ValueError(f"{conf['name']}: program {attr}={got!r} but the "
                             f"configuration states {key}={published[key]!r}")
    return cfg


def _check_tree(ours, specs, lead: int = 1):
    """Our weights fill the program's parameter tree exactly: same keys,
    shapes (one layer of ``lead`` stacked axes) and dtypes."""
    import jax
    import jax.numpy as jnp

    from repro.models.params import is_pspec

    want = jax.tree.map(lambda p: (tuple(p.shape[lead:]), jnp.dtype(p.dtype).name),
                        specs, is_leaf=is_pspec)
    got = jax.tree.map(lambda a: (tuple(a.shape[lead:]), a.dtype.name), ours)
    if got != want:
        raise ValueError(f"bench weights do not form the program's tree:\n"
                         f"{got}\nvs\n{want}")


@dataclasses.dataclass
class CallRecord:
    rids: list
    lengths: list
    seconds: float
    stats: dict
    tokens: list            # served tokens per request (numpy)
    stolen: float | None    # CPU seconds the hypervisor stole meanwhile


class Run:
    """State one run builds; per-layer readers read it (``bench/metrics``)."""

    def __init__(self, finder: Finder, workload: str, seed: int):
        self.finder = finder
        self.workload = workload
        self.seed = int(seed)
        self.cell, self.bench = finder.cell(workload)
        self.conf = finder.json("configs", self.cell["config"])
        self.mix = finder.json("traffic", self.cell["traffic"])
        self.reference = finder.module("reference", self.conf["reference"])
        self.sizes = self.reference.Sizes(self.conf["published"],
                                          self.conf.get("architecture", {}))
        self.traffic = Traffic(self.mix, seed, self.sizes.vocab)
        self.calls: list[CallRecord] = []
        self.trace = None          # bench.trace.Trace of a --trace 1 run
        self.traced = 0            # how many of the window's calls it holds
        self.peaks = None
        self.setup_s = None

    # -- set-up ---------------------------------------------------------------

    def setup(self, warm: bool = True):
        import jax
        import jax.numpy as jnp

        from repro.core import kvcache
        from repro.core.policy import get_policy
        from repro.models import lm
        from repro.models.common import ModelCtx
        from repro.runtime import serve_loop
        from repro.sharding.rules import ShardCtx

        serving = self.conf["serving"]
        self.cfg = cfg = program_config(self.conf)
        policy = get_policy(serving["policy"], impl=serving["impl"],
                            kv=kvcache.KVCacheConfig(serving["kv_format"]))
        plan = lm.quant_plan(cfg, policy)
        self.ctx = ModelCtx(quant=plan.base, plan=plan,
                            shard=ShardCtx(mesh=None), remat=False)
        ref, s = self.reference, self.sizes
        self.key = ref.seed_key(self.seed)

        pack = jax.jit(lambda blk: serve_loop.prepare_params_for_serving(
            {"blocks": blk}, cfg, plan)["blocks"])
        t0 = time.perf_counter()
        layers = []
        for layer in range(s.layers):
            blk = ref.to_program(s, ref.layer_weights(s, self.key, layer))
            if layer == 0:
                _check_tree(blk, lm.abstract_params(cfg)["blocks"])
            layers.append(pack(blk))
            del blk
        blocks = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *layers)
        del layers
        top = ref.top_to_program(ref.top_weights(s, self.key))
        _check_tree(top, {k: v for k, v in lm.abstract_params(cfg).items()
                          if k != "blocks"}, lead=0)
        params = dict(top, blocks=blocks)
        self.params = jax.block_until_ready(params)
        t = self.traffic
        self.batch = min(t.slots, t.per_call)
        self.serve_cfg = serve_loop.ServeConfig(
            max_new_tokens=t.new_tokens, kv_format=serving["kv_format"],
            kv_pages=t.pool_pages, kv_page_tokens=t.page_tokens,
            cache_capacity=t.capacity)
        self.serve = serve_loop.serve_requests
        packed, values = serve_loop.packed_weight_bytes(self.params)
        log(f"bench: {self.workload}: {cfg.name} {cfg.n_layers}L "
            f"d_model={cfg.d_model}; packed weights {packed / 2**20:.2f} MiB "
            f"for {values} values; pool {t.pool_pages} pages x "
            f"{t.page_tokens} tokens; {self.batch} slots")
        t1 = time.perf_counter()
        if warm:    # every prompt length, every slot, the window's budget
            self._serve([jnp.asarray(p) for p in t.warmup()], {})
        log(f"bench: set-up parts: weights and packing {t1 - t0:.3f} s, "
            f"warm-up call {time.perf_counter() - t1:.3f} s")

    def _serve(self, prompts, stats):
        import jax

        out = self.serve(self.cfg, self.params, prompts, self.ctx,
                         self.serve_cfg, slots=self.batch, stats=stats)
        return jax.device_get(out)

    # -- window ---------------------------------------------------------------

    def window(self, seconds: float, counter: CompileCounter, tracer=None):
        """Whole calls until ``seconds`` have passed; with ``tracer``, the
        calls of the first TRACE_SECONDS are traced (``self.trace``)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        spent, i = 0.0, 0
        counter.armed = True
        if tracer is not None:
            tracer.start()
        while spent < seconds:
            with jax.profiler.TraceAnnotation("bench.prepare"):
                batch = self.traffic.call(i)
                prompts = [jnp.asarray(p) for _, p in batch]
                jax.block_until_ready(prompts)
            stats: dict = {}
            h0 = host_cpu()
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.call"):
                out = self._serve(prompts, stats)
            dt = time.perf_counter() - t0
            h1 = host_cpu()
            spent += dt
            self.calls.append(CallRecord(
                rids=[r for r, _ in batch], lengths=[len(p) for _, p in batch],
                seconds=dt, stats=stats,
                tokens=[np.asarray(o) for o in out],
                stolen=None if h0 is None or h1 is None else h1 - h0))
            i += 1
            if tracer is not None and (spent >= TRACE_SECONDS or spent >= seconds):
                self.trace, self.traced, tracer = tracer.stop(), i, None
        counter.armed = False

    # -- end-to-end numbers -----------------------------------------------------

    def request_status(self):
        """(attempted, failed): requests of the window's calls, and those
        the scheduler did not report ``ok``."""
        attempted = failed = 0
        for c in self.calls:
            reports = c.stats.get("reports", {})
            for j in range(len(c.rids)):
                attempted += 1
                if reports.get(j, {}).get("status", "ok") != "ok":
                    failed += 1
        return attempted, failed

    def traced_calls(self) -> list:
        """The calls a --trace 1 run traced (all calls otherwise)."""
        return self.calls if self.trace is None else self.calls[: self.traced]

    def window_seconds(self) -> float:
        return sum(c.seconds for c in self.calls)

    def tokens_served(self) -> int:
        return sum(len(t) for c in self.calls for t in c.tokens)

    def free_program_state(self):
        self.params = None
        gc.collect()


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs),
            "memory_peak_bytes": int(peak)}


def host_cpu():
    """CPU seconds the machine's hypervisor has stolen so far (``steal`` in
    /proc/stat, all CPUs), or None where the file does not say."""
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()
        return int(fields[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return None


def _seconds(x) -> str:
    return "-" if x is None else f"{x:.2f}"


def configure_process():
    """JAX as the serve launcher sets it up (``configure_jax``: the compile
    cache in the checkout unless JAX_COMPILATION_CACHE_DIR says otherwise,
    bf16 rounded as written), and every program kept in the persistent
    cache however quick its compile, so only a checkout's first run
    compiles."""
    from repro.jax_setup import configure_jax

    configure_jax()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, finder: Finder | None = None,
        require_tpu: bool = True, configure: bool = True,
        control: bool = False) -> dict:
    """One run; returns the result line's object (``correct`` etc.).

    ``require_tpu=False, configure=False`` is for tests on the CPU: no
    peaks, and JAX left as the calling process set it up. ``control``
    scores the control's tokens in place of the served ones
    (``check.check_run``): such a run has to come out not correct."""
    finder = finder or Finder()
    r = Run(finder, workload, seed)
    if configure:
        configure_process()
    import jax

    devs = jax.devices()
    log(f"bench: JAX up on {len(devs)} {devs[0].platform} devices at "
        f"{time.perf_counter() - t_start:.3f} s")
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU: JAX found {devs[0].platform!r} "
                         "devices only; the benchmark never runs on the CPU")
    if len(devs) < r.cell["chips"]:
        raise SystemExit(f"bench: the cell asks for {r.cell['chips']} chips, "
                         f"JAX found {len(devs)}")
    if require_tpu:
        from bench.peaks import peaks_for
        r.peaks = peaks_for(devs[0].device_kind)
    counter = CompileCounter()
    r.setup()
    setup_s = time.perf_counter() - t_start
    log(f"bench: set-up {setup_s:.3f} s")

    tracer = None
    if trace:
        from bench.trace import Tracer
        tracer = Tracer()
    load0, cpu0, host0 = os.getloadavg(), os.times(), host_cpu()
    r.window(seconds, counter, tracer)
    cpu1, host1 = os.times(), host_cpu()
    stolen = ("" if host0 is None or host1 is None else
              f"; CPU seconds stolen by the hypervisor {host1 - host0:.2f}")
    log(f"bench: host load average {load0[0]:.2f} before the window, "
        f"{os.getloadavg()[0]:.2f} after; this process's CPU seconds in it: "
        f"user {cpu1.user - cpu0.user:.2f}, system {cpu1.system - cpu0.system:.2f}"
        + stolen)
    if trace:
        log(f"bench: traced {r.traced} calls, trace window "
            f"{r.trace.window_s:.3f} s, busy {r.trace.busy_s:.3f} s, "
            f"{r.trace.ops_outside} device ops outside it; kernels "
            f"{r.trace.custom_calls()}")
    secs = sorted((c.seconds, i) for i, c in enumerate(r.calls))
    log(f"bench: call seconds min {secs[0][0]:.4f} median "
        f"{secs[len(secs) // 2][0]:.4f}; slowest (s, call) "
        + ", ".join(f"({t:.4f}, {i}, stolen {_seconds(r.calls[i].stolen)})"
                    for t, i in secs[::-1][:4])
        + "; in order " + " ".join(f"{c.seconds:.3f}" for c in r.calls))
    device = device_info(jax, r.cell["chips"])
    log(f"bench: window {r.window_seconds():.3f} s, {len(r.calls)} calls; "
        f"compilations inside the window: {counter.lowered} lowered, "
        f"{counter.compiled} compiled")
    attempted, failed = r.request_status()

    r.setup_s = setup_s
    metrics = {}
    for m in metrics_of(r.bench, workload, "per_layer" if trace else "end_to_end"):
        value = finder.module("metrics", m["name"]).read(r)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if trace:
        device["busy_s"] = r.trace.busy_s
        device["window_s"] = r.trace.window_s

    r.free_program_state()
    limits = finder.json("limits", workload)
    checks = check_mod.check_run(r, limits, log=log, control=control)
    correct = (failed == 0 and attempted > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = r.trace.breakdown()
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return out

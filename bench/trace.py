"""From a profiler trace of the window to the numbers readers need.

``Tracer`` records the first calls of the window with JAX's profiler (the
Python tracer off, the host's annotations on) into a temporary directory
and reduces the trace there; nothing of it stays on disk. ``Trace`` holds:

* the window: from the first ``bench.*`` host annotation to the end of
  the last ``bench.call``;
* device operations: the events of each device plane's op line, named by
  their HLO instruction text (``%fused_packed_matmul.3 = f32[32,2816]
  custom-call(...)``: name, result and operand shapes), without the
  control-flow ops (while, conditional, call) that only contain others.
  An instruction runs many times with the same text, so events are kept
  per distinct text: their count and their time inside the window;
* busy time: the union of the op intervals, averaged over the chips;
* idle gaps: the rest of the window on the first chip, each labelled by
  the host events that cover it (the benchmark's own annotations and
  JAX's dispatch events).
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_LINES = ("XLA Ops",)
HOST_PLANE = "/host:CPU"
CONTAINER = re.compile(r"^%(while|conditional|call)[.\d]* = ")


class Op:
    """One device op event: start and end in seconds, HLO text."""
    __slots__ = ("start", "end", "text")

    def __init__(self, start, end, text):
        self.start, self.end, self.text = start, end, text


def short_name(text: str) -> str:
    """Instruction name and result type, e.g. ``%copy.434 = u8[2177,512,64]``."""
    return text.split("{", 1)[0].split("(", 1)[0].strip()[:120]


def _merge(start, end):
    """Sorted disjoint intervals covering the union of [start, end)."""
    order = np.argsort(start, kind="stable")
    s, e = start[order], np.maximum.accumulate(end[order])
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > e[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(s) - 1)
    return s[first], e[last]


class _Device:
    """One chip's ops, clipped to [lo, hi], grouped by distinct text."""

    def __init__(self, ops, lo, hi):
        ops = [o for o in ops if o.end > lo and o.start < hi]
        self.texts: list = []
        index: dict = {}
        ids = np.empty(len(ops), np.int64)
        for i, o in enumerate(ops):
            j = index.get(o.text)
            if j is None:
                j = index[o.text] = len(self.texts)
                self.texts.append(o.text)
            ids[i] = j
        self.start = np.clip(np.fromiter((o.start for o in ops), float, len(ops)), lo, hi)
        self.end = np.clip(np.fromiter((o.end for o in ops), float, len(ops)), lo, hi)
        n = len(self.texts)
        self.count = np.bincount(ids, minlength=n)
        self.seconds = np.bincount(ids, weights=self.end - self.start, minlength=n)
        self.merged = _merge(self.start, self.end) if len(ops) else (
            np.empty(0), np.empty(0))


class Trace:
    """Times in seconds on the trace's clock."""

    def __init__(self, device_ops: dict, host_spans: list):
        """device_ops: {device name: [Op]}; host_spans: [(start, end, name)]."""
        calls = [sp for sp in host_spans if sp[2] == "bench.call"]
        marks = [sp for sp in host_spans if sp[2].startswith("bench.")]
        if not calls:
            raise ValueError("the trace holds no bench.call annotation")
        self.lo = min(s for s, _, _ in marks)
        self.hi = max(e for _, e, _ in calls)
        self.window_s = self.hi - self.lo
        kept = {dev: [o for o in ops if not CONTAINER.match(o.text)]
                for dev, ops in device_ops.items()}
        self.devices = {dev: _Device(ops, self.lo, self.hi)
                        for dev, ops in kept.items()}
        self.ops_outside = sum(len(ops) for ops in kept.values()) - sum(
            int(d.count.sum()) for d in self.devices.values())
        self.host = sorted(host_spans)
        self.busy_s = sum(float((e - s).sum()) for s, e in
                          (d.merged for d in self.devices.values())
                          ) / max(len(self.devices), 1)

    @classmethod
    def from_profile(cls, pd) -> "Trace":
        device_ops: dict = {}
        host: list = []
        for plane in pd.planes:
            if DEVICE_PLANE.match(plane.name):
                ops = device_ops.setdefault(plane.name, [])
                for line in plane.lines:
                    if line.name in OP_LINES:
                        ops += [Op(e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                                for e in line.events]
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    host += [(e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                             for e in line.events]
        return cls(device_ops, host)

    # -- queries readers use ----------------------------------------------------

    def kernel(self, pattern: str) -> list:
        """(text, calls, seconds in the window) of each distinct device op
        whose HLO text matches ``pattern``, over all chips."""
        rx = re.compile(pattern)
        return [(t, int(d.count[j]), float(d.seconds[j]))
                for d in self.devices.values()
                for j, t in enumerate(d.texts) if rx.search(t)]

    def kernel_seconds(self, pattern: str) -> float:
        return sum(secs for _, _, secs in self.kernel(pattern))

    def top_ops(self, n: int = 10) -> list:
        total: dict = defaultdict(float)
        for d in self.devices.values():
            for j, t in enumerate(d.texts):
                total[short_name(t)] += float(d.seconds[j])
        return sorted(([k, v] for k, v in total.items()), key=lambda kv: -kv[1])[:n]

    def custom_calls(self) -> dict:
        """Calls of each custom call (kernel) in the window, by name."""
        seen: dict = defaultdict(int)
        for d in self.devices.values():
            for j, t in enumerate(d.texts):
                if " custom-call(" in t:
                    seen[re.sub(r"[.\d]*$", "", t.split(" = ", 1)[0])] += int(d.count[j])
        return dict(seen)

    def idle_gaps(self, n: int = 10) -> list:
        """The ``n`` longest stretches of the window in which no device op
        ran (on the first chip), each named by what the host was doing."""
        d = next(iter(self.devices.values()), None)
        s, e = d.merged if d is not None else (np.empty(0), np.empty(0))
        lo = np.concatenate([[self.lo], e])
        hi = np.concatenate([s, [self.hi]])
        keep = hi > lo
        lo, hi = lo[keep], hi[keep]
        top = np.argsort(lo - hi, kind="stable")[:n]
        return [[self.host_label((lo[i] + hi[i]) / 2), float(hi[i] - lo[i])]
                for i in top]

    def host_label(self, t: float) -> str:
        """The host events covering ``t``, outermost first."""
        cover = [sp for sp in self.host if sp[0] <= t <= sp[1]]
        cover.sort(key=lambda sp: sp[0] - sp[1])          # longest first
        names = []
        for _, _, name in cover:
            if name not in names:
                names.append(name)
        return " > ".join(names[:4]) if names else "no host event"

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


class Tracer:
    def start(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> Trace:
        import jax
        from jax.profiler import ProfileData

        jax.profiler.stop_trace()
        try:
            path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                             recursive=True)[0]
            return Trace.from_profile(ProfileData.from_file(path))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

"""Reduction of a profiler trace to the numbers the per-layer metrics
read: device busy time and idle share, time per device operation,
kernel time, and the idle gaps named by what the host was doing.

Two stages. ``load`` reads the ``.xplane.pb`` the profiler wrote into
plain event records; ``Trace`` reduces records. The records are plain
JSON (``Trace.to_json``), so a recorded trace can be kept beside the
tests and reduced again without a chip.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

# device lines whose events are operations running on the chip
OP_LINES = ("XLA Ops",)
# the benchmark's own host spans, preferred when naming an idle gap
BENCH_SPANS = ("window", "eval_hook", "job")
_STATS = ("long_name", "tf_op", "hlo_op", "kernel_details")


def load(trace_dir: str) -> dict:
    """Records of the newest trace under ``trace_dir``: device op events
    per TPU core, and host events (name, start, duration; ns)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                "NON_CORE" not in plane.name:
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name not in OP_LINES:
                    continue
                for e in line.events:
                    stats = {k: str(v)[:300] for k, v in e.stats
                             if k in _STATS}
                    evs.append([e.name, int(e.start_ns), int(e.duration_ns),
                                stats])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns), line.name])
    return {"devices": devices, "host": host}


def short_name(name: str) -> str:
    """``%fusion.12 fusion f32[5,128,32,32,64]`` for the HLO text a TPU
    trace names its ops by: the instruction, its opcode, its first
    output's type."""
    lhs, eq, rest = name.partition(" = ")
    if not eq:
        return name[:120]
    op = re.search(r"\s([a-z][\w-]*)\(", rest)
    shape = re.search(r"[a-z][a-z0-9]*\[[0-9,]*\]", rest)
    return " ".join(p for p in (lhs, op and op.group(1),
                                shape and shape.group(0)) if p)


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Trace:
    def __init__(self, records: dict):
        self.records = records
        spans = [h for h in records["host"] if h[0] == "window"]
        if spans:
            s = max(spans, key=lambda h: h[2])
            self.t0, self.t1 = s[1], s[1] + s[2]
        else:
            evs = [e for d in records["devices"].values() for e in d]
            self.t0 = min(e[1] for e in evs)
            self.t1 = max(e[1] + e[2] for e in evs)
        self.devices = {name: [e for e in evs if e[1] < self.t1
                               and e[1] + e[2] > self.t0]
                        for name, evs in records["devices"].items()}
        self.devices = {k: v for k, v in self.devices.items() if v}

    @classmethod
    def from_dir(cls, trace_dir: str) -> "Trace":
        return cls(load(trace_dir))

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            return cls(json.load(f))

    def to_json(self, path: str) -> None:
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "wt") as f:
            json.dump(self.records, f)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def _busy(self, evs):
        return _union([(max(e[1], self.t0), min(e[1] + e[2], self.t1))
                       for e in evs])

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the cores
        that ran any."""
        if not self.devices:
            return 0.0
        tot = sum(sum(e - s for s, e in self._busy(evs))
                  for evs in self.devices.values())
        return tot / len(self.devices) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self) -> dict:
        """Self time per device operation name within the window,
        averaged over cores: an event's time less that of the events it
        encloses on its line."""
        out: dict = {}
        t0, t1 = self.t0, self.t1
        for evs in self.devices.values():
            evs = sorted(evs, key=lambda e: (e[1], -e[2]))
            stack = []
            for name, s, d, _ in evs:
                while stack and stack[-1][1] <= s:
                    stack.pop()
                inside = min(s + d, t1) - max(s, t0)
                if stack:
                    out[stack[-1][0]] = out.get(stack[-1][0], 0) - inside
                out[name] = out.get(name, 0) + inside
                stack.append((name, s + d))
        n = max(len(self.devices), 1)
        return {k: v / n / 1e9 for k, v in out.items()}

    def matching(self, pattern: str) -> list:
        """(name, seconds) of every device operation whose name or long
        name matches ``pattern``, on every core."""
        rx = re.compile(pattern)
        return [(name, d / 1e9) for evs in self.devices.values()
                for name, _, d, stats in evs
                if rx.search(name) or any(rx.search(v)
                                          for v in stats.values())]

    def kernel(self, pattern: str) -> tuple[float, int]:
        """(seconds, events) of the operations ``matching`` finds,
        averaged over cores."""
        found = self.matching(pattern)
        n = max(len(self.devices), 1)
        return sum(d for _, d in found) / n, len(found) // n

    def idle_gaps(self, top: int = 10) -> list:
        """The longest idle gaps of the first core, each named by the
        benchmark's host span that covers most of it, else by the host
        event that does."""
        if not self.devices:
            return []
        first = sorted(self.devices)[0]
        busy = self._busy(self.devices[first])
        gaps, cur = [], self.t0
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < self.t1:
            gaps.append((cur, self.t1))
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        host = self.records["host"]
        out = []
        for g0, g1 in gaps:
            best, best_own, ov_own = None, None, 0
            ov_best = 0
            for name, s, d, _ in host:
                ov = min(g1, s + d) - max(g0, s)
                if ov <= 0 or name == "window":
                    continue
                if name in BENCH_SPANS and ov > ov_own:
                    best_own, ov_own = name, ov
                if ov > ov_best or (ov == ov_best and best is not None
                                    and d < best[1]):
                    best, ov_best = (name, d), ov
            label = best_own or (best[0] if best else "unattributed")
            out.append([label, (g1 - g0) / 1e9])
        return out

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[short_name(k), v] for k, v in ops[:top]],
                "idle_gaps": self.idle_gaps(top)}

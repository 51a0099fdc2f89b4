"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
readers and ``breakdown`` need.

Device planes are the ``/device:TPU:<i>`` planes; on each, the ``XLA Ops``
line holds one event per operation run and ``XLA Modules`` one per jitted
program run.  Host spans are the benchmark's own
``jax.profiler.TraceAnnotation`` events, named ``bench.<what>``, on the
host plane.  All times are nanoseconds on the trace's one clock.

The window is the stretch from the first ``bench.batch`` span's start to
the last one's end.  Busy time is the union of a device's op intervals
inside it; idle time is the rest, and each idle gap is attributed to the
innermost benchmark span that was open at the gap's midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.batch"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# control-flow ops whose event spans the ops of their body
CONTAINER_OP = re.compile(r"[)}\]] (while|conditional|call)\(")
HLO_TEXT = re.compile(r"^(%\S+) = (\(.*?\)|\S+) ([\w\-]+)\(")
MODULES_LINE = "XLA Modules"

Event = Tuple[str, float, float]            # name, start_ns, end_ns


@dataclass
class Trace:
    ops: Dict[int, List[Event]] = field(default_factory=dict)
    modules: Dict[int, List[Event]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)

    # -- the window ----------------------------------------------------------
    @property
    def window(self) -> Optional[Tuple[float, float]]:
        batches = [s for s in self.spans if s[0] == WINDOW_SPAN]
        if not batches:
            return None
        return (min(s[1] for s in batches), max(s[2] for s in batches))

    @property
    def window_s(self) -> float:
        w = self.window
        return (w[1] - w[0]) * 1e-9 if w else 0.0

    @property
    def n_batches(self) -> int:
        return sum(1 for s in self.spans if s[0] == WINDOW_SPAN)

    @property
    def devices(self) -> List[int]:
        return sorted(d for d, ev in self.ops.items() if ev)

    # -- busy and idle -------------------------------------------------------
    def busy_intervals(self, device: int) -> List[Tuple[float, float]]:
        """Union of the device's op intervals, clipped to the window."""
        w = self.window
        if w is None:
            return []
        iv = sorted((max(s, w[0]), min(e, w[1]))
                    for _, s, e in self.ops.get(device, ()))
        out: List[List[float]] = []
        for s, e in iv:
            if e <= s:
                continue
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return [(s, e) for s, e in out]

    def busy_s(self, device: int) -> float:
        return sum(e - s for s, e in self.busy_intervals(device)) * 1e-9

    def mean_busy_s(self) -> float:
        devs = self.devices
        return sum(self.busy_s(d) for d in devs) / len(devs) if devs else 0.0

    def idle_gaps(self, device: int) -> List[Tuple[float, float]]:
        w = self.window
        if w is None:
            return []
        gaps, t = [], w[0]
        for s, e in self.busy_intervals(device):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if w[1] > t:
            gaps.append((t, w[1]))
        return gaps

    def span_at(self, t: float) -> str:
        """Innermost benchmark span open at instant t."""
        best = None
        for name, s, e in self.spans:
            if s <= t <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        return best[0] if best else "outside_spans"

    # -- sums by name ----------------------------------------------------------
    def time_s(self, line: str, pattern: str) -> float:
        """Seconds, averaged over devices, of events in the window whose
        name matches ``pattern`` (a regular expression) on ``line``."""
        w = self.window
        devs = self.devices
        if w is None or not devs:
            return 0.0
        rx = re.compile(pattern)
        src = self.ops if line == OPS_LINE else self.modules
        tot = 0.0
        for d in devs:
            for name, s, e in src.get(d, ()):
                if rx.search(name) and e > w[0] and s < w[1]:
                    tot += min(e, w[1]) - max(s, w[0])
        return tot * 1e-9 / len(devs)

    def top_ops(self, k: int = 10) -> List[List]:
        """The k device operations that took most time, seconds averaged
        over devices; loops and calls are left out (their body's ops are
        counted), and each op is named by its HLO name, opcode and shape."""
        w = self.window
        devs = self.devices
        if w is None or not devs:
            return []
        by = defaultdict(float)
        for d in devs:
            for name, s, e in self.ops[d]:
                if e > w[0] and s < w[1] and not CONTAINER_OP.search(name):
                    by[short_op_name(name)] += (min(e, w[1])
                                                - max(s, w[0])) * 1e-9
        top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
        return [[name, v / len(devs)] for name, v in top]

    def top_gaps(self, k: int = 10) -> List[List]:
        """Idle seconds by what the host was doing, averaged over devices."""
        devs = self.devices
        if not devs:
            return []
        by = defaultdict(float)
        for d in devs:
            for s, e in self.idle_gaps(d):
                by[self.span_at(0.5 * (s + e))] += (e - s) * 1e-9
        top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
        return [[name, v / len(devs)] for name, v in top]


def short_op_name(text: str) -> str:
    """``%fusion.7 = f32[8,2]{1,0:T(8,128)} fusion(...), ...`` ->
    ``%fusion.7 fusion f32[8,2]``."""
    m = HLO_TEXT.match(text)
    if not m:
        return text[:120]
    return f"{m.group(1)} {m.group(3)} {re.sub(r'{[^}]*}', '', m.group(2))}"


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read the device ops, device modules and benchmark spans of one
    ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events]
                    (tr.ops if line.name == OPS_LINE
                     else tr.modules).setdefault(dev, []).extend(ev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        tr.spans.append((e.name, e.start_ns,
                                         e.start_ns + e.duration_ns))
    return tr

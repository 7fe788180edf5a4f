"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: the device's busy union and idle share over a window, device
time per program and per operation, the benchmark's host spans, and the
longest idle gaps labelled by the span the host was in.

The window is the benchmark's own ``bench.window`` host span. Device planes
are ``/device:TPU:<i>``; their ``XLA Ops`` line holds one event per
operation and ``XLA Modules`` one per program run. The profiler puts host
and device events on one clock, so a gap on the device can be matched to
the host span around it.
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_RUN_ID = re.compile(r"\(\d+\)$")


def program_name(name: str) -> str:
    """``jit_step(12)`` -> ``jit_step``: the program without its run id."""
    return _RUN_ID.sub("", name)


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    total, end = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, end), min(e, hi)
        if e > s:
            total += e - s
            end = e
    return total


@dataclass
class Reduction:
    window: Tuple[float, float]               # ns, on the profiler's clock
    devices: int
    busy_ns: float                            # union, mean over devices
    program_ns: Dict[str, float] = field(default_factory=dict)
    op_ns: Dict[Tuple[str, str], float] = field(default_factory=dict)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns

    def span_ns(self, *names: str) -> float:
        """Union of the named host spans inside the window."""
        lo, hi = self.window
        return union_ns([(s, e) for n, s, e in self.spans if n in names],
                        lo, hi)

    def span_durations(self, name: str) -> List[float]:
        lo, hi = self.window
        return [e - s for n, s, e in self.spans
                if n == name and s >= lo and e <= hi]

    def programs(self, pattern: str) -> float:
        """Device ns of the programs whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(v for k, v in self.program_ns.items() if rx.search(k))

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` operations that took most device time, as
        ``[program/op, seconds]``; an op is named by its HLO result name
        (the event's text up to `` = ``)."""
        by_op: Dict[str, float] = {}
        for (p, o), v in self.op_ns.items():
            key = f"{p}/{o.split(' = ')[0]}"
            by_op[key] = by_op.get(key, 0.0) + v
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9 / self.devices] for k, v in top]


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def _enclosing(modules, starts, t: float) -> str:
    """The program whose run on the device covers ``t``, else ``""``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][0] <= t <= modules[i][1]:
        return modules[i][2]
    return ""


def _label(spans, t: float) -> str:
    """The innermost benchmark span that covers ``t``, else ``host``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and name != WINDOW_SPAN and (
                best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "host"


def reduce_planes(planes, n_gaps: int = 10) -> Optional[Reduction]:
    """``planes`` as ``jax.profiler.ProfileData`` gives them. None when the
    trace holds no window span or no device operation."""
    spans, devices = [], []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                devices.append(lines)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows or not devices:
        return None
    lo, hi = windows[0]
    busy, program_ns, op_ns, all_busy = 0.0, {}, {}, []
    for lines in devices:
        modules = []
        if "XLA Modules" in lines:
            for ev in lines["XLA Modules"].events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                modules.append((s, e, program_name(ev.name)))
                if e <= lo or s >= hi:
                    continue
                prog = program_name(ev.name)
                program_ns[prog] = (program_ns.get(prog, 0.0)
                                    + min(e, hi) - max(s, lo))
        modules.sort()
        starts = [m[0] for m in modules]
        ivs = []
        for ev in lines["XLA Ops"].events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= lo or s >= hi:
                continue
            ivs.append((s, e))
            prog = program_name(str(_stats(ev).get("hlo_module", ""))) or \
                _enclosing(modules, starts, s)
            key = (prog, ev.name)
            op_ns[key] = op_ns.get(key, 0.0) + min(e, hi) - max(s, lo)
        busy += union_ns(ivs, lo, hi)
        all_busy.append(sorted(ivs))
    gaps = []
    for ivs in all_busy[:1]:                  # gaps of the first device
        t = lo
        for s, e in ivs + [(hi, hi)]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n_gaps]
    return Reduction(window=(lo, hi), devices=len(devices),
                     busy_ns=busy / len(devices), program_ns=program_ns,
                     op_ns=op_ns, spans=spans,
                     gaps=[(_label(spans, (s + e) / 2), e - s)
                           for s, e in gaps])


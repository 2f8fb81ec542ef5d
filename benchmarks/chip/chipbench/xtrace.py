"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Device activity is read from the ``XLA Ops`` line of every
``/device:TPU:<n>`` plane, program boundaries from its ``XLA Modules``
line, and the host's doing from the benchmark's own
``TraceAnnotation`` spans (named ``bench.*``) on the ``/host:CPU``
plane.  All three share one clock in the trace.

Names: an op event is named by its HLO text (``%ring_lookup64.1 =
s32[...] custom-call(...)``) and reduced to ``ring_lookup64``; a module
event (``jit_decode_slots_fused(1234...)``) to ``jit_decode_slots_fused``.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\.\d+$")
HOST_PREFIX = "bench."


def op_name(raw: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``."""
    head = raw.split(" = ", 1)[0].strip()
    return _SUFFIX.sub("", head.lstrip("%"))


def module_name(raw: str) -> str:
    """``jit_ring_lookup64(1320...)`` -> ``jit_ring_lookup64``."""
    return raw.split("(", 1)[0]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals; sorted, disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


@dataclass
class Events:
    """Device ops and modules per chip, and host spans, in ns."""
    ops: Dict[str, List[Tuple[str, float, float]]] = field(default_factory=dict)
    modules: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    host: List[Tuple[str, float, float]] = field(default_factory=list)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Events:
    from jax.profiler import ProfileData

    ev = Events()
    for plane in ProfileData.from_file(path).planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dst = ev.ops.setdefault(plane.name, [])
                elif line.name == "XLA Modules":
                    dst = ev.modules.setdefault(plane.name, [])
                else:
                    continue
                dst.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                ev.host.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(HOST_PREFIX))
    return ev


@dataclass
class Reduced:
    window_s: float
    busy_s: float                 # mean over the chips traced
    chips: int
    op_s: Dict[str, float]        # op base name -> seconds, summed on chips
    module_s: Dict[str, float]    # module base name -> seconds
    gaps_s: Dict[str, float]      # idle time by the host span covering it

    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0 or not self.chips:
            return None
        return 1.0 - self.busy_s / self.window_s

    def ops_matching(self, pred: Callable[[str], bool]) -> float:
        return sum(s for n, s in self.op_s.items() if pred(n))

    def modules_matching(self, pred: Callable[[str], bool]) -> float:
        return sum(s for n, s in self.module_s.items() if pred(n))

    def breakdown(self, top: int = 10) -> dict:
        def best(d):
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:top]]
        return {"device_ops": best(self.op_s), "idle_gaps": best(self.gaps_s)}


def _host_at(spans: List[Tuple[str, float, float]], t: float) -> str:
    """Innermost (shortest) benchmark span covering time ``t``."""
    inner = None
    for name, s, e in spans:
        if s <= t < e and (inner is None or e - s < inner[1]):
            inner = (name, e - s)
    return inner[0] if inner else "outside bench spans"


def reduce(ev: Events, window: Interval) -> Reduced:
    """Busy union, per-name device time and idle gaps inside ``window``
    (ns).  Ops that overlap (async copies, several streams) count once
    towards busy time and in full towards their own name."""
    lo, hi = window
    op_s: Dict[str, float] = {}
    module_s: Dict[str, float] = {}
    gaps_s: Dict[str, float] = {}
    busy = 0.0
    host = [h for h in ev.host if h[0] != HOST_PREFIX + "window"]
    for plane, ops in ev.ops.items():
        spans = clip([(s, e) for _, s, e in ops], lo, hi)
        merged = union(spans)
        busy += total(merged)
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                k = op_name(name)
                op_s[k] = op_s.get(k, 0.0) + d / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                k = _host_at(host, (a + b) / 2)
                gaps_s[k] = gaps_s.get(k, 0.0) + (b - a) / 1e9
    for plane, mods in ev.modules.items():
        for name, s, e in mods:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                k = module_name(name)
                module_s[k] = module_s.get(k, 0.0) + d / 1e9
    chips = len(ev.ops)
    return Reduced(window_s=(hi - lo) / 1e9,
                   busy_s=busy / 1e9 / max(chips, 1), chips=chips,
                   op_s=op_s, module_s=module_s, gaps_s=gaps_s)


def window_of(ev: Events) -> Interval:
    """The traced window: the benchmark's ``bench.window`` span."""
    spans = [(s, e) for n, s, e in ev.host if n == HOST_PREFIX + "window"]
    if not spans:
        raise ValueError("trace holds no bench.window span")
    return spans[0]

"""Reduce a profiler trace (``.xplane.pb``) to device busy time, time by
device operation and module, and the idle gaps with what the host did.

The device planes are ``/device:TPU:<i>``. Their ``XLA Ops`` line holds
one event per operation run (the event name is the HLO instruction's
text, ``%<op> = <shape> <opcode>(...)``) and their ``XLA Modules`` line
one event per program run (``<jit name>(<fingerprint>)``). The window
is the span of the benchmark's own ``bench.interval`` annotations on the
host plane, which shares the device planes' clock; without them, the
span of all device events.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

INTERVAL = "bench.interval"
_MODULE = re.compile(r"^(.*)\(\d+\)$")


def op_name(text: str) -> str:
    """``%while.75 = (...) while(...)`` -> ``while.75``."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def module_name(text: str) -> str:
    """``jit_step(694062...)`` -> ``jit_step``."""
    m = _MODULE.match(text)
    return m.group(1) if m else text


def union_length(spans: List[Tuple[float, float]]) -> Tuple[float, list]:
    """Total length of the union of ``(start, end)`` spans, and the merged
    spans in order."""
    merged: List[List[float]] = []
    for s, e in sorted(spans):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


@dataclass
class TraceReduction:
    window_s: float
    busy_s: float                       # averaged over the device planes
    n_devices: int
    op_s: Dict[str, float] = field(default_factory=dict)      # module/op
    module_s: Dict[str, float] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)
    n_intervals: int = 0

    def op_time(self, prefix: str) -> float:
        """Seconds of every operation whose name starts with ``prefix``
        (any module)."""
        return sum(v for k, v in self.op_s.items()
                   if k.split("/", 1)[-1].startswith(prefix))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:top]]}


def _host_annotations(planes) -> List[Tuple[float, float, str]]:
    out = []
    for pl in planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            for e in ln.events:
                if e.name.startswith("bench."):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
    return out


def reduce_planes(planes, top_gaps: int = 10) -> TraceReduction:
    """Reduce parsed planes (``ProfileData(...).planes`` or any objects
    with ``name``, ``lines``; lines with ``name``, ``events``; events with
    ``name``, ``start_ns``, ``duration_ns``)."""
    planes = list(planes)
    notes = _host_annotations(planes)
    ivals = [(s, e) for s, e, n in notes if n == INTERVAL]
    devices = [pl for pl in planes if pl.name.startswith("/device:TPU:")]
    parsed = []
    for pl in devices:
        lines = {ln.name: list(ln.events) for ln in pl.lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       module_name(e.name))
                      for e in lines.get("XLA Modules", []))
        ops = [(e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
               for e in lines.get("XLA Ops", [])]
        parsed.append((mods, ops))
    if ivals:
        lo = min(s for s, _ in ivals)
        hi = max(e for _, e in ivals)
    else:
        spans = [(s, e) for _, ops in parsed for s, e, _ in ops]
        if not spans:
            return TraceReduction(window_s=0.0, busy_s=0.0,
                                  n_devices=len(devices))
        lo = min(s for s, _ in spans)
        hi = max(e for _, e in spans)
    module_s: Dict[str, float] = defaultdict(float)
    op_s: Dict[str, float] = defaultdict(float)
    for mods, ops in parsed:
        for s, e, m in mods:
            if lo <= s < hi:
                module_s[m] += (e - s) * 1e-9
        starts = [m[0] for m in mods]
        for s, e, name in ops:
            if not lo <= s < hi:
                continue
            k = bisect.bisect_right(starts, s) - 1
            mod = mods[k][2] if k >= 0 and s < mods[k][1] else "?"
            op_s[f"{mod}/{name}"] += (e - s) * 1e-9
    busy, gaps = [], []
    for _, ops in parsed:
        clipped = [(max(s, lo), min(e, hi)) for s, e, _ in ops
                   if e > lo and s < hi]
        length, merged = union_length(clipped)
        busy.append(length * 1e-9)
        edges = [lo] + [x for m in merged for x in m] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [(_label(notes, (a + b) / 2), (b - a) * 1e-9)
                for a, b in gaps[:top_gaps]]
    return TraceReduction(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / max(len(busy), 1), n_devices=len(devices),
        op_s=dict(op_s), module_s=dict(module_s), gaps=labelled,
        n_intervals=len(ivals))


def _label(notes, t: float) -> str:
    """The innermost ``bench.*`` annotation around host time ``t``."""
    inner: Optional[Tuple[float, float, str]] = None
    for s, e, n in notes:
        if s <= t < e and (inner is None or e - s < inner[1] - inner[0]):
            inner = (s, e, n)
    return inner[2] if inner else "outside bench annotations"


def reduce_file(path: str, top_gaps: int = 10) -> TraceReduction:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, top_gaps)

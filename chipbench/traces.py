"""Reduction of a JAX profiler trace to the device's busy and idle time,
the time of each device operation and program, and the idle gaps labelled
by what the host was doing.

A trace is read with ``jax.profiler.ProfileData`` into plain tuples
``(name, start_ns, dur_ns)`` per (plane, line), so the reduction itself
works on lists and is tested on a small trace recorded on the chip.
Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation and ``XLA Modules`` one per program execution.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, dur_ns)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{plane: {line: [(name, start_ns, dur_ns), ...]}} from an
    ``.xplane.pb`` file, or from the newest one under a trace directory."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = ProfileData.from_file(path)
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in pd.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            evs.extend((e.name, int(e.start_ns), int(e.duration_ns))
                       for e in line.events)
    return out


_HLO = re.compile(r"^(%?[^\s=]+) = .*?\b([a-z][a-z0-9\-]*)\(")


def short_op(name: str) -> str:
    """'%fusion.3 = f32[..]{..} fusion(...), kind=..' -> '%fusion.3 fusion'
    (an op's event name is its whole HLO instruction)."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def device_planes(trace) -> List[str]:
    return sorted(p for p in trace if p.startswith(DEVICE_PREFIX)
                  and trace[p].get(OPS_LINE))


def clip(events: Iterable[Event], lo: int, hi: int) -> List[Event]:
    """Events cut to [lo, hi); those outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events: Iterable[Event], lo: int, hi: int):
    """(busy_ns, gaps) over [lo, hi): the length of the union of the
    events' intervals, and the idle gaps [(start, end)] between them."""
    iv = sorted((s, s + d) for _, s, d in clip(events, lo, hi))
    busy, gaps, cur = 0, [], lo
    for a, b in iv:
        if a > cur:
            gaps.append((cur, a))
        if b > cur:
            busy += b - max(a, cur)
            cur = b
    if hi > cur:
        gaps.append((cur, hi))
    return busy, gaps


def totals(events: Iterable[Event]) -> Dict[str, Tuple[int, int]]:
    """{name: (count, total_ns)}."""
    out: Dict[str, Tuple[int, int]] = {}
    for name, _, d in events:
        c, t = out.get(name, (0, 0))
        out[name] = (c + 1, t + d)
    return out


def host_events(trace, prefixes: Tuple[str, ...]) -> List[Event]:
    """Events of host planes whose name starts with one of `prefixes`."""
    out = []
    for plane, lines in trace.items():
        if plane.startswith("/device:"):
            continue
        for evs in lines.values():
            out.extend(e for e in evs if e[0].startswith(prefixes))
    return out


def label_gap(gap: Tuple[int, int], spans: List[Event]) -> str:
    """The innermost host span that covers the gap's midpoint, else the
    one that overlaps it most, else "(no host span)"."""
    a, b = gap
    mid = (a + b) // 2
    cover = [s for s in spans if s[1] <= mid < s[1] + s[2]]
    if cover:
        return min(cover, key=lambda s: s[2])[0]
    best, over = None, 0
    for s in spans:
        o = min(b, s[1] + s[2]) - max(a, s[1])
        if o > over:
            best, over = s, o
    return best[0] if best else "(no host span)"


def reduce(trace, lo: int, hi: int, spans: Optional[List[Event]] = None,
           top: int = 10) -> dict:
    """Busy and idle time of the device planes over [lo, hi) (averaged
    over the planes), program and operation totals, and the `top` longest
    idle gaps of the first plane labelled from `spans`."""
    planes = device_planes(trace)
    if not planes:
        return {}
    busy_ns, gaps0 = [], None
    ops: Dict[str, Tuple[int, int]] = {}
    mods: Dict[str, Tuple[int, int]] = {}
    for p in planes:
        b, gaps = union(trace[p][OPS_LINE], lo, hi)
        busy_ns.append(b)
        if gaps0 is None:
            gaps0 = gaps
        for src, dst in ((OPS_LINE, ops), (MODULES_LINE, mods)):
            evs = [(short_op(e[0]), e[1], e[2]) if src == OPS_LINE else e
                   for e in clip(trace[p].get(src, []), lo, hi)]
            for name, (c, t) in totals(evs).items():
                c0, t0 = dst.get(name, (0, 0))
                dst[name] = (c0 + c, t0 + t)
    n = len(planes)
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy_ns) / n / 1e9
    longest = sorted(gaps0, key=lambda g: g[0] - g[1])[:top]
    return {
        "chips": n,
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "modules": {k: (c, t / n / 1e9) for k, (c, t) in mods.items()},
        "device_ops": [[k, t / n / 1e9] for k, (c, t) in sorted(
            ops.items(), key=lambda kv: -kv[1][1])[:top]],
        "idle_gaps": [[label_gap(g, spans or []), (g[1] - g[0]) / 1e9]
                      for g in longest],
    }

"""Reduction of one profiler trace (``.xplane.pb``) to the benchmark's
device numbers, read with ``jax.profiler.ProfileData`` and nothing else.

- The traced window is the host span ``bench.window`` the harness puts
  around its window.
- Device busy time is the union, clipped to the window, of the intervals of
  the device's XLA programs (the ``XLA Modules`` line of each
  ``/device:<kind>:<n>`` plane), averaged over the chips used.  A program
  here never waits on the host once it started (no host callbacks), so its
  interval is busy time.
- Device time per program is the sum of its intervals, keyed by the module
  name the trace prints, without the ``(<id>)`` suffix (``jit_one``).
- Idle gaps are the stretches of the window with no program on the first
  chip; each is named by the benchmark's own host span (``bench.*``) that
  covers most of it.
"""
from __future__ import annotations

import re
from dataclasses import dataclass

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    return _SUFFIX.sub("", event_name)


def union(intervals) -> list:
    """Merged ``(start, end)`` intervals, sorted."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                        # averaged over chips
    modules: dict                        # module name -> device seconds
    idle_gaps: list                      # [(span name, seconds)], longest first

    def module_seconds(self, name: str) -> float:
        return self.modules.get(name, 0.0)

    def breakdown(self) -> dict:
        ops = sorted(self.modules.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:10]]}


def _device_planes(pd, chips: int) -> list:
    planes = [p for p in pd.planes
              if re.fullmatch(r"/device:[A-Z]+:\d+", p.name)]
    planes.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    return planes[:chips]


def summarize(path_or_data, chips: int = 1) -> TraceSummary:
    """Reduce a trace file (or loaded ``ProfileData``) to the window's
    device busy time, per-program device time and idle gaps."""
    if isinstance(path_or_data, str):
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path_or_data)
    else:
        pd = path_or_data
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    _, lo, hi = windows[0]
    window_ns = hi - lo

    busy, modules, first_busy = [], {}, None
    for plane in _device_planes(pd, chips):
        progs = []
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for ev in line.events:
                progs.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                name = module_name(ev.name)
                (s, e), = clip([progs[-1]], lo, hi) or [(0, 0)]
                modules[name] = modules.get(name, 0.0) + (e - s) / 1e9
        merged = clip(union(progs), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged
    if not busy:
        raise ValueError("the trace holds no device plane")

    gaps, cursor = [], lo
    for s, e in (first_busy or []) + [(hi, hi)]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    host = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]
    named = []
    for gs, ge in gaps:
        cover: dict = {}
        for n, s, e in host:
            overlap = min(e, ge) - max(s, gs)
            if overlap > 0:
                cover[n] = cover.get(n, 0) + overlap
        name = max(cover, key=cover.get) if cover else "outside bench spans"
        named.append((name, (ge - gs) / 1e9))
    named.sort(key=lambda g: -g[1])
    return TraceSummary(window_s=window_ns / 1e9,
                        busy_s=sum(busy) / len(busy) / 1e9,
                        modules=modules, idle_gaps=named)

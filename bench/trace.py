"""Reduction of a profiler trace to intervals the metric readers read.

``jax.profiler`` writes an ``.xplane.pb``; `jax.profiler.ProfileData`
reads it.  Device planes (``/device:TPU:n``) carry a line of XLA
operations and a line of XLA modules (whole compiled programs); the host
plane carries the benchmark's own spans (``bench.*`` annotations).  All
share one clock.  The traced window is the ``bench.traced`` span.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.traced"


@dataclasses.dataclass
class Reduced:
    t0: float                  # the traced window, seconds on the trace clock
    t1: float
    ops: list                  # (name, start_s, dur_s) device operations
    modules: list              # (name, start_s, dur_s) device programs
    host: list                 # (name, start_s, dur_s) bench.* host spans
    n_devices: int

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


def find_xplane(directory) -> Path | None:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    return found[-1] if found else None


def reduce_xspace(pd) -> Reduced:
    """From a `ProfileData` (or anything with the same planes/lines/events
    shape) to a `Reduced`."""
    ops, modules, host = [], [], []
    window = None
    n_dev = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:") and any(
                line.name == OPS_LINE for line in plane.lines):
            n_dev += 1
            for line in plane.lines:
                dst = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dst is None:
                    continue
                for e in line.events:
                    dst.append((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
                    elif e.name.startswith("bench."):
                        host.append((e.name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9))
    if window is None:
        starts = [s for _, s, _ in ops + host]
        ends = [s + d for _, s, d in ops + host]
        window = (min(starts, default=0.0), max(ends, default=0.0))
    return Reduced(window[0], window[1], ops, modules, host, max(n_dev, 1))


def load(directory) -> Reduced | None:
    path = find_xplane(directory)
    if path is None:
        return None
    from jax.profiler import ProfileData

    return reduce_xspace(ProfileData.from_file(str(path)))


def clip(events: list, t0: float, t1: float) -> list:
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events: list) -> list:
    """Merged ``(start, end)`` intervals covered by ``events``."""
    spans = sorted((s, s + d) for _, s, d in events)
    out: list = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def busy_s(red: Reduced) -> float:
    """Seconds of the window in which some operation ran, averaged over the
    devices in the trace."""
    spans = union(clip(red.ops, red.t0, red.t1))
    return sum(b - a for a, b in spans) / red.n_devices


def idle_gaps(red: Reduced, top: int = 10) -> list:
    """The longest gaps between device operations, each named by the
    benchmark's host span that covers its middle (``other`` if none)."""
    spans = union(clip(red.ops, red.t0, red.t1))
    edges = [red.t0] + [x for ab in spans for x in ab] + [red.t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:top]:
        mid = (a + b) / 2
        covering = [(d, n) for n, s, d in red.host if s <= mid <= s + d]
        name = min(covering)[1] if covering else "other"
        out.append([name, b - a])
    return out


def short_name(op: str) -> str:
    """``%copy_bitcast_fusion.17 = bf16[...] ...`` -> ``copy_bitcast_fusion``."""
    head = op.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def self_times(events: list) -> list:
    """``(name, self seconds)`` per event: its duration less that of the
    events nested directly inside it (a loop op encloses its body's ops)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = [[n, d] for n, _, d in evs]
    stack: list = []                     # indices of open enclosing events
    for i, (_, s, d) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= d
        stack.append(i)
    return [(n, max(d, 0.0)) for n, d in out]


def top_ops(red: Reduced, top: int = 10) -> list:
    """Device operations by self time in the window, grouped by name."""
    total: dict = {}
    for name, d in self_times(clip(red.ops, red.t0, red.t1)):
        k = short_name(name)
        total[k] = total.get(k, 0.0) + d
    return [[n, s] for n, s in sorted(total.items(), key=lambda x: -x[1])[:top]]


def breakdown(red: Reduced) -> dict:
    return {"device_ops": top_ops(red), "idle_gaps": idle_gaps(red)}

"""Profiler capture and the reduction from a device trace to numbers.

The harness wraps its host phases in ``jax.profiler.TraceAnnotation``
(``bench.window`` around the measured loop, ``bench.dispatch``,
``bench.wait`` and ``bench.record`` inside it).  :func:`reduce` reads the
``.xplane.pb`` the profiler writes and, clipped to the window:

* busy seconds per chip: the union of the intervals of the ops on the
  chip's ``XLA Ops`` line, so nested or overlapping ops count once;
* seconds per op name on the first chip, for the ops that hold no other
  op (a ``while`` loop's own event spans its body's ops and is left
  out); the name is the HLO instruction's, without its ``%``;
* the first chip's idle gaps, each charged to the host phase that
  overlaps it most (``host.none`` when no phase does).

Host and device events share the trace's clock; both are read from
``ProfileData`` in nanoseconds.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import re
from pathlib import Path

WINDOW = "bench.window"
PHASE_PREFIX = "bench."
NO_PHASE = "host.none"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: dict            # chip id -> busy seconds in the window
    op_s: dict              # op name -> seconds on the first chip
    idle_by_phase: dict     # host phase -> idle seconds of the first chip

    def busy_mean_s(self, chips) -> float:
        return sum(self.busy_s[c] for c in chips) / len(chips)

    def op_seconds(self, pattern: str) -> float:
        """Seconds on the first chip of the ops whose name contains
        ``pattern``."""
        return sum(s for name, s in self.op_s.items() if pattern in name)


@contextlib.contextmanager
def capture(trace_dir):
    import jax
    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(trace_dir):
    """``ProfileData`` of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(str(files[-1]))


def _union(intervals):
    """Merged, sorted, non-overlapping copy of ``[(start, end), ...]``."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def op_name(text: str) -> str:
    """``fusion.12`` from the trace's ``%fusion.12 = f32[...] fusion(...)``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _leaves(ops):
    """The ops of one line that contain no other op of the line."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    holder = [False] * len(ops)
    stack = []
    for i in order:
        s, e = ops[i][0], ops[i][1]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            holder[stack[-1]] = True
        stack.append(i)
    return [op for op, h in zip(ops, holder) if not h]


def reduce(profile) -> TraceSummary:
    """Reduce a ``ProfileData`` to a :class:`TraceSummary`."""
    window = None
    phases = []
    devices = {}
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name.startswith(PHASE_PREFIX):
                        phases.append((ev.start_ns,
                                       ev.start_ns + ev.duration_ns, ev.name))
            continue
        match = DEVICE_PLANE.match(plane.name)
        if match is None:
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                devices[int(match.group(1))] = [
                    (ev.start_ns, ev.start_ns + ev.duration_ns,
                     op_name(ev.name)) for ev in line.events]
    if window is None:
        raise ValueError(f"trace holds no {WINDOW!r} host span")
    if not devices:
        raise ValueError("trace holds no TPU device plane with an "
                         f"{OPS_LINE!r} line")
    lo, hi = window
    busy, op_s, idle = {}, {}, {}
    first = min(devices)
    for chip, ops in devices.items():
        clipped = [_clip(s, e, lo, hi) + (name,) for s, e, name in ops]
        clipped = [c for c in clipped if c[1] > c[0]]
        merged = _union([(s, e) for s, e, _ in clipped])
        busy[chip] = sum(e - s for s, e in merged) / 1e9
        if chip != first:
            continue
        for s, e, name in _leaves(clipped):
            op_s[name] = op_s.get(name, 0.0) + (e - s) / 1e9
        phases.sort()
        starts = [ps for ps, _, _ in phases]
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge <= gs:
                continue
            best, who = 0.0, NO_PHASE
            # the host phases run one after another, so only those that
            # start before the gap ends, back to the last that starts
            # before it, can overlap it
            i = bisect.bisect_left(starts, ge)
            j = max(bisect.bisect_right(starts, gs) - 1, 0)
            for ps, pe, name in phases[j:i]:
                overlap = min(ge, pe) - max(gs, ps)
                if overlap > best:
                    best, who = overlap, name
            idle[who] = idle.get(who, 0.0) + (ge - gs) / 1e9
    return TraceSummary((hi - lo) / 1e9, busy, op_s, idle)


def top(d: dict, n: int = 10):
    """The ``n`` largest entries of ``{name: seconds}`` as
    ``[[name, seconds], ...]``."""
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]

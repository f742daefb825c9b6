"""The program's own spans and scopes in a profiler trace.

The program marks the layer boundaries of its solve path with
``repro.runtime.spans.span``: eagerly a host span
(``jax.profiler.TraceAnnotation``) on the python thread's line, in a
compiled program a ``jax.named_scope`` that lands in each device op's
``tf_op``.  :func:`reduce` reads, clipped to the harness's
``bench.window``:

* ``span_count``: the ``repro.*`` host spans that start in the window, by
  name, on the thread line that holds ``bench.window``;
* ``idle_by_span``: the first chip's idle gaps charged by self time: each
  instant of a gap goes to the innermost ``bench.*`` or ``repro.*`` span
  open on that line at that instant (``bench.window`` itself is not a
  phase), or to ``host.none`` if none is.  Busy time plus every entry
  is the window;
* ``scope_s`` and ``scope_runs``: the first chip's leaf-op seconds keyed
  by the ``repro.*`` segments of each op's ``tf_op`` joined with ``/``
  (``repro.gk.left/repro.op.matvec``), and the largest event count of any
  one op name under a key: how many times the scope ran, which holds
  when XLA splits one scope into several fusions.  Ops with no
  ``repro.*`` segment have no key.

``jax.profiler.ProfileData`` does not expose the stats of event metadata,
where ``tf_op`` lives, so :func:`tf_ops` reads them from the
``.xplane.pb`` with a small reader of the protobuf wire format, and the
reduction joins them to ``ProfileData``'s events by event name.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

from bench import tracing

PREFIX = "repro."
SCOPE_SEP = "/"

# field numbers of tsl/profiler/protobuf/xplane.proto
XSPACE_PLANES = 1
XPLANE_NAME, XPLANE_EVENT_METADATA, XPLANE_STAT_METADATA = 2, 4, 5
MAP_KEY, MAP_VALUE = 1, 2
XEVENT_METADATA_NAME, XEVENT_METADATA_STATS = 2, 5
XSTAT_METADATA_ID, XSTAT_STR_VALUE, XSTAT_REF_VALUE = 1, 5, 7
XSTAT_METADATA_NAME = 2
TF_OP = "tf_op"


@dataclasses.dataclass
class SpanSummary:
    span_count: dict        # repro.* span name -> spans started in window
    idle_by_span: dict      # innermost span -> idle seconds of first chip
    scope_s: dict           # repro.* scope path -> first chip op seconds
    scope_runs: dict        # scope path -> times the scope ran


def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    ``memoryview`` for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _map(field):
    """``(key, value)`` of one protobuf map entry."""
    entry = dict(_fields(field))
    return entry.get(MAP_KEY, 0), entry.get(MAP_VALUE, b"")


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def tf_ops(xspace: bytes) -> dict:
    """``{device plane name: {event name: tf_op}}`` from a serialized
    ``XSpace``, for the planes that :mod:`bench.tracing` reads as chips."""
    out = {}
    for number, plane in _fields(memoryview(xspace)):
        if number != XSPACE_PLANES:
            continue
        fields = list(_fields(plane))
        name = next((_text(v) for f, v in fields if f == XPLANE_NAME), "")
        if tracing.DEVICE_PLANE.match(name) is None:
            continue
        stat_names = {}
        for f, v in fields:
            if f == XPLANE_STAT_METADATA:
                key, meta = _map(v)
                stat_names[key] = _text(dict(_fields(meta)).get(
                    XSTAT_METADATA_NAME, b""))
        ops = {}
        for f, v in fields:
            if f != XPLANE_EVENT_METADATA:
                continue
            _, meta = _map(v)
            event, tf_op = None, None
            for mf, mv in _fields(meta):
                if mf == XEVENT_METADATA_NAME:
                    event = _text(mv)
                elif mf == XEVENT_METADATA_STATS:
                    stat = dict(_fields(mv))
                    if stat_names.get(stat.get(XSTAT_METADATA_ID)) != TF_OP:
                        continue
                    if XSTAT_STR_VALUE in stat:
                        tf_op = _text(stat[XSTAT_STR_VALUE])
                    else:
                        tf_op = stat_names.get(stat.get(XSTAT_REF_VALUE))
            if event is not None and tf_op is not None:
                ops.setdefault(event, tf_op)
        out[name] = ops
    return out


def scope(tf_op: str) -> str:
    """The ``repro.*`` segments of ``tf_op``: ``repro.gk.left/repro.op.matvec``
    from ``jit(run)/while/body/closed_call/repro.gk.left/repro.op.matvec/``
    ``dot_general:``; empty when the op ran in no ``repro.*`` scope."""
    return SCOPE_SEP.join(s for s in tf_op.split("/") if s.startswith(PREFIX))


def _innermost(spans, lo, hi):
    """``[(start, end, name)]`` covering ``[lo, hi]`` in order: at each
    instant the innermost of the nested ``spans`` open then, or
    ``tracing.NO_PHASE``."""
    out, stack, t = [], [], lo

    def advance(to):
        nonlocal t
        while stack and stack[-1][0] <= to:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end
        if to > t:
            out.append((t, to, stack[-1][1] if stack else tracing.NO_PHASE))
            t = to

    for s, e, name in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
        s, e = tracing._clip(s, e, lo, hi)
        if e <= s:
            continue
        advance(s)
        stack.append((e, name))
    advance(hi)
    return out


def reduce(profile, xspace: bytes) -> SpanSummary:
    """Reduce a ``ProfileData`` and the serialized ``XSpace`` it was read
    from to a :class:`SpanSummary`."""
    window, spans, first = None, [], None
    for plane in profile.planes:
        if plane.name == tracing.HOST_PLANE:
            for line in plane.lines:
                events = list(line.events)
                held = [ev for ev in events if ev.name == tracing.WINDOW]
                if held:
                    ev = held[0]
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    spans = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name) for ev in events
                             if ev.name != tracing.WINDOW
                             and ev.name.startswith((PREFIX,
                                                     tracing.PHASE_PREFIX))]
            continue
        match = tracing.DEVICE_PLANE.match(plane.name)
        if match is None:
            continue
        chip = int(match.group(1))
        if first is not None and chip > first[0]:
            continue
        for line in plane.lines:
            if line.name == tracing.OPS_LINE:
                first = (chip, plane.name, [
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in line.events])
    if window is None:
        raise ValueError(f"trace holds no {tracing.WINDOW!r} host span")
    if first is None:
        raise ValueError("trace holds no TPU device plane with an "
                         f"{tracing.OPS_LINE!r} line")
    lo, hi = window
    count = {}
    for s, _, name in spans:
        if name.startswith(PREFIX) and lo <= s < hi:
            count[name] = count.get(name, 0) + 1

    _, plane_name, ops = first
    clipped = [tracing._clip(s, e, lo, hi) + (name,) for s, e, name in ops]
    clipped = [c for c in clipped if c[1] > c[0]]
    merged = tracing._union([(s, e) for s, e, _ in clipped])
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2]) if ge > gs]
    idle, i = {}, 0
    segments = _innermost(spans, lo, hi)
    for gs, ge in gaps:
        while segments[i][1] <= gs:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < ge:
            s, e, name = segments[j]
            idle[name] = idle.get(name, 0.0) + (min(e, ge) - max(s, gs)) / 1e9
            j += 1

    names = tf_ops(xspace).get(plane_name, {})
    scope_s, runs = {}, {}
    for s, e, name in tracing._leaves(clipped):
        key = scope(names.get(name, ""))
        if not key:
            continue
        scope_s[key] = scope_s.get(key, 0.0) + (e - s) / 1e9
        per_op = runs.setdefault(key, {})
        op = tracing.op_name(name)
        per_op[op] = per_op.get(op, 0) + 1
    scope_runs = {key: max(per_op.values()) for key, per_op in runs.items()}
    return SpanSummary(count, idle, scope_s, scope_runs)


def load(trace_dir):
    """``(ProfileData, serialized XSpace)`` of the newest ``.xplane.pb``
    under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    raw = files[-1].read_bytes()
    return ProfileData.from_serialized_xspace(raw), raw

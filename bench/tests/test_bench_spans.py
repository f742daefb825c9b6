"""The reduction of the program's spans and scopes (``bench/spans.py``)
and the readers of the four metrics built on it."""
import gzip
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, spans, tracing  # noqa: E402

RECORDED = Path(__file__).parent / "data" / "small_trace.xplane.pb.gz"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def profile(host, ops, other_line=()):
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=host),
                                    NS(name="worker", events=other_line)]),
        NS(name="/device:TPU:0", lines=[NS(name=tracing.OPS_LINE,
                                           events=ops)])])


# a serialized XSpace, field by field (bench/spans.py names the numbers)
def varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(number, payload):
    if isinstance(payload, int):
        return varint(number << 3) + varint(payload)
    if isinstance(payload, str):
        payload = payload.encode()
    return varint(number << 3 | 2) + varint(len(payload)) + payload


def xspace(plane_name, tf_op_of, by_ref=()):
    """One plane whose event metadata give each event name of ``tf_op_of``
    its ``tf_op`` stat, as a string or, for names in ``by_ref``, as a
    reference to a stat metadata that holds the string."""
    stat_meta = {1: "tf_op", 2: "flops"}
    events = b""
    for i, (name, tf_op) in enumerate(tf_op_of.items(), start=1):
        stats = field(5, field(1, 2) + field(4, 7))          # an int stat
        if tf_op is not None:
            if name in by_ref:
                ref = 100 + i
                stat_meta[ref] = tf_op
                stats += field(5, field(1, 1) + field(7, ref))
            else:
                stats += field(5, field(1, 1) + field(5, tf_op))
        meta = field(1, i) + field(2, name) + field(4, name[:4]) + stats
        events += field(4, field(1, i) + field(2, meta))
    stats = b"".join(field(5, field(1, k) + field(2, field(1, k) +
                                                     field(2, v)))
                     for k, v in stat_meta.items())
    plane = field(1, 3) + field(2, plane_name) + events + stats
    return field(1, plane)


def test_idle_by_span_charges_self_time():
    host = [ev("bench.window", 100, 1000),
            ev("bench.dispatch", 100, 500),
            ev("repro.plan.estimate", 120, 460),    # [120, 580]
            ev("repro.gk.sync", 200, 200),          # [200, 400]
            ev("$contextlib.py:132 __enter__", 210, 5),
            ev("repro.gk.sync", 450, 50),           # [450, 500]
            ev("bench.wait", 600, 400),             # [600, 1000]
            ev("repro.gk.sync", 20, 40),            # before the window
            ev("repro.gk.sync", 1200, 10)]          # after it
    ops = [ev("fusion.1", 100, 50), ev("fusion.2", 300, 50),
           ev("fusion.3", 700, 200)]
    other = [ev("repro.gk.sync", 300, 600)]         # another thread
    p = profile(host, ops, other)
    s = spans.reduce(p, b"")
    # gaps [150, 300], [350, 700], [900, 1100]; each instant goes to the
    # innermost span open then, the rest of the window to host.none
    assert s.idle_by_span == pytest.approx({
        "repro.plan.estimate": 180e-9,      # 150-200, 400-450, 500-580
        "repro.gk.sync": 200e-9,            # 200-300, 350-400, 450-500
        "bench.dispatch": 20e-9,            # 580-600
        "bench.wait": 200e-9,               # 600-700, 900-1000
        "host.none": 100e-9})               # 1000-1100
    t = tracing.reduce(p)
    assert t.busy_s[0] + sum(s.idle_by_span.values()) == pytest.approx(
        t.window_s)
    assert sum(s.idle_by_span.values()) == pytest.approx(
        sum(t.idle_by_phase.values()))
    assert s.span_count == {"repro.plan.estimate": 1, "repro.gk.sync": 2}
    assert s.scope_s == {} and s.scope_runs == {}


def test_missing_window_or_device_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        spans.reduce(profile([], []), b"")
    with pytest.raises(ValueError, match="TPU"):
        spans.reduce(NS(planes=[NS(name="/host:CPU", lines=[NS(
            name="python", events=[ev("bench.window", 0, 10)])])]), b"")


def test_scopes_from_tf_op():
    left = ("jit(run)/while/body/closed_call/repro.gk.left/"
            "repro.op.matvec/dot_general:")
    right = "jit(run)/repro.gk.right/repro.op.matvec/jit(gk)/pallas_call:"
    names = {"%fusion.1 = f32[8] fusion(%a)": left,
             "%fusion.2 = f32[8] fusion(%b)": left,    # a split fusion
             "%fusion.3 = f32[8] fusion(%c)": right,
             "%copy.4 = f32[8] copy(%d)": "jit(run)/copy:",
             "%while.5 = (s32[]) while(%t)": None}
    raw = (xspace("/host:CPU", {"repro.gk.left": "x/repro.gk.left/y:"})
           + xspace("/device:TPU:0", names, by_ref={
               "%fusion.3 = f32[8] fusion(%c)"}))
    assert spans.tf_ops(raw) == {"/device:TPU:0": {
        k: v for k, v in names.items() if v is not None}}
    f1, f2, f3, cp, wh = names
    ops = ([ev(f1, 100 + 100 * i, 30) for i in range(3)]
           + [ev(f2, 130 + 100 * i, 20) for i in range(3)]
           + [ev(f3, 160 + 100 * i, 10) for i in range(2)]
           + [ev(wh, 90, 400), ev(cp, 450, 10)])
    s = spans.reduce(profile([ev("bench.window", 0, 1000)], ops), raw)
    assert s.scope_s == pytest.approx({
        "repro.gk.left/repro.op.matvec": 150e-9,
        "repro.gk.right/repro.op.matvec": 20e-9})
    assert s.scope_runs == {"repro.gk.left/repro.op.matvec": 3,
                            "repro.gk.right/repro.op.matvec": 2}


def test_scope_keeps_only_program_segments():
    assert spans.scope("jit(run)/while/body/closed_call/repro.gk.left/"
                       "jit(norm)/repro.op.cgs/dot_general:") == \
        "repro.gk.left/repro.op.cgs"
    assert spans.scope("jit(run)/copy:") == ""


def test_small_trace_recorded_on_the_chip():
    """The recorded F-SVD solve predates the program's spans: its Pallas
    steps carry the jit path as ``tf_op``, no op has a ``repro.*`` scope,
    and the self-time split covers the window as the phase split does."""
    from jax.profiler import ProfileData
    raw = gzip.decompress(RECORDED.read_bytes())
    profile_data = ProfileData.from_serialized_xspace(raw)
    ops = spans.tf_ops(raw)["/device:TPU:0"]
    steps = [ev.name for plane in profile_data.planes
             if plane.name == "/device:TPU:0" for line in plane.lines
             if line.name == tracing.OPS_LINE for ev in line.events
             if tracing.op_name(ev.name).startswith("gk_step_fused.")]
    assert steps
    for name in steps:
        assert ops[name].replace("while/body/closed_call/", "") == \
            "jit(run)/jit(gk_step_fused)/pallas_call:"
    s = spans.reduce(profile_data, raw)
    t = tracing.reduce(profile_data)
    assert s.span_count == {} and s.scope_s == {}
    assert set(s.idle_by_span) <= {"bench.dispatch", "bench.wait",
                                   "bench.record", "host.none"}
    assert t.busy_s[0] + sum(s.idle_by_span.values()) == pytest.approx(
        t.window_s, abs=1e-12)


# the readers, on a synthetic run of paper_dense_x1
CONFIG = {"m": 24576, "n": 80000, "dtype": "float32", "layout": "single"}
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def run_with(trace, solves=2):
    return NS(cell=NS(config=CONFIG, entry="estimate"), devices=[NS(id=0)],
              peak=PEAK, solves=solves, trace=trace)


def summary(**fields):
    base = tracing.TraceSummary(10.0, {0: 8.0}, {}, {})
    return NS(**vars(base), **fields) if fields else base


def test_host_syncs_reader():
    read = harness.load_reader("host_syncs.host_loop")
    assert read(run_with(summary(span_count={"repro.gk.sync": 222}))) == 111
    assert read(run_with(summary(span_count={"repro.gk.left": 220}))) is None
    assert read(run_with(summary())) is None


def test_sync_idle_share_reader():
    read = harness.load_reader("sync_idle_share.host_loop")
    idle = {"repro.gk.sync": 1.5, "repro.gk.left": 0.2, "host.none": 0.3}
    assert read(run_with(summary(idle_by_span=idle))) == pytest.approx(15.0)
    assert read(run_with(summary(idle_by_span={"host.none": 2.0}))) is None
    assert read(run_with(summary())) is None


def test_enqueue_idle_share_reader():
    read = harness.load_reader("enqueue_idle_share.host_loop")
    idle = {"repro.gk.sync": 1.5, "repro.gk.left": 0.2,
            "repro.gk.right": 0.1, "repro.gk.basis": 0.05,
            "repro.op.matvec": 0.03, "repro.op.cgs": 0.02,
            "repro.plan.estimate": 0.04, "bench.dispatch": 0.01}
    assert read(run_with(summary(idle_by_span=idle))) == pytest.approx(4.0)
    assert read(run_with(summary(idle_by_span={"repro.gk.sync": 1.0}))) \
        is None
    assert read(run_with(summary())) is None


def test_matvec_hbm_roofline_reader():
    read = harness.load_reader("matvec_hbm_roofline")
    gemv = 4 * 24576 * 80000 / 819e9            # one read of A at peak
    trace = summary(
        scope_s={"repro.gk.left/repro.op.matvec": 600 * 0.0105,
                 "repro.gk.right/repro.op.matvec": 600 * 0.0104,
                 "repro.gk.left/repro.op.cgs": 3.0},
        scope_runs={"repro.gk.left/repro.op.matvec": 597,
                    "repro.gk.right/repro.op.matvec": 597,
                    "repro.gk.left/repro.op.cgs": 597})
    assert read(run_with(trace)) == pytest.approx(
        100 * 1194 * gemv / (600 * 0.0209))
    assert read(run_with(summary(scope_s={"repro.gk.left/repro.op.cgs": 1.0},
                                 scope_runs={"repro.gk.left/repro.op.cgs":
                                             3}))) is None
    assert read(run_with(summary())) is None

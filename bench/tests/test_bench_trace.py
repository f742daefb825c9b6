"""The reduction from a profiler trace to busy time, op time and idle
gaps by host phase."""
import gzip
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import tracing  # noqa: E402


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def profile(host, devices):
    planes = [NS(name="/host:CPU", lines=[NS(name="python", events=host)])]
    for chip, ops in devices.items():
        planes.append(NS(name=f"/device:TPU:{chip}", lines=[
            NS(name="XLA Modules", events=[ev("jit_run", 0, 10**9)]),
            NS(name=tracing.OPS_LINE, events=ops)]))
    planes.append(NS(name="/device:TPU_NON_CORE:0", lines=[]))
    return NS(planes=planes)


def test_synthetic_trace():
    host = [ev("bench.window", 100, 1000), ev("bench.dispatch", 100, 150),
            ev("bench.wait", 250, 700), ev("bench.record", 950, 150),
            ev("unrelated", 0, 5000)]
    ops0 = [ev("%fusion.1 = f32[8] fusion(f32[8] %x)", 50, 150),  # [100, 200]
            ev("%while.4 = (s32[]) while(%t)", 300, 500),  # holds the next
            ev("fusion.2", 300, 400),       # [300, 700]
            ev("all-reduce.3", 700, 100),   # [700, 800]
            ev("fusion.1", 1050, 200)]      # clipped to [1050, 1100]
    ops1 = [ev("fusion.1", 100, 1000)]
    s = tracing.reduce(profile(host, {0: ops0, 1: ops1}))
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s[0] == pytest.approx((100 + 500 + 50) * 1e-9)
    assert s.busy_s[1] == pytest.approx(1000e-9)
    assert s.busy_mean_s([0, 1]) == pytest.approx(825e-9)
    assert s.op_s == pytest.approx({"fusion.1": 150e-9, "fusion.2": 400e-9,
                                    "all-reduce.3": 100e-9})
    assert s.op_seconds("all-reduce") == pytest.approx(100e-9)
    # gaps [200, 300] (dispatch 50, wait 50: first max wins: dispatch),
    # [800, 1050] (wait 150, record 100) -> wait
    assert s.idle_by_phase == pytest.approx({"bench.dispatch": 100e-9,
                                             "bench.wait": 250e-9})
    assert tracing.top(s.op_s, 2) == [["fusion.2", pytest.approx(400e-9)],
                                      ["fusion.1", pytest.approx(150e-9)]]


def test_missing_window_or_device_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        tracing.reduce(profile([], {0: []}))
    with pytest.raises(ValueError, match="TPU"):
        tracing.reduce(profile([ev("bench.window", 0, 10)], {}))


def test_small_trace_recorded_on_the_chip():
    """One F-SVD solve (2048 x 1024, 12 GK iterations, pallas) traced on a
    TPU v5 lite through the harness's window; the numbers were read off
    the trace by hand."""
    from jax.profiler import ProfileData
    raw = gzip.decompress(
        (Path(__file__).parent / "data" / "small_trace.xplane.pb.gz")
        .read_bytes())
    s = tracing.reduce(ProfileData.from_serialized_xspace(raw))
    assert s.window_s == pytest.approx(0.08681418, abs=1e-12)
    assert s.busy_s == pytest.approx({0: 0.001294984}, abs=1e-12)
    assert s.idle_by_phase == pytest.approx(
        {"bench.dispatch": 0.083480596, "bench.wait": 0.0020386}, abs=1e-12)
    # every nanosecond of the window is busy or charged to a phase
    assert s.busy_s[0] + sum(s.idle_by_phase.values()) == pytest.approx(
        s.window_s, abs=1e-12)
    assert tracing.top(s.op_s, 2) == [
        ["gk_step_fused.54", pytest.approx(0.000394207, abs=1e-12)],
        ["gk_rstep_fused.48", pytest.approx(0.000383898, abs=1e-12)]]
    assert sum(s.op_s.values()) <= s.busy_s[0]

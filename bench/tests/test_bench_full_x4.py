"""The paper's full operand on four chips, ``paper_full_x4.fsvd_sharded``,
driven on four virtual CPU devices at a small size, each in a process of
its own (this process keeps its one device): the listed cell with its own
limits, sound and with each fault a sharded solve can have, and the
sharded solve against the unsharded one and the exact reference."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_drive  # noqa: E402

sys.path.insert(0, str(bench_drive.ROOT / "tests"))

from conftest import run_on_devices  # noqa: E402

CELL = "paper_full_x4.fsvd_sharded"
M, N = 1024, 512
VARIANTS = ["sound", "alter_answer", "state_unchanged", "no_exchange"]


@pytest.fixture(scope="module")
def driven():
    """One result line per variant of the listed cell, from
    ``bench_drive.py`` run in one process on four devices."""
    lines = run_on_devices(f"""
        import contextlib, io, runpy, sys
        sys.argv = ["bench_drive.py", "{CELL}", "{M}", "{N}", *{VARIANTS}]
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            runpy.run_path("bench/tests/bench_drive.py", run_name="__main__")
        print(json.dumps({{"lines": [json.loads(line) for line in
                                    out.getvalue().splitlines()
                                    if line.startswith("{{")]}}))
    """, 4)["lines"]
    assert [o["variant"] for o in lines] == VARIANTS
    return {o["variant"]: o for o in lines}


@pytest.mark.parametrize("variant", VARIANTS)
def test_four_chip_cell_and_its_faults(driven, variant):
    out = driven[variant]
    assert out["device"]["count"] == 4
    assert out["attempted"] >= 1
    assert set(out["checks"]) == {"sigma_err", "out_err"}
    if variant == "sound":
        assert out["correct"], out["checks"]
        assert out["failed"] == 0
    else:
        assert not out["correct"], out["checks"]
        assert out["failed"] >= 1


PARITY = """
    from bench import harness, reference
    import repro.distributed.gk_dist  # noqa: F401  (registers fsvd_sharded)
    from repro.api import SVDSpec, plan
    from repro.core.fsvd import fsvd

    cell = harness.load_cell("%(cell)s")
    config = {**cell.config, "m": %(m)d, "n": %(n)d}
    spec = SVDSpec(**cell.traffic["spec"])
    k_op, key = jax.random.split(harness.seed_key(%(seed)d))
    operand = harness.make_operand(config, k_op, jax.devices()[:4],
                                   spec.backend)
    sharded = plan(spec, like=operand.op).solve(operand.op, key=key)
    A = jax.device_put(operand.A, jax.devices()[0])
    dense = fsvd(A, spec.rank, spec.max_iters, key=key)
    M, N = np.asarray(operand.M), np.asarray(operand.N)
    ex = reference.exact(M, N)
    got = {
        "shards": len(operand.A.sharding.device_set),
        "sharded": reference.compare(sharded.U, sharded.s, sharded.V, ex,
                                     spec.rank),
        "dense": reference.compare(dense.U, dense.s, dense.V, ex,
                                   spec.rank)}
    s1 = float(ex.s[0])
    got["sigma_gap"] = float(np.max(np.abs(np.asarray(sharded.s)
                                           - np.asarray(dense.s)))) / s1
    # each vector against its unsharded twin, up to the sign
    for name in ("U", "V"):
        X = np.asarray(getattr(sharded, name), np.float64)
        Y = np.asarray(getattr(dense, name), np.float64)
        sign = np.sign(np.sum(X * Y, axis=0))
        got[name + "_gap"] = float(np.max(np.linalg.norm(X - sign * Y,
                                                         axis=0)))
    print(json.dumps(got))
"""


def test_sharded_solve_agrees_with_unsharded_and_reference():
    got = run_on_devices(PARITY % {"cell": CELL, "m": M, "n": N,
                                   "seed": bench_drive.SEED}, 4)
    limits = json.loads((bench_drive.ROOT / "bench" / "limits"
                         / f"{CELL}.json").read_text())
    assert got["shards"] == 4
    # Both runs take the same start vector and make the same products in
    # float32; only the order of the row-shard sums differs.  So σ agrees
    # to float32 round-off of σ₁ (1.1e-7 to 1.6e-7 of σ₁ on three seeds)
    # ...
    assert got["sigma_gap"] < 1e-6, got
    # ... and each vector to that round-off over the gap between its
    # singular value and the next, a few hundredths of σ₁ among the top
    # 20 at this size (1.0e-5 to 5.0e-5 on three seeds).
    assert got["U_gap"] < 1e-4 and got["V_gap"] < 1e-4, got
    # Both are correct against the exact reference under the cell's
    # limits.
    for run in ("sharded", "dense"):
        for k, v in got[run].items():
            assert v < limits[k]["limit"], (run, k, v)


def test_psum_share_reads_the_sharded_operators_all_reduces():
    """``psum_share`` sums the ops XLA names after ``jax.lax.psum`` and
    leaves GSPMD's own all-reduces and every other op out; it reads
    nothing without a trace or a sharded operand."""
    import types
    from bench import harness, tracing
    read = harness.load_reader("psum_share")
    summary = tracing.TraceSummary(
        window_s=10.0, busy_s={0: 9.9}, idle_by_phase={},
        op_s={"psum.27": 0.01, "psum.30": 0.02, "all-reduce.4": 0.5,
              "multiply_reduce_fusion.37": 6.0, "copy-done": 0.1})
    cell = harness.load_cell(CELL)
    assert "psum_share" in [m["name"] for m in cell.per_layer]
    run = types.SimpleNamespace(cell=cell, trace=summary)
    assert read(run) == pytest.approx(0.3)
    assert read(types.SimpleNamespace(cell=cell, trace=None)) is None
    quiet = tracing.TraceSummary(10.0, {0: 9.9}, {"all-reduce.4": 0.5}, {})
    assert read(types.SimpleNamespace(cell=cell, trace=quiet)) is None
    single = harness.load_cell("paper_dense_x1.rank_ingraph")
    assert read(types.SimpleNamespace(cell=single, trace=summary)) is None

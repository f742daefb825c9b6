"""A run with the timed path broken underneath comes out not correct;
the same run unbroken comes out correct.  Each run skips only the look
for a chip and drives the rest on the CPU at a small size."""
import importlib
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_drive  # noqa: E402

CELLS = [w["name"] for w in json.loads(
    (bench_drive.ROOT / "BENCHMARK.json").read_text())["workloads"]]
# The F-SVD cells wait outside BENCHMARK.json for a fault of the program
# to be mended (PERF.md); their files are driven here as unlisted cells.
ONE_CHIP = ["paper_dense_x1.fsvd", "paper_dense_x1.fsvd_pallas"]
M, N = 1024, 512


def cell_of(name):
    if name in CELLS:
        return name
    config, traffic = name.split(".")
    return bench_drive.unlisted_cell(config, traffic, name)


@pytest.fixture
def fresh_plans():
    from repro.api.plan import clear_plan_cache
    clear_plan_cache()
    yield
    clear_plan_cache()


@pytest.mark.parametrize("cell", ONE_CHIP + CELLS)
def test_sound_run_is_correct(cell, fresh_plans):
    out = bench_drive.drive(cell_of(cell), M, N)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_run_keeps_the_callers_matmul_precision(fresh_plans, monkeypatch):
    """The harness sets no precision of its own: the solver is traced
    under the default matmul precision a user's call gets."""
    import jax
    from repro.api import registry
    seen = []
    get = registry.get_solver

    def get_solver(name):
        solver = get(name)

        def spy(*a, **kw):
            seen.append(jax.config.jax_default_matmul_precision)
            return solver(*a, **kw)
        return spy
    monkeypatch.setattr(importlib.import_module("repro.api.plan"),
                        "get_solver", get_solver)
    out = bench_drive.drive(cell_of(ONE_CHIP[0]), M, N)
    assert out["correct"], out["checks"]
    assert seen and all(p is None for p in seen), seen


@pytest.mark.parametrize("fault", ["alter_answer", "state_unchanged"])
@pytest.mark.parametrize("cell", ONE_CHIP + CELLS)
def test_fault_is_not_correct(cell, fault, fresh_plans, monkeypatch):
    bench_drive.FAULTS[fault](monkeypatch)
    out = bench_drive.drive(cell_of(cell), M, N)
    assert not out["correct"], out["checks"]
    assert out["failed"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_rank_miscount_is_not_correct(cell, fresh_plans, monkeypatch):
    """A rank one too high fails the exact comparison alone."""
    bench_drive.FAULTS["miscount"](monkeypatch)
    out = bench_drive.drive(cell, M, N)
    assert not out["correct"], out["checks"]
    assert out["checks"]["rank_err"]["value"] == 1.0
    assert not out["correct"], out["checks"]
    assert out["failed"] >= 1


def test_four_chip_cell_and_its_faults():
    """The four-chip deployment (``paper_dense_x4`` under ``fsvd_sharded``,
    which BENCHMARK.json does not list until it is measured on four chips)
    on four virtual CPU devices, in a process of its own, held to the
    limits of ``paper_dense_x1.fsvd``: sound, then each fault a sharded
    solve can have."""
    variants = ["sound", "alter_answer", "state_unchanged", "no_exchange"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run(
        [sys.executable, bench_drive.__file__,
         "paper_dense_x4:fsvd_sharded:paper_dense_x1.fsvd",
         str(M), str(N), *variants],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    outs = [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]
    assert [o["variant"] for o in outs] == variants
    assert outs[0]["correct"], outs[0]["checks"]
    assert outs[0]["device"]["count"] == 4
    for o in outs[1:]:
        assert not o["correct"], (o["variant"], o["checks"])

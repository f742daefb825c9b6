"""The roofline's operation and byte counts against sums written out by
hand, for every traffic file, and the peaks table."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import roofline  # noqa: E402

TRAFFIC = sorted((ROOT / "bench" / "traffic").glob("*.json"))
CONFIGS = {p.stem: json.loads(p.read_text())
           for p in (ROOT / "bench" / "configs").glob("*.json")}


def gk_by_hand(m, n, kprime, passes, shards, assemble=True):
    """Walk the solve step by step: the first Aᵀq, iterations 1..k'−1
    (left half-step against i columns of Q, right against i of P), the
    closing half-step against k' columns of Q, then U = A V, which a rank
    estimate does not form."""
    ml = m // shards
    nbytes = 4 * ml * n                      # p = Aᵀ q
    flops = 2 * ml * n
    for i in range(1, kprime):
        nbytes += 4 * (ml * n + (passes + 1) * ml * i)    # left
        nbytes += 4 * (ml * n + (passes + 1) * n * i)     # right
        flops += 2 * 2 * ml * n + 4 * passes * (ml * i + n * i)
    nbytes += 4 * (ml * n + (passes + 1) * ml * kprime)   # closing
    flops += 2 * ml * n + 4 * passes * ml * kprime
    if assemble:
        nbytes += 4 * ml * n                              # U = A V
        flops += 2 * ml * n
    return flops, nbytes


@pytest.mark.parametrize("traffic", TRAFFIC, ids=lambda p: p.stem)
@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_counts_match_hand_sums(traffic, config):
    body = json.loads(traffic.read_text())
    spec = body["spec"]
    assemble = body.get("entry", "solve") == "solve"
    cfg = CONFIGS[config]
    m, n = cfg["m"], cfg["n"]
    shards = cfg["chips"] if cfg["layout"] == "rows" else 1
    for kprime in (1, 2, 3, 100, 101, spec["max_iters"]):
        got = roofline.gk_work(m, n, kprime, 2, row_shards=shards,
                               assemble=assemble)
        want = gk_by_hand(m, n, kprime, 2, shards, assemble)
        assert got == pytest.approx(want, rel=1e-12)


def test_paper_dense_x1_at_100_iterations():
    # 201 sweeps of the 24576 x 80000 f32 operand dominate.
    assert (CONFIGS["paper_dense_x1"]["m"],
            CONFIGS["paper_dense_x1"]["n"]) == (24576, 80000)
    flops, nbytes = roofline.gk_work(24576, 80000, 100, 2)
    sweeps = 201 * 24576 * 80000 * 4
    basis = 3 * 4 * (24576 * (4950 + 100) + 80000 * 4950)
    assert nbytes == sweeps + basis
    peak = roofline.peaks("TPU v5 lite")
    assert roofline.least_seconds(flops, nbytes, peak) == nbytes / 819e9


def test_rank_estimate_leaves_out_one_sweep():
    solve = roofline.gk_work(24576, 80000, 110, 2)
    estimate = roofline.gk_work(24576, 80000, 110, 2, assemble=False)
    assert solve[1] - estimate[1] == 4 * 24576 * 80000
    assert solve[0] - estimate[0] == 2 * 24576 * 80000


def test_unknown_device_kind_is_an_error():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_kprime_below_one_is_refused():
    with pytest.raises(ValueError):
        roofline.gk_work(8, 8, 0, 2)

"""Operations and bytes that the algorithms need, from shapes alone.

These count the work a solve has to do, not what the program happens to
execute, so a roofline share built on them stays comparable when the
program changes.  Every count is per chip: ``row_shards`` chips each hold
``m / row_shards`` rows of A, and the right-side vectors are replicated.

The least time for a piece of work is the larger of operations over the
chip's peak FLOP/s and bytes over its peak HBM bandwidth (``peaks.json``,
keyed by ``device_kind``).  The peak FLOP/s is the published bf16 rate,
the chip's fastest, so the bound holds for the float32 work as well.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a kind that is not in the
    table is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def gk_work(m: int, n: int, kprime: int, passes: int, *, itemsize: int = 4,
            row_shards: int = 1, assemble: bool = True) -> tuple[float, float]:
    """(flops, bytes) per chip of an F-SVD solve that made ``kprime``
    useful GK iterations (paper Alg 1 + Alg 2 line 6), or with
    ``assemble=False`` of a rank estimate (Alg 1 + Alg 3), which forms no
    singular vectors.

    A is swept ``2 kprime + 1`` times: the first ``Aᵀ q``, two half-steps
    for each of the ``kprime − 1`` further iterations, the closing half-step
    that yields ``β_{k'+1}``, and ``U = A V``, which a rank estimate
    leaves out.  Each half-step reads its
    basis ``passes + 1`` times, the fused minimum of CGS^passes (the first
    product rides along with the matvec), at the width that iteration has:
    ``i`` columns in iteration ``i``, ``kprime`` in the closing half-step.
    Iterations after breakdown are not useful work and are not counted.
    """
    if kprime < 1:
        raise ValueError(f"kprime must be >= 1, got {kprime}")
    ml = m // row_shards
    sweeps = 2 * kprime + (1 if assemble else 0)
    cols_left = kprime * (kprime - 1) // 2 + kprime   # Σ_{i<k'} i, + k'
    cols_right = kprime * (kprime - 1) // 2
    basis_elems = ml * cols_left + n * cols_right
    nbytes = itemsize * (sweeps * ml * n + (passes + 1) * basis_elems)
    flops = 2.0 * sweeps * ml * n + 4.0 * passes * basis_elems
    return flops, float(nbytes)


"""matvec_hbm_roofline: the least time of the A sweeps that ran in a
``repro.op.matvec`` scope, over the first chip's seconds of the ops in
those scopes, in %.  One run of the scope reads A once (m·n·itemsize
bytes per chip, from the config; HBM bytes bound a GEMV); the runs are
``scope_runs`` of every scope path that ends in ``repro.op.matvec``.
Eager sweeps carry no scope, so a host-loop cell reads nothing here."""
import numpy as np

from bench import roofline

SCOPE = "repro.op.matvec"


def read(run):
    scope_s = getattr(run.trace, "scope_s", None) or {}
    keys = [k for k in scope_s if k.split("/")[-1] == SCOPE]
    seconds = sum(scope_s[k] for k in keys)
    if not keys or seconds <= 0:
        return None
    cfg = run.cell.config
    shards = len(run.devices) if cfg["layout"] == "rows" else 1
    rows = cfg["m"] // shards
    itemsize = np.dtype(cfg["dtype"]).itemsize
    sweeps = sum(run.trace.scope_runs[k] for k in keys)
    least = sweeps * roofline.least_seconds(
        2.0 * rows * cfg["n"], itemsize * rows * cfg["n"], run.peak)
    return 100.0 * least / seconds

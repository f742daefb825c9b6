"""gk_hbm_roofline: the least time the chip could take for the useful
work of the window's F-SVD solves or rank estimates, over the chips' mean
busy time in the traced window, in %.  The work comes from the shapes and
each call's useful iterations (``bench/roofline.py:gk_work``); at these
shapes HBM bytes bound it."""
from bench import roofline

GK_METHODS = ("fsvd", "fsvd_sharded")


def read(run):
    estimate = run.cell.entry == "estimate"
    if not (estimate or run.spec.method in GK_METHODS) or run.trace is None:
        return None
    cfg = run.cell.config
    shards = len(run.devices) if cfg["layout"] == "rows" else 1
    least = 0.0
    for kprime in run.iterations:
        flops, nbytes = roofline.gk_work(
            cfg["m"], cfg["n"], kprime, run.spec.reorth_passes,
            row_shards=shards, assemble=not estimate)
        least += roofline.least_seconds(flops, nbytes, run.peak)
    busy = run.trace.busy_mean_s([d.id for d in run.devices])
    if busy <= 0:
        return None
    return 100.0 * least / busy

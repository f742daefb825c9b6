"""psum_share: device time of a sharded operator's all-reduces on the
first chip over the traced window, in %.  XLA names the all-reduce that
a ``jax.lax.psum`` lowers to after its primitive (``psum.27``), so the
ops whose name holds ``psum`` are those of ``ShardedOp``; GSPMD's own
all-reduces (``all-reduce.N``) are left out."""


def read(run):
    if run.trace is None or run.cell.config["layout"] != "rows":
        return None
    seconds = run.trace.op_seconds("psum")
    if seconds <= 0 or run.trace.window_s <= 0:
        return None
    return 100.0 * seconds / run.trace.window_s

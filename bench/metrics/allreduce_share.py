"""allreduce_share: device time of the all-reduce ops on the first chip
over the traced window, in %.  Only a sharded operand has them."""


def read(run):
    if run.trace is None or run.cell.config["layout"] != "rows":
        return None
    seconds = run.trace.op_seconds("all-reduce")
    if seconds <= 0 or run.trace.window_s <= 0:
        return None
    return 100.0 * seconds / run.trace.window_s

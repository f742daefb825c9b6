"""device_idle_share: 1 − (union of the first chip's op intervals) /
(traced window), in %."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    busy = run.trace.busy_s[run.devices[0].id]
    return 100.0 * (1.0 - busy / run.trace.window_s)

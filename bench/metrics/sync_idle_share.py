"""sync_idle_share: the first chip's idle time while the host waits in a
``repro.gk.sync`` span (a device-to-host read) and in no span nested in
it, over the traced window, in %."""


def read(run):
    idle = getattr(run.trace, "idle_by_span", None) or {}
    if "repro.gk.sync" not in idle or run.trace.window_s <= 0:
        return None
    return 100.0 * idle["repro.gk.sync"] / run.trace.window_s

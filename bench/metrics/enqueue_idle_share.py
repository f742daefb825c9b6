"""enqueue_idle_share: the first chip's idle time while the host enqueues
the GK half-steps and writes the basis: self time of the
``repro.gk.left``, ``repro.gk.right`` and ``repro.gk.basis`` spans and of
the ``repro.op.*`` spans nested in them, over the traced window, in %."""

SPANS = ("repro.gk.left", "repro.gk.right", "repro.gk.basis")
NESTED = "repro.op."


def read(run):
    idle = getattr(run.trace, "idle_by_span", None) or {}
    held = [s for name, s in idle.items()
            if name in SPANS or name.startswith(NESTED)]
    if not held or run.trace.window_s <= 0:
        return None
    return 100.0 * sum(held) / run.trace.window_s

"""plan_traces: solver traces staged through ``repro.api.plan`` during the
window (``trace_count()`` delta).  0 expected: a trace in the window is
set-up leaking into the measure."""


def read(run):
    return run.plan_traces

"""host_syncs: device-to-host reads per call, the ``repro.gk.sync`` host
spans that start in the traced window over the calls completed in it.
The host loop reads α₁ once, then both recurrence scalars once per GK
iteration: ``gk_iters + 1`` a call."""


def read(run):
    count = getattr(run.trace, "span_count", None) or {}
    syncs = count.get("repro.gk.sync")
    if not syncs or run.solves <= 0:
        return None
    return syncs / run.solves

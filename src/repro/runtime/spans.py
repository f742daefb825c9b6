"""Named spans at the layer boundaries of the solve path.

``span(name)`` is a ``jax.profiler.TraceAnnotation(name)`` and, while JAX
traces a program, a ``jax.named_scope(name)`` as well.  Run eagerly, it
writes a host span into the profiler's trace, on the clock of the device
planes; while tracing, it also tags the operations it encloses, so their
HLO ``op_name``, and the device trace's ``tf_op``, carry ``name`` as one
segment of the path.  Eager operations keep the names they were compiled
with, so the scope is entered only under a trace: entered eagerly it
would cost the host loop's eager dispatch time and tag nothing.  With the
profiler off a span records nothing; the profiler keeps spans in memory
and writes them when the trace stops.

Names share the ``repro.`` namespace and say what the time is for:
``repro.plan.*`` (entry points), ``repro.gk.*`` (GK half-steps and host
syncs), ``repro.op.*`` (operator sweeps and
reorthogonalization), ``repro.rank.*`` (the rank count).

Dependency-free apart from ``jax``, so ``repro.core`` imports it without
a cycle.
"""
from __future__ import annotations

import jax
from jax.core import trace_ctx


class span:
    """A host span and, under a trace, a device scope named ``name``."""

    __slots__ = ("name", "_annotation", "_scope")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._scope = (None if trace_ctx.is_top_level()
                       else jax.named_scope(self.name))
        if self._scope is not None:
            self._scope.__enter__()

    def __exit__(self, *exc):
        if self._scope is not None:
            self._scope.__exit__(*exc)
        self._annotation.__exit__(*exc)

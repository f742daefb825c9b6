"""Runtime: train-state/step builders, the fault-tolerant training loop,
serving telemetry, the fault-injection (failpoint) registry and the
solve path's profiler spans (``repro.runtime.spans``).

Train-loop members resolve lazily (PEP 562): ``repro.runtime.faults`` is
compiled into hot serving/checkpoint paths, and importing it must not
drag the trainer/model stack into a solve server's process.
"""
from repro.runtime import faults  # dependency-free; safe eagerly

__all__ = ["TrainState", "build_train_step", "build_eval_step", "Trainer",
           "faults"]


def __getattr__(name):
    if name in ("TrainState", "build_train_step", "build_eval_step"):
        from repro.runtime import steps
        return getattr(steps, name)
    if name == "Trainer":
        from repro.runtime.trainer import Trainer
        return Trainer
    raise AttributeError(f"module 'repro.runtime' has no attribute {name!r}")

"""Wall time the host spends in ``dst:train/dispatch`` a training step (the
call of the step program: argument handling and the runtime's enqueue),
outside the profiler's slice: the median over the window's unprofiled steps of
``phases["train/dispatch"]`` in the program's step timeline.  The device
waits through it: ``benchmarks/layer_metrics/_step_timeline.py``."""

from benchmarks.layer_metrics import _step_timeline


def compute(record, trace):
    return _step_timeline.host_ms(record, "dispatch")

"""Wall time the host spends in ``dst:train/report`` and
``dst:train/readback`` a training step (keeping the new state, the step's
report and what it reads back for it), outside the profiler's slice: the
median over the window's unprofiled steps of those phases in the program's
step timeline: ``benchmarks/layer_metrics/_step_timeline.py``."""

from benchmarks.layer_metrics import _step_timeline


def compute(record, trace):
    return _step_timeline.host_ms(record, "report")

"""Wall time between the end of one ``train_batch`` and the start of the
next (a step record's ``t1`` to the next one's ``t0``), outside the
profiler's slice, the median over the window's unprofiled steps: the caller's
own code (the runner's batch, its wait for the step before) while the
device, fenced, has nothing to do:
``benchmarks/layer_metrics/_step_timeline.py``."""

from benchmarks.layer_metrics import _step_timeline


def compute(record, trace):
    return _step_timeline.host_ms(record, "outside")

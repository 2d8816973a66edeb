"""Wall time the host spends in ``dst:train/input`` a training step
(stacking the microbatches and putting them on the device; a prefetching
loader's pull is inside it), outside the profiler's slice: the median over the
window's unprofiled steps of ``phases["train/input"]`` in the program's step
timeline.  The device waits through it (the engine fences every step):
``benchmarks/layer_metrics/_step_timeline.py``."""

from benchmarks.layer_metrics import _step_timeline


def compute(record, trace):
    return _step_timeline.host_ms(record, "input")

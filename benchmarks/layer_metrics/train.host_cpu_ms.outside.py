"""CPU time of the process a training step that the step's own thread did
not spend inside ``train_batch``, outside the profiler's slice:
``train.host_cpu_ms.step`` less that thread's ``time.thread_time()`` from the
step's start to its end (``thread_cpu0`` to ``thread_cpu1`` in the program's
step timeline), the mean over the window's unprofiled steps.  What is left is
every other thread of the process (the runtime's) and the caller between two
``train_batch`` calls: ``benchmarks/layer_metrics/_step_timeline.py`` (a
mean, not a median: the chip machine's CPU clocks tick every 10 ms)."""

from benchmarks.layer_metrics import _step_timeline


def compute(record, trace):
    return _step_timeline.host_cpu_ms(record, "outside")

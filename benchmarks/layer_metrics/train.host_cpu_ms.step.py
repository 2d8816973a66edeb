"""CPU time of the whole process a training step, outside the profiler's
slice: ``time.process_time()`` from a step's start to the next step's, the
mean over the window's unprofiled steps, from the program's own step timeline
(``telemetry.step_timeline()``: ``cpu0`` to ``cpu0``).  Every thread counts,
the runtime's among them.  It is the number that tells a process's two speeds
apart (PERF.md section 7), now a step at a time and from inside:
``benchmarks/layer_metrics/_step_timeline.py`` (a mean, not a median: the
chip machine's CPU clocks tick every 10 ms)."""

from benchmarks.layer_metrics import _step_timeline


def compute(record, trace):
    return _step_timeline.host_cpu_ms(record, "step")

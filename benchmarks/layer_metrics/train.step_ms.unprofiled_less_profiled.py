"""Median time of a training step outside the profiler's slice less that
inside it, each from a step's start to the next step's on the program's own
clock (``telemetry.step_timeline()``: ``t0`` to ``t0``, apart by
``profiled``).  A process runs at one of two speeds about 7.5 ms a step apart
and a slow one is fast for exactly the steps the profiler is on (PERF.md
section 7): about 0 says the run drew the fast speed, about 7.5 the slow one,
so every traced run says which it was.  No number without three steps of
each kind: ``benchmarks/layer_metrics/_step_timeline.py``."""

from benchmarks.layer_metrics import _step_timeline


def compute(record, trace):
    return _step_timeline.unprofiled_less_profiled_ms(record)

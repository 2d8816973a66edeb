"""Device-idle time of one training step while the host was in none of the
three phases that have a metric of their own: the caller's code between two
``train_batch`` calls (the harness draws the next batch there), and the
engine's ``dst:train/report`` and ``dst:train/readback``.  With
``train.idle_ms.fence``, ``.input`` and ``.dispatch`` it adds up to the
device's idle time a step."""

from benchmarks import program_trace


def compute(record, trace):
    found = program_trace.for_reader(record, trace)
    return found and found.idle_ms_per_step(program_trace.OUTSIDE)

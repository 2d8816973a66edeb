"""Device-idle time of one training step while the host was in
``dst:train/dispatch``: the step's rng program and the call of the step program until it returns.
Each idle gap of the device is cut along the innermost program span over
each part of it: benchmarks/program_trace.py."""

from benchmarks import program_trace


def compute(record, trace):
    found = program_trace.for_reader(record, trace)
    return found and found.idle_ms_per_step("dispatch")

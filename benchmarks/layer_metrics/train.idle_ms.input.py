"""Device-idle time of one training step while the host was in
``dst:train/input`` (or ``dst:train/prefetch`` inside it): stacking the microbatches and putting the batch on the device.
Each idle gap of the device is cut along the innermost program span over
each part of it: benchmarks/program_trace.py."""

from benchmarks import program_trace


def compute(record, trace):
    found = program_trace.for_reader(record, trace)
    return found and found.idle_ms_per_step("input")

"""Device time of one training step under the scope ``head_ce``: the final norm, the head GEMM and the cross entropy, forward and backward.
Scope by scope: benchmarks/program_trace.py."""

from benchmarks import program_trace


def compute(record, trace):
    found = program_trace.for_reader(record, trace)
    return found and found.scope_ms_per_step("head_ce")

"""Device time of one training step under the scope ``attention``: a layer's input norm, QKV GEMM, rotary, the layout copies around the kernel, the flash kernel itself and the output projection, forward, recomputed and backward.
Scope by scope: benchmarks/program_trace.py."""

from benchmarks import program_trace


def compute(record, trace):
    found = program_trace.for_reader(record, trace)
    return found and found.scope_ms_per_step("attention")

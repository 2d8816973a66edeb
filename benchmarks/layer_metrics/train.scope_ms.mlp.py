"""Device time of one training step under the scope ``mlp``: a layer's post-attention norm, the two GEMMs and the GELU, forward, recomputed and backward.
Scope by scope: benchmarks/program_trace.py."""

from benchmarks import program_trace


def compute(record, trace):
    found = program_trace.for_reader(record, trace)
    return found and found.scope_ms_per_step("mlp")

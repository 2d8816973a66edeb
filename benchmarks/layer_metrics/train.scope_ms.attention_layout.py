"""Device time of one training step under ``attention_layout``, a part of ``train.scope_ms.attention``: the ``[B,S,N,D] <-> [B*N,S,D]`` transposes and reshapes on both sides of the flash kernel and the split of the fused QKV.
Scope by scope: benchmarks/program_trace.py."""

from benchmarks import program_trace


def compute(record, trace):
    found = program_trace.for_reader(record, trace)
    return found and found.scope_ms_per_step("attention_layout")

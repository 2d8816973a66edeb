"""Device time of one training step under the scope ``moe_experts``, a part of ``train.scope_ms.mlp``: a chunk of the routed walk's two matmuls by its expert's matrices and the activation between them, forward and backward.
``program_trace.SCOPES`` does not know the scope: benchmarks/hybrid_trace.py."""

from benchmarks import hybrid_trace


def compute(record, trace):
    return hybrid_trace.scope_ms_per_step(record, trace, "moe_experts")

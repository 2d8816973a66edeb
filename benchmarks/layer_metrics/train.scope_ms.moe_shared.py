"""Device time of one training step under the scope ``moe_shared``, a part of ``train.scope_ms.mlp``: the shared expert of every sparse layer (its gate, up and down projections on every token, beside the routed sum), forward, recomputed and backward.
``program_trace.SCOPES`` does not know the scope: benchmarks/hybrid_trace.py."""

from benchmarks import hybrid_trace


def compute(record, trace):
    return hybrid_trace.scope_ms_per_step(record, trace, "moe_shared")

"""Device time of one training step under the scope ``moe_route``, a part of ``train.scope_ms.mlp``: an expert layer's routing (the float32 scores over all experts, top-k, the slot plan, each chunk's gather of its rows and the weighted add of its results), forward, recomputed and backward.
``program_trace.SCOPES`` does not know the scope: benchmarks/hybrid_trace.py."""

from benchmarks import hybrid_trace


def compute(record, trace):
    return hybrid_trace.scope_ms_per_step(record, trace, "moe_route")

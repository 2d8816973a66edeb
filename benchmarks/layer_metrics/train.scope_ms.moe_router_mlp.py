"""Device time of one training step under the scope ``moe_router_mlp``, a part of ``train.scope_ms.mlp``: what precedes ``moe_route`` in a layer whose router is an MLP with state (the float32 down-projection of the sublayer's input, the depth averaging with the previous layer's state, the norm and the three-matrix GELU MLP that gives the logits).
``program_trace.SCOPES`` does not know the scope: benchmarks/hybrid_trace.py."""

from benchmarks import hybrid_trace


def compute(record, trace):
    return hybrid_trace.scope_ms_per_step(record, trace, "moe_router_mlp")

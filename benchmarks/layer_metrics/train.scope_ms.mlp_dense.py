"""Device time of one training step under the scope ``mlp_dense``, a part of ``train.scope_ms.mlp``: the gated MLP of the layers whose ``mlp_layer_types`` is ``dense`` (gate, up and down projections at ``intermediate_size``), forward, recomputed and backward.
``program_trace.SCOPES`` does not know the scope: benchmarks/hybrid_trace.py."""

from benchmarks import hybrid_trace


def compute(record, trace):
    return hybrid_trace.scope_ms_per_step(record, trace, "mlp_dense")

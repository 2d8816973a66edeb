"""Device time of one training step under the scope ``dsa_select``, a part of ``train.scope_ms.attention``: the indexer's scores over the causal triangle and each row's 2048 best of them, exactly (the kernel dsa_select and the count of chosen pairs a tile), once a step a layer: a recomputed layer keeps the packed selection.
``program_trace.SCOPES`` does not know the scope: benchmarks/hybrid_trace.py."""

from benchmarks import hybrid_trace


def compute(record, trace):
    return hybrid_trace.scope_ms_per_step(record, trace, "dsa_select")

"""Device time of one training step under the scope ``dsa_index``, a part of ``train.scope_ms.attention``: the indexer's three projections of the sublayer's input held constant, its key's LayerNorm and its rotary (the scores themselves are made inside the selection's kernel: dsa_select).
``program_trace.SCOPES`` does not know the scope: benchmarks/hybrid_trace.py."""

from benchmarks import hybrid_trace


def compute(record, trace):
    return hybrid_trace.scope_ms_per_step(record, trace, "dsa_index")

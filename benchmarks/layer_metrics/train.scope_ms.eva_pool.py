"""Device time of one training step under the scope ``eva_pool``, a part of ``train.scope_ms.attention``: the chunk summaries of every EVA attention sublayer (the two pooling logits a key, their softmax over a chunk's 16 rows and the two pooled sums), forward, recomputed and backward.
``program_trace.SCOPES`` does not know the scope: benchmarks/hybrid_trace.py."""

from benchmarks import hybrid_trace


def compute(record, trace):
    return hybrid_trace.scope_ms_per_step(record, trace, "eva_pool")

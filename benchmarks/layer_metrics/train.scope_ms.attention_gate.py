"""Device time of one training step under the scope ``attention_gate``, a part of ``train.scope_ms.attention``: the per-head output gate of every attention sublayer (the gate's projection ``u W_g``, its sigmoid and the product with the attention output), forward, recomputed and backward.
``program_trace.SCOPES`` does not know the scope: benchmarks/hybrid_trace.py."""

from benchmarks import hybrid_trace


def compute(record, trace):
    return hybrid_trace.scope_ms_per_step(record, trace, "attention_gate")

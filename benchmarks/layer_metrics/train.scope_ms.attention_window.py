"""Device time of one training step under the scope ``attention_window``, a part of ``train.scope_ms.attention``: the whole attention sublayer (input norm, q/k/v projections, rotary, GQA's copy, the flash kernel, the output projection) of the layers whose kind is ``sliding_attention``, forward, recomputed and backward.
``program_trace.SCOPES`` does not know the scope: benchmarks/hybrid_trace.py."""

from benchmarks import hybrid_trace


def compute(record, trace):
    return hybrid_trace.scope_ms_per_step(record, trace, "attention_window")

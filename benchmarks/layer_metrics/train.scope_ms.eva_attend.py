"""Device time of one training step under the scope ``eva_attend``, a part of ``train.scope_ms.attention``: every EVA attention sublayer from q, k, v and the summaries to the mixed output (the kernel pair eva_attention and the reshapes around it), forward, recomputed and backward.
``program_trace.SCOPES`` does not know the scope: benchmarks/hybrid_trace.py."""

from benchmarks import hybrid_trace


def compute(record, trace):
    return hybrid_trace.scope_ms_per_step(record, trace, "eva_attend")

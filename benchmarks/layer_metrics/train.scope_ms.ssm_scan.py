"""Device time of one training step under the scope ``ssm_scan``, a part of ``train.scope_ms.ssm``: the chunked state-space scan alone (``ops/ssm.py``: the chunks' masked products, the states pushed, the scan over chunks, the states read), forward, recomputed and backward.
``program_trace.SCOPES`` does not know the scope: benchmarks/hybrid_trace.py."""

from benchmarks import hybrid_trace


def compute(record, trace):
    return hybrid_trace.scope_ms_per_step(record, trace, "ssm_scan")

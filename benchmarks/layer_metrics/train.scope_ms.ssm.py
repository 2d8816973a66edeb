"""Device time of one training step under the scope ``ssm``, a Mamba-2 layer whole: its norm, the in-projection, the convolution, the chunked scan (``ssm_scan``, inside), the gated group norm and the out-projection, forward, recomputed and backward.
``program_trace.SCOPES`` does not know the scope: benchmarks/hybrid_trace.py."""

from benchmarks import hybrid_trace


def compute(record, trace):
    return hybrid_trace.scope_ms_per_step(record, trace, "ssm")

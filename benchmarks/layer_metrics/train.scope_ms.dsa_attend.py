"""Device time of one training step under the scope ``dsa_attend``, a part of ``train.scope_ms.attention``: the attention over each row's chosen keys from q, k, v and the packed selection to the mixed output (the kernels dsa_attention forward, dq and dk/dv, and the reshapes around them), forward and backward.
``program_trace.SCOPES`` does not know the scope: benchmarks/hybrid_trace.py."""

from benchmarks import hybrid_trace


def compute(record, trace):
    return hybrid_trace.scope_ms_per_step(record, trace, "dsa_attend")

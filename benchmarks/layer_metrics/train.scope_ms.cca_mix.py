"""Device time of one training step under the scope ``cca_mix``, a part of ``train.scope_ms.attention``: everything between the compressed attention's projections and the flash kernel (the value shift, the depthwise and the per-head convolution on the packed q | k latent, the q-k mean, unit-RMS heads, k's temperature, rotary on half a head), forward, recomputed and backward.
``program_trace.SCOPES`` does not know the scope: benchmarks/hybrid_trace.py."""

from benchmarks import hybrid_trace


def compute(record, trace):
    return hybrid_trace.scope_ms_per_step(record, trace, "cca_mix")

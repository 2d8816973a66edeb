"""Device time of one training step under the scope ``mla_latent``, a part of ``train.scope_ms.attention``: everything between latent attention's input and its flash kernel but q's projection (the 576-wide down-projection, the latent's RMSNorm, the two up-projections to k_nope and v, the split and the rotary on q's rotary part and on the ONE shared rotary key), forward, recomputed and backward.
``program_trace.SCOPES`` does not know the scope: benchmarks/hybrid_trace.py."""

from benchmarks import hybrid_trace


def compute(record, trace):
    return hybrid_trace.scope_ms_per_step(record, trace, "mla_latent")

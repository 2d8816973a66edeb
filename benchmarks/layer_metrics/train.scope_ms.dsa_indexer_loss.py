"""Device time of one training step under the scope ``dsa_indexer_loss``, a part of ``train.scope_ms.attention``: the indexer's loss and its gradients, made in one pass over the rows once a step a layer: the head-averaged probabilities of the main attention over the chosen keys (the kernel dsa_head_probs), the indexer's scores a second time, the KL and what it sends to the indexer's queries, key and weights (plain jnp by row chunks).
``program_trace.SCOPES`` does not know the scope: benchmarks/hybrid_trace.py."""

from benchmarks import hybrid_trace


def compute(record, trace):
    return hybrid_trace.scope_ms_per_step(record, trace, "dsa_indexer_loss")

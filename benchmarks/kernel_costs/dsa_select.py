"""Operations and bytes the indexer's scores and each row's selection need,
from the shapes: ``I[t, s] = sum_j w[t, j] relu(q_j[t] . k[s])`` over the
CAUSAL pairs, ``2 D_I`` FLOPs a pair and indexer head (the weighting, the
ReLU and the selection's counting passes are not matmul work and count
nothing: the share says how far the kernel is from a pass that only
scored); the indexer's queries, key and weights read once, a bit a causal
pair of the selection written."""


def forward(batch, heads, seq, head_dim, itemsize=2):
    causal = seq * (seq + 1) // 2
    return {"flops": 2.0 * batch * heads * causal * head_dim,
            "bytes": batch * ((heads + 1) * seq * head_dim * itemsize
                              + heads * seq * 4 + seq * seq // 8)}

"""Operations and bytes that attention over each row's chosen keys needs,
from its shapes: the CHOSEN pairs only.

Row ``t`` keeps ``min(t + 1, topk)`` keys: a sequence's rows keep ``k (k +
1) / 2 + (S - k) k`` (row, key) pairs, ``k = min(topk, S)`` (``pairs``).
Forward: the two matmuls ``Q K^T`` and ``P V`` are 2 * D multiply-adds =
``4 D`` FLOPs a pair and query head.  Backward: five matmuls of the same
size (recomputed ``Q K^T``, ``dV``, ``dP``, ``dQ``, ``dK``), 2.5x the
forward, whatever the number of passes the kernels take (the two-pass
backward makes ``Q K^T`` and ``dP`` twice).  What a kernel computes beside
the chosen pairs (the rest of every tile some row chose in) counts nothing
here: the least work, so a share of the roofline says what skipping could
still buy and cannot read over 100 % by counting pairs a kernel may skip.
Bytes are the least traffic to HBM: each operand read once (q and o at the
query heads, k and v at the KV heads), each result written once, a bit a
causal pair of the selection.
"""


def pairs(seq, topk):
    k = min(int(topk), int(seq))
    return k * (k + 1) // 2 + (int(seq) - k) * k


def _tensors(batch, heads, kv_heads, seq, head_dim, itemsize):
    return (batch * heads * seq * head_dim * itemsize,
            batch * kv_heads * seq * head_dim * itemsize,
            batch * heads * seq * 4,            # a float a row and head
            batch * seq * seq // 8)             # the selection, a bit a pair


def forward(batch, heads, kv_heads, seq, head_dim, topk, itemsize=2):
    wide, thin, stats, bits = _tensors(batch, heads, kv_heads, seq, head_dim,
                                       itemsize)
    return {"flops": 4.0 * batch * heads * pairs(seq, topk) * head_dim,
            # q -> o, k and v read, the log-sum-exp written
            "bytes": 2 * wide + 2 * thin + stats + bits}


def backward(batch, heads, kv_heads, seq, head_dim, topk, itemsize=2):
    """Both backward kernels (dq; dk and dv) of one call together."""
    wide, thin, stats, bits = _tensors(batch, heads, kv_heads, seq, head_dim,
                                       itemsize)
    fwd = forward(batch, heads, kv_heads, seq, head_dim, topk, itemsize)
    # reads q do (o went into delta outside) k v + lse and delta, writes dq
    # dk dv
    return {"flops": 2.5 * fwd["flops"],
            "bytes": 3 * wide + 4 * thin + 2 * stats + bits}

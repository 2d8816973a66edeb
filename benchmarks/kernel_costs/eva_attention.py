"""Operations and bytes that EVA attention needs, from its shapes: the pairs
INSIDE the mask only.

A row sees the keys of its own window up to itself, ``W (W + 1) / 2`` pairs
a window, and ``W / C`` summaries for every earlier window: with ``n = S /
W`` windows a head's rows see ``n W (W + 1) / 2 + W (W / C) n (n - 1) / 2``
(row, key) pairs (``pairs``).  Forward: the two matmuls ``Q K^T`` and ``P
V`` are 2 * D multiply-adds = ``4 D`` FLOPs a pair.  Backward: five matmuls
of the same size (recomputed ``Q K^T``, ``dV``, ``dP``, ``dQ``, ``dK``),
2.5x the forward.  What a kernel computes beside the mask (the halves of the
tiles the diagonal crosses) counts nothing here: the least work, so a share
of the roofline cannot read over 100 % by counting pairs the kernel may skip.
Bytes are the least traffic to HBM: each operand read once, each result
written once; the summaries are ``S / C`` rows a side beside ``S``.
"""


def pairs(seq, window, chunk):
    n, rest = divmod(int(seq), int(window))
    per = window // chunk
    return (n * window * (window + 1) // 2 + rest * (rest + 1) // 2
            + per * (window * n * (n - 1) // 2 + rest * n))


def forward(batch, heads, seq, head_dim, window, chunk, itemsize=2):
    flops = 4.0 * batch * heads * pairs(seq, window, chunk) * head_dim
    tensor = batch * heads * seq * head_dim * itemsize
    pooled = tensor // chunk
    row_stats = batch * heads * seq * 4              # log-sum-exp, float32
    # q k v -> o, and both summaries read
    return {"flops": flops, "bytes": 4 * tensor + 2 * pooled + row_stats}


def backward(batch, heads, seq, head_dim, window, chunk, itemsize=2):
    fwd = forward(batch, heads, seq, head_dim, window, chunk, itemsize)
    tensor = batch * heads * seq * head_dim * itemsize
    pooled = tensor // chunk
    row_stats = batch * heads * seq * 4
    # reads q k v o do + lse and both summaries, writes dq dk dv and theirs
    return {"flops": 2.5 * fwd["flops"],
            "bytes": 8 * tensor + 4 * pooled + row_stats}

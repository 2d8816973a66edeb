"""Operations and bytes that causal flash attention under a sliding window
needs, from its shapes: the BAND's pairs only.

Row i sees the columns ``i - window < j <= i``: ``window * S - window *
(window - 1) / 2`` (row, column) pairs a head (``pairs``), the triangle's
``S (S + 1) / 2`` where the window reaches the whole length.  Forward: the
two matmuls ``Q K^T`` and ``P V`` are 2 * D multiply-adds = ``4 D`` FLOPs a
pair.  Backward: five matmuls of the same size (recomputed ``Q K^T``,
``dV``, ``dP``, ``dQ``, ``dK``), 2.5x the forward.  What a kernel computes
beside the band (the halves of the tiles an edge crosses, whole tiles it
does not skip) counts nothing here: the least work, so a share of the
roofline cannot read over 100 % by counting pairs the kernel may skip.
Bytes are the least traffic to HBM: each operand read once, each result
written once, as ``kernel_costs/flash_attention`` counts them.
"""


def pairs(seq, window):
    w = min(int(window), int(seq))
    return w * seq - w * (w - 1) // 2


def forward(batch, heads, seq, head_dim, window, itemsize=2):
    flops = 4.0 * batch * heads * pairs(seq, window) * head_dim
    tensor = batch * heads * seq * head_dim * itemsize
    row_stats = batch * heads * seq * 4              # log-sum-exp, float32
    return {"flops": flops, "bytes": 4 * tensor + row_stats}  # q k v -> o


def backward(batch, heads, seq, head_dim, window, itemsize=2):
    fwd = forward(batch, heads, seq, head_dim, window, itemsize)
    tensor = batch * heads * seq * head_dim * itemsize
    row_stats = batch * heads * seq * 4
    # reads q k v o do + lse, writes dq dk dv
    return {"flops": 2.5 * fwd["flops"], "bytes": 8 * tensor + row_stats}

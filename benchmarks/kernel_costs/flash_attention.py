"""Operations and bytes that causal flash attention needs, from its shapes.

Forward: the two matmuls ``Q K^T`` and ``P V`` are ``2 * S * S * D``
multiply-adds = ``4 S^2 D`` FLOPs per head each counted once, halved for
causality (only the lower triangle is needed).  Backward: five matmuls of
the same size (recomputed ``Q K^T``, ``dV``, ``dP``, ``dQ``, ``dK``), 2.5x
the forward.  Bytes are the least traffic to HBM: each operand read once,
each result written once; the S x S scores never leave the chip.
"""


def forward(batch, heads, seq, head_dim, itemsize=2, causal=True):
    share = 0.5 if causal else 1.0
    flops = 4.0 * batch * heads * seq * seq * head_dim * share
    tensor = batch * heads * seq * head_dim * itemsize
    row_stats = batch * heads * seq * 4              # log-sum-exp, float32
    return {"flops": flops, "bytes": 4 * tensor + row_stats}  # q k v -> o


def backward(batch, heads, seq, head_dim, itemsize=2, causal=True):
    fwd = forward(batch, heads, seq, head_dim, itemsize, causal)
    tensor = batch * heads * seq * head_dim * itemsize
    row_stats = batch * heads * seq * 4
    # reads q k v o do + lse, writes dq dk dv
    return {"flops": 2.5 * fwd["flops"], "bytes": 8 * tensor + row_stats}


def train_step(layers, batch, heads, seq, head_dim, remat, itemsize=2):
    """All attention calls of one training step: per layer a forward, a
    second forward when the layer is recomputed, and a backward.  ``layers``
    may be any number of (layer, step) pairs, whole or not."""
    f = forward(batch, heads, seq, head_dim, itemsize)
    b = backward(batch, heads, seq, head_dim, itemsize)
    n_fwd = 2 if remat else 1
    return {"flops": layers * (n_fwd * f["flops"] + b["flops"]),
            "bytes": layers * (n_fwd * f["bytes"] + b["bytes"])}

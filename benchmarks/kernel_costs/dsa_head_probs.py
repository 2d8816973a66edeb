"""Operations and bytes the head-averaged probabilities of the main
attention over each row's chosen keys need (what the indexer's loss
imitates), from the shapes: the CHOSEN pairs only.  One matmul ``Q K^T``,
``2 D`` FLOPs a pair and query head (the exponentials and the mean over the
heads are not matmul work); q and k read once, a float a row and head of
log-sum-exp, a bit a causal pair of the selection, and a float32 a chosen
pair written.  The kernel writes a float32 for EVERY pair of a chunk of rows
(zero outside the chosen): what it moves beside counts nothing here."""

from benchmarks import core


def layer(batch, heads, kv_heads, seq, head_dim, topk, itemsize=2):
    """One layer's calls together (a call is a chunk of rows)."""
    pairs = core.load_kernel_cost("dsa_attention").pairs(seq, topk)
    return {"flops": 2.0 * batch * heads * pairs * head_dim,
            "bytes": batch * ((heads + kv_heads) * seq * head_dim * itemsize
                              + heads * seq * 4 + seq * seq // 8
                              + 4 * pairs)}

"""Operations and bytes that the routed experts' grouped matmuls need in one
training step of one expert layer, from its shapes and the slots it was sent.

A slot is one (token, chosen expert) pair held here.  Forward it is two
products: its ``latent`` row through the expert's ``[latent, w_in]`` matrix
(``w_in`` is twice ``intermediate`` where gate and up lie side by side) and
its ``intermediate`` row through ``[intermediate, latent]``: ``2 (L W_in +
F L)`` FLOPs.  A recomputed layer runs them a second time, and the backward
pass takes each product's two transposes, twice the forward: ``8 (L W_in +
F L)`` a slot under remat, ``6`` without (``tools/profile_moe_walk.py``
counts the same).  CHOSEN slots only: the rows a kernel multiplies beside
them (a tile an expert fills partly, ``moe_rows_computed``) count nothing,
so padding is a loss and a share of the roofline cannot pass what the MXU
did.  Bytes are the least traffic to HBM: each product reads its two
operands and writes its result once, a slot's rows and, an expert held, its
matrices (bf16), and the weights' gradient is written once in float32.
"""


def slot_flops(latent, intermediate, gated, remat):
    w_in = (2 if gated else 1) * intermediate
    return (8.0 if remat else 6.0) * (latent * w_in + intermediate * latent)


def train_step(slots, experts, latent, intermediate, gated=True, remat=True,
               itemsize=2):
    """One expert layer's step at ``slots`` chosen slots over ``experts``
    held experts -> {"flops", "bytes"}."""
    w_in = (2 if gated else 1) * intermediate
    weights = experts * (latent * w_in + intermediate * latent)
    forward_rows = latent + w_in + intermediate + latent
    # dy -> da and dW_out; dh -> dx and dW_in: two operands read, one written
    backward_rows = 2 * (latent + intermediate) + 2 * (w_in + latent)
    passes = 2 if remat else 1
    return {
        "flops": slots * slot_flops(latent, intermediate, gated, remat),
        "bytes": (slots * (passes * forward_rows + backward_rows) * itemsize
                  + (passes + 1) * weights * itemsize + weights * 4)}

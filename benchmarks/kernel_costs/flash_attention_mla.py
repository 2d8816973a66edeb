"""Operations and bytes that causal latent attention (MLA) needs in training,
from its shapes, whatever kernel implements it.

A head's score is ``q_nope . k_nope + q_rope . k_rope`` over ``d_nope +
d_rope``; its value and output are ``d_v`` wide.  Forward: the score and ``P
V`` on the causal half of the square, ``2 B N S^2 / 2 (d_nope + d_rope +
d_v)`` FLOPs.  Backward: the recomputed score and ``dQ`` and ``dK`` over the
score's width (``d_nope + d_rope`` each), ``dP`` and ``dV`` over the value's
(``d_v`` each).  Bytes are the least traffic to HBM, each operand once at
the heads it HAS: the rotary key is ONE head (``k_rope`` and ``dk_rope`` are
``[B, S, d_rope]``), the S x S scores never leave the chip.  A kernel that
pads a product or copies the rotary key to the heads does more than this
count and reads a smaller share of its roofline.
"""


def _square(B, N, S):
    """Multiply-adds' worth of FLOPs a unit of width on the causal half."""
    return 2.0 * B * N * S * S * 0.5


def _operands(B, S, N, d_nope, d_rope, d_v, itemsize):
    """Bytes of (what has every head's q side and k_nope, the one rotary
    key, a value-shaped tensor, the row statistic)."""
    rows = B * S * itemsize
    return (rows * N * (2 * d_nope + d_rope), rows * d_rope,
            rows * N * d_v, B * N * S * 4)


def forward(B, S, N, d_nope, d_rope, d_v, itemsize=2):
    qk, shared, value, stat = _operands(B, S, N, d_nope, d_rope, d_v,
                                        itemsize)
    return {"flops": _square(B, N, S) * (d_nope + d_rope + d_v),
            # q_nope q_rope k_nope, k_rope, v -> o, lse
            "bytes": qk + shared + 2 * value + stat}


def backward(B, S, N, d_nope, d_rope, d_v, itemsize=2):
    qk, shared, value, stat = _operands(B, S, N, d_nope, d_rope, d_v,
                                        itemsize)
    return {"flops": _square(B, N, S) * (3 * (d_nope + d_rope) + 2 * d_v),
            # reads the forward's operands, o, do, lse; writes five gradients
            "bytes": 2 * (qk + shared) + 4 * value + stat}


def step_work(passes, **shape):
    """Operations and bytes of one step's kernel calls: every forward call
    (first or recomputed) a forward's, each layer (a first forward call) one
    backward's."""
    f, b = forward(**shape), backward(**shape)
    n_fwd = passes["forward"] + passes["recomputed"]
    n_bwd = passes["forward"] if passes["backward"] else 0
    return {key: n_fwd * f[key] + n_bwd * b[key] for key in ("flops", "bytes")}

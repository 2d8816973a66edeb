"""Operations and bytes that Mamba-2's chunked SSD scan needs, from its
shapes.

Forward, per token: per head the chunk's masked product ``(scores * decay)
(dt x)`` (2 Q P), the state the chunk pushes and the state it reads (2 P N
each); per group the scores ``C B^T`` (2 Q N), computed once a group.  These
are the matmuls ``reference/nemotron_h_ref.scan_flops_per_token`` counts (less
its convolution term).  Backward: each forward matmul's two transposes, twice
the forward; what a backward kernel recomputes of the forward is not counted
here (a recomputed forward *call* is a forward call: the reader counts calls
by ``telemetry.kernel_passes()``).  Bytes are the least traffic to HBM: each
operand read once and each result written once; the ``[Q, Q]`` products, the
states and whatever a forward call keeps for the backward never count.  The
least work on both sides, so a share of the roofline cannot read over 100 %.
"""


def _tensors(batch, seq, heads, groups, head_dim, state, itemsize):
    wide = batch * seq * heads * head_dim * itemsize        # x, y, dy, dx
    narrow = batch * seq * groups * state * itemsize        # b, c, db, dc
    steps = batch * seq * heads * 4                         # dt, ddt: float32
    return wide, narrow, steps


def forward(batch, seq, heads, groups, head_dim, state, chunk, itemsize=2):
    per_token = (heads * (2 * chunk * head_dim + 4 * head_dim * state)
                 + groups * 2 * chunk * state)
    wide, narrow, steps = _tensors(batch, seq, heads, groups, head_dim, state,
                                   itemsize)
    # x b c dt -> y
    return {"flops": float(batch * seq * per_token),
            "bytes": 2 * wide + 2 * narrow + steps}


def backward(batch, seq, heads, groups, head_dim, state, chunk, itemsize=2):
    fwd = forward(batch, seq, heads, groups, head_dim, state, chunk, itemsize)
    wide, narrow, steps = _tensors(batch, seq, heads, groups, head_dim, state,
                                   itemsize)
    # reads x dy b c dt, writes dx db dc ddt
    return {"flops": 2.0 * fwd["flops"],
            "bytes": 3 * wide + 4 * narrow + 2 * steps}

"""Causal flash attention whose score is the sum of two products: latent
attention's training half (MLA, DeepSeek-V2 section 2.1).

A head's score is ``q_nope . k_nope + q_rope . k_rope``: a product of its
own over ``d_nope`` and one over ``d_rope`` against a rotary key that ALL
the heads share; its value and output are ``d_v`` wide, which need not be
the score's ``d_nope + d_rope``.  ``pallas_flash.mha`` takes one ``D``, so
it could run this only on a rotary key copied to every head and a value
padded to the score's width.  This file is its sibling for that shape and
nothing else: the same tile plan (``pallas_flash.tile_plan`` at the head's
``d_nope``), the same walk (a q block against a resident span of keys in
the forward, a k/v block against a head's resident q side in the one-kernel
backward), the same masks on the diagonal alone, the same two residuals
under the same names (``SAVED_BY_REMAT``).

Operands as the projections hold them, heads as column groups:

* ``q_nope`` ``[B, S, N * d_nope]``, ``k_nope`` likewise, ``v`` ``[B, S, N *
  d_v]``: a head is a lane block of its own (``d_nope`` and ``d_v``
  multiples of 128);
* ``q_rope`` ``[B, S, N * d_rope]``: at ``d_rope`` = 64 two heads share a
  lane block, so a program loads its pair's block and zeroes the other
  head's lanes (``pallas_flash._own_lanes``, as the plain kernel's
  two-heads-a-block layout does);
* ``k_rope`` ``[B, S, d_rope]``: ONE head.  It is never copied out to the
  heads in HBM: a program lays it under each head's lanes of a pair block
  once a grid step, in VMEM (``_under_each``).

A tile's score is then ONE product, ``[q_nope | q_rope's own lanes] .
[k_nope | k_rope under each]`` over ``d_nope + 128``: the lanes zeroed in q
contribute nothing, the MXU passes are those of ``d_nope + d_rope`` (a
64-deep contraction half-fills the v5e's 128-deep MXU either way), and the
float32 score tile is touched by the softmax alone (two products would add
one pass of the VPU over every score tile, which weighs as much as a
product there: ``pallas_flash``'s docstring).  The backward's ``dK`` and
``dQ`` products are over the same joined operands: ``dk``'s rotary lanes
hold the head's share of ``dk_rope`` in its own lanes, summed over the heads
in a float32 accumulator of the whole length that stays in VMEM while the
grid walks the heads one after the other (as the grouped-query backward sums
``dk`` / ``dv`` over a group), folded to ``d_rope`` and written once, with
the last head; ``dq``'s rotary lanes go to the pair's block.

Causal only, no window, no grouped k_nope / v; lengths whose q side does
not fit VMEM are not taken (``supported`` says which calls are; the plain
path in ``core.py`` takes every shape and is this kernel's oracle).
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ..pallas_utils import LANES, NEG_INF, interpret_mode
from .pallas_flash import (_NN, _NT, _TN, _VMEM_LIMIT, SAVED_BY_REMAT, _ds,
                           _edge_tiles, _masked, _own_lanes, _pad_seq,
                           _params, _pieces, _rows_off_lanes,
                           _rows_onto_lanes, _vmem_limit, _walk, tile_plan)

#: the scope the kernels run under: their events' name in a device trace
#: and their counts in ``telemetry.kernel_paths()`` / ``kernel_passes()``
KERNEL = "flash_attention_mla"


def _pair(d_rope):
    """Heads to a lane block of ``q_rope``."""
    return LANES // d_rope if d_rope < LANES else 1


def _under_each(x, pair):
    """``[rows, d]`` -> ``[rows, pair * d]``: the one key under each head's
    lanes of a pair block."""
    return x if pair == 1 else jnp.concatenate([x] * pair, axis=1)


def _folded(x, pair):
    """``[rows, pair * d]`` -> ``[rows, d]``: the sum of the heads' lanes."""
    d = x.shape[-1] // pair
    return sum(x[:, h * d:(h + 1) * d] for h in range(pair))


def _joined(a, b):
    return jnp.concatenate([a, b], axis=1)


# --------------------------------------------------------------------- fwd
def _fwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, kr_scr, *, block, sub, pair):
    """One q block of a head against one resident span of its keys."""
    g, qi, kj = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    cps = kn_ref.shape[1] // block          # chunks per span

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    for c in range(cps):
        chunk = pl.ds(c * block, block)
        kr_scr[chunk, :] = _under_each(kr_ref[0, chunk, :], pair)

    def tile(row0, col0, pieces):
        """Softmax update of the ``sub`` rows from ``row0`` of the q block by
        the column ``pieces`` of the chunk at ``col0`` of the span."""
        rows = pl.ds(row0, sub)
        # both arrive pre-scaled
        q = _joined(qn_ref[0, rows, :],
                    _own_lanes(qr_ref[0, rows, :], g % pair, pair))
        pieces = [(pl.ds(col0 + c0, nc), mask) for c0, nc, mask in pieces]
        ss = [_masked(jax.lax.dot_general(
            q, _joined(kn_ref[0, cols, :], kr_scr[cols, :]), _NT,
            preferred_element_type=jnp.float32), mask, True, 0, 0)
            for cols, mask in pieces]
        m_prev = m_scr[rows, :1]
        m_new = functools.reduce(jnp.maximum, [m_prev] + [
            jnp.max(s, axis=1, keepdims=True) for s in ss])
        ps = [jnp.exp(s - m_new) for s in ss]
        l_new = sum(jnp.sum(p, axis=1, keepdims=True) for p in ps)
        acc = sum(
            jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0, cols, :],
                                _NN, preferred_element_type=jnp.float32)
            for p, (cols, _) in zip(ps, pieces))
        alpha = jnp.exp(m_prev - m_new)
        m_scr[rows, :] = jnp.broadcast_to(m_new, (sub, LANES))
        l_scr[rows, :] = jnp.broadcast_to(l_scr[rows, :1] * alpha + l_new,
                                          (sub, LANES))
        acc_scr[rows, :] = acc_scr[rows, :] * alpha + acc

    def interior(c):
        col0 = pl.multiple_of((c - kj * cps) * block, block)
        for row0 in range(0, block, sub):
            tile(row0, col0, _pieces(block, sub, False))

    def edge_chunk():
        col0 = pl.multiple_of((qi - kj * cps) * block, block)
        for row0, ncols in _edge_tiles(block, sub, True):
            tile(row0, col0, _pieces(ncols, sub, True))

    _walk(kj * cps, jnp.minimum((kj + 1) * cps, qi), interior)
    pl.when(qi // cps == kj)(edge_chunk)

    @pl.when(kj == pl.num_programs(3) - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)
        for r in range(0, block, sub):
            rows = pl.ds(r, sub)
            lse_ref[0, :, rows] = _rows_onto_lanes(
                m_scr[rows, :] + jnp.log(l_scr[rows, :]))


# ---------------------------------------------------------------------- bwd
def _bwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, o_ref, lse_ref,
                dqn_ref, dqr_ref, dkn_ref, dkr_ref, dv_ref,
                lse_scr, dk_scr, dv_scr, dqn_scr, dqr_scr, dkr_scr, kr_scr,
                *, block, sub, rows, n, heads, pair):
    """One k/v block of a head against the head's resident q side: dk_nope
    and dv of the block, the block's share of the head's dq (nope and, in
    the pair's block, rope), and the head's share of the block's dk_rope,
    added to the float32 sum over the heads the grid walks."""
    g, kj = pl.program_id(1), pl.program_id(2)
    dn = qn_ref.shape[2]

    @pl.when(kj == 0)
    def _init_head():
        for r in range(0, n * block, rows):
            lse_scr[pl.ds(r, rows), :] = _rows_off_lanes(
                lse_ref[0, :, pl.ds(r, rows)])
        dqn_scr[:] = jnp.zeros_like(dqn_scr)

    @pl.when(jnp.logical_and(kj == 0, g % pair == 0))
    def _init_pair():
        dqr_scr[:] = jnp.zeros_like(dqr_scr)

    dk_scr[:] = jnp.zeros_like(dk_scr)
    dv_scr[:] = jnp.zeros_like(dv_scr)
    kr_scr[:] = _under_each(kr_ref[0], pair)

    def tile(row0, nrows, pieces):
        """``nrows`` q rows from ``row0`` (within the head) against the
        column ``pieces`` of the k/v block."""
        at = _ds(row0, nrows)
        q = _joined(qn_ref[0, at, :],
                    _own_lanes(qr_ref[0, at, :], g % pair, pair))
        do = do_ref[0, at, :]
        lse = lse_scr[at, :1]
        delta = jnp.sum(do.astype(jnp.float32)
                        * o_ref[0, at, :].astype(jnp.float32),
                        axis=1, keepdims=True)
        dq = None
        for c0, nc, mask in pieces:
            cols = pl.ds(c0, nc)
            k = _joined(kn_ref[0, cols, :], kr_scr[cols, :])
            s = _masked(jax.lax.dot_general(
                q, k, _NT, preferred_element_type=jnp.float32),
                mask, True, 0, 0)
            p = jnp.exp(s - lse)
            dv_scr[cols, :] += jax.lax.dot_general(
                p.astype(do.dtype), do, _TN,
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v_ref[0, cols, :], _NT,
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(q.dtype)
            # [dk_nope | the head's dk_rope, in its own lanes]
            dk_scr[cols, :] += jax.lax.dot_general(
                ds, q, _TN, preferred_element_type=jnp.float32)
            part = jax.lax.dot_general(ds, k, _NN,
                                       preferred_element_type=jnp.float32)
            dq = part if dq is None else dq + part
        dqn_scr[at, :] += dq[:, :dn]
        dqr_scr[at, :] += _own_lanes(dq[:, dn:], g % pair, pair)

    def interior(c):
        for r in range(0, block, rows):
            tile(c * block + r, rows, _pieces(block, sub, False))

    # the chunk the diagonal crosses, then every q chunk below it
    for row0, ncols in _edge_tiles(block, sub, True):
        tile(kj * block + row0, sub, _pieces(ncols, sub, True))
    _walk(kj + 1, n, interior)

    dkn_ref[0] = dk_scr[:, :dn].astype(dkn_ref.dtype)
    dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)
    own = dk_scr[:, dn:]
    if heads == 1:
        dkr_ref[0] = _folded(own, pair).astype(dkr_ref.dtype)
    else:
        block_rows = _ds(kj * block, block)

        @pl.when(g == 0)
        def _first_head():
            dkr_scr[block_rows, :] = own

        @pl.when(jnp.logical_and(g > 0, g < heads - 1))
        def _add_head():
            dkr_scr[block_rows, :] += own

        @pl.when(g == heads - 1)
        def _last_head():
            dkr_ref[0] = _folded(dkr_scr[block_rows, :] + own,
                                 pair).astype(dkr_ref.dtype)

    @pl.when(kj == n - 1)
    def _finalize_head():
        dqn_ref[0] = dqn_scr[:].astype(dqn_ref.dtype)

    @pl.when(jnp.logical_and(kj == n - 1, g % pair == pair - 1))
    def _finalize_pair():
        dqr_ref[0] = dqr_scr[:].astype(dqr_ref.dtype)


# ------------------------------------------------------------------ calls
def _widths(qn, kr, v, heads):
    dr = kr.shape[2]
    return qn.shape[2] // heads, dr, v.shape[2] // heads, _pair(dr)


def _cost(qn, kr, v, heads, matmuls_qk, matmuls_v, tensors):
    """What a call costs, for XLA's scheduler (``pallas_flash._cost``):
    ``matmuls_qk`` products over the score's width and ``matmuls_v`` over
    the value's on the causal half of the square, each operand through HBM
    at the heads it has."""
    b, sp, _ = qn.shape
    dn, dr, dv, _ = _widths(qn, kr, v, heads)
    square = b * heads * sp * sp // 2
    return pl.CostEstimate(
        flops=2 * square * (matmuls_qk * (dn + dr) + matmuls_v * dv),
        transcendentals=square,
        bytes_accessed=tensors * qn.dtype.itemsize * b * sp * (
            heads * (2 * dn + dr + 2 * dv) + dr) + 4 * b * heads * sp)


def _fwd_call(qn, qr, kn, kr, v, plan, heads):
    b, sp, _ = qn.shape
    dn, dr, dv, pair = _widths(qn, kr, v, heads)
    block, span = plan.block, plan.span
    from jax.experimental.pallas import tpu as pltpu

    # a span wholly above the diagonal is not walked: name the last needed
    # one again, so it is not loaded either
    def kv_index(b, g, i, j):
        return (b, jnp.minimum(j, (i * block) // span), g)

    def kr_index(b, g, i, j):
        return (b, jnp.minimum(j, (i * block) // span), 0)

    itemsize = qn.dtype.itemsize
    need = (2 * span * (dn + dv + 2 * LANES) * itemsize   # k_nope, v, k_rope
            + span * LANES * itemsize                     # k_rope under each
            + 2 * block * (dn + dv + LANES) * itemsize    # q, o
            + 2 * block * LANES * 4 + 2 * 8 * block * 4   # m, l; lse out
            + block * dv * 4                              # acc
            + 3 * plan.sub * block * 4)         # a score tile, its exp, slack
    return pl.pallas_call(
        functools.partial(_fwd_kernel, block=block, sub=plan.sub, pair=pair),
        grid=(b, heads, sp // block, sp // span),
        in_specs=[
            pl.BlockSpec((1, block, dn), lambda b, g, i, j: (b, i, g)),
            pl.BlockSpec((1, block, pair * dr),
                         lambda b, g, i, j: (b, i, g // pair)),
            pl.BlockSpec((1, span, dn), kv_index),
            pl.BlockSpec((1, span, dr), kr_index),
            pl.BlockSpec((1, span, dv), kv_index)],
        out_specs=[
            pl.BlockSpec((1, block, dv), lambda b, g, i, j: (b, i, g)),
            pl.BlockSpec((1, 1, block),
                         lambda b, g, i, j: (b * heads + g, 0, i))],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b * heads, 1, sp), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, LANES), jnp.float32),
                        pltpu.VMEM((block, LANES), jnp.float32),
                        pltpu.VMEM((block, dv), jnp.float32),
                        pltpu.VMEM((span, pair * dr), kr.dtype)],
        cost_estimate=_cost(qn, kr, v, heads, 1, 1, tensors=1),
        interpret=interpret_mode(),
        **_params("parallel", "parallel", "parallel", "arbitrary",
                  vmem=_vmem_limit(need)),
    )(qn, qr, kn, kr, v)


def _bwd_need(sp, block, rows, dn, dr, dv, itemsize):
    """VMEM the backward holds per program: the head's q side (q_nope, the
    pair's q_rope, do, o) double-buffered, its lse twice (as it arrives and
    as the tiles read it), the float32 dq sums and their output blocks, the
    float32 dk_rope sum of the whole length, the k/v block with its outputs
    and sums, and a tile's temporaries."""
    wr = _pair(dr) * dr
    return (2 * sp * (dn + wr + 2 * dv) * itemsize + 2 * 8 * sp * 4
            + sp * LANES * 4 + sp * (dn + wr) * 4
            + 2 * sp * (dn + wr) * itemsize + sp * wr * 4
            + (4 * (dn + dv + LANES) + wr) * block * itemsize
            + block * (dn + wr + dv) * 4 + 5 * rows * block * 4)


def _bwd_call(qn, qr, kn, kr, v, do, o, lse, plan, heads):
    """Grid (batch, head, k/v block): the heads one after the other, so that
    the pair's dq_rope block and the one dk_rope are summed in VMEM."""
    b, sp, _ = qn.shape
    dn, dr, dv, pair = _widths(qn, kr, v, heads)
    block, wr, n = plan.block, pair * dr, sp // plan.block
    from jax.experimental.pallas import tpu as pltpu

    def owned(width):
        return pl.BlockSpec((1, block, width), lambda b, g, j: (b, j, g))

    def head(width):
        return pl.BlockSpec((1, sp, width), lambda b, g, j: (b, 0, g))

    pair_head = pl.BlockSpec((1, sp, wr), lambda b, g, j: (b, 0, g // pair))
    # a block leaves when the next step names another: each does once, after
    # the last head has written the sum to it
    dkr_out = pl.BlockSpec((1, block, dr), lambda b, g, j: (
        b, jnp.where(g == heads - 1, j, 0), 0))
    need = _bwd_need(sp, block, max(plan.rows, plan.sub), dn, dr, dv,
                     qn.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, block=block, sub=plan.sub,
                          rows=plan.rows, n=n, heads=heads, pair=pair),
        grid=(b, heads, n),
        in_specs=[head(dn), pair_head, owned(dn),
                  pl.BlockSpec((1, block, dr), lambda b, g, j: (b, j, 0)),
                  owned(dv), head(dv), head(dv),
                  pl.BlockSpec((1, 1, sp),
                               lambda b, g, j: (b * heads + g, 0, 0))],
        out_specs=[head(dn), pair_head, owned(dn), dkr_out, owned(dv)],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype)
                   for t in (qn, qr, kn, kr, v)],
        scratch_shapes=[pltpu.VMEM((sp, LANES), jnp.float32),
                        pltpu.VMEM((block, dn + wr), jnp.float32),
                        pltpu.VMEM((block, dv), jnp.float32),
                        pltpu.VMEM((sp, dn), jnp.float32),
                        pltpu.VMEM((sp, wr), jnp.float32),
                        pltpu.VMEM((sp, wr), jnp.float32),
                        pltpu.VMEM((block, wr), kr.dtype)],
        cost_estimate=_cost(qn, kr, v, heads, 3, 2, tensors=2),
        interpret=interpret_mode(),
        **_params("parallel", "arbitrary", "arbitrary",
                  vmem=_vmem_limit(need)),
    )(qn, qr, kn, kr, v, do, o, lse)


# ------------------------------------------------------------- public API
def supported(q_nope_shape, d_rope, d_v, dtype=None):
    """True where the kernels take a ``[B, S, N, d_nope]`` call with a
    rotary part of ``d_rope`` and values of ``d_v`` (forward AND backward):
    a head's nope part and value whole lane blocks, the rotary part one too
    or half of one with the heads in pairs, and a head's q side resident in
    the backward."""
    _, S, N, dn = q_nope_shape
    if dtype is not None and jnp.dtype(dtype) not in (
            jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    if dn % LANES or d_v % LANES or not (
            d_rope % LANES == 0 or (2 * d_rope == LANES and N % 2 == 0)):
        return False
    itemsize = jnp.dtype(dtype or jnp.bfloat16).itemsize
    plan = tile_plan(S, max(dn, d_v), dtype or jnp.bfloat16, N=N)
    sp = -(-S // plan.block) * plan.block
    return _bwd_need(sp, plan.block, max(plan.rows, plan.sub), dn, d_rope,
                     d_v, itemsize) * 5 // 4 <= _VMEM_LIMIT


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _mla(qn, qr, kn, kr, v, scale, plan, heads):
    return _mla_fwd(qn, qr, kn, kr, v, scale, plan, heads)[0]


def _mla_fwd(qn, qr, kn, kr, v, scale, plan, heads):
    with jax.named_scope(KERNEL):
        s_valid = qn.shape[1]
        qn, qr, kn, kr, v = (_pad_seq(t, plan.block)
                             for t in (qn, qr, kn, kr, v))
        # pre-scale q once instead of scaling every score tile inside the
        # kernels; dq is post-scaled in ``_mla_bwd``
        qn, qr = (t * jnp.asarray(scale, t.dtype) for t in (qn, qr))
        o, lse = _fwd_call(qn, qr, kn, kr, v, plan, heads)
        # what a remat policy keeps (``SAVED_BY_REMAT``) so that a
        # recomputed block does not run the forward kernel again
        o, lse = (checkpoint_name(t, name)
                  for t, name in zip((o, lse), SAVED_BY_REMAT))
        return o[:, :s_valid], (qn, qr, kn, kr, v, o, lse)


def _mla_bwd(scale, plan, heads, res, do):
    with jax.named_scope(KERNEL):
        *operands, o, lse = res
        s_valid = do.shape[1]
        grads = _bwd_call(*operands, _pad_seq(do, plan.block), o, lse, plan,
                          heads)
        # s was computed from the pre-scaled q: d/dq gains the scale
        return tuple(
            (t * jnp.asarray(scale, t.dtype) if i < 2 else t)[:, :s_valid]
            for i, t in enumerate(grads))


_mla.defvjp(_mla_fwd, _mla_bwd)


def mla(q_nope, q_rope, k_nope, k_rope, v, scale=None, block=None):
    """Causal attention ``softmax((q_nope . k_nope + q_rope . k_rope) *
    scale) v``: ``q_nope``, ``k_nope`` ``[B, S, N, d_nope]``, ``q_rope``
    ``[B, S, N, d_rope]``, ``k_rope`` ``[B, S, d_rope]`` (one head, shared),
    ``v`` ``[B, S, N, d_v]`` -> ``[B, S, N, d_v]``.  ``scale`` defaults to
    ``(d_nope + d_rope) ** -0.5``.  Differentiable in all five (custom VJP);
    any S (padded to the tile internally); ``block`` overrides the owner
    block (tests).  The shapes ``supported`` names."""
    from ...telemetry.trace import count_kernel_path

    B, S, N, dn = q_nope.shape
    dr, dv = q_rope.shape[3], v.shape[3]
    if (k_nope.shape != q_nope.shape or q_rope.shape != (B, S, N, dr)
            or k_rope.shape != (B, S, dr) or v.shape != (B, S, N, dv)):
        raise ValueError(
            f"q_nope {q_nope.shape}, q_rope {q_rope.shape}, k_nope "
            f"{k_nope.shape}, k_rope {k_rope.shape}, v {v.shape} are no "
            "latent-attention call")
    if not supported(q_nope.shape, dr, dv, q_nope.dtype):
        raise ValueError(f"{KERNEL} takes no heads of {dn} + {dr} | {dv} "
                         f"x {N} at length {S}")
    if scale is None:
        scale = float(dn + dr) ** -0.5
    plan = tile_plan(S, max(dn, dv), q_nope.dtype, block, N)
    count_kernel_path(KERNEL, f"in_place_{_pair(dr)}")
    with jax.named_scope("attention_layout"):
        flat = [t.reshape(B, S, -1) for t in (q_nope, q_rope, k_nope)] + [
            k_rope, v.reshape(B, S, -1)]
    o = _mla(*flat, float(scale), plan, N)
    with jax.named_scope("attention_layout"):
        return o.reshape(B, S, N, dv)

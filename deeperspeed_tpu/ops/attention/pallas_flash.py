"""In-tree Pallas blocked (flash) attention, forward + backward.

The framework's own MXU attention kernel -- the TPU re-design of the
reference's fused attention/softmax CUDA kernels
(``csrc/transformer/softmax_kernels.cu``, inference ``softmax.cu``): online
softmax over score tiles, so no [S, S] score matrix ever reaches HBM.
FlashAttention-2 style:

* forward saves only O and the per-row logsumexp (LSE);
* backward recomputes P = exp(S - LSE) per tile, seeded by
  ``delta = rowsum(dO * O)``.

Two-level tiling: the block the grid *loads* and the tile the kernel
*computes* are separate sizes, chosen from the shapes by ``tile_plan``.

* A grid program owns one ``block`` of rows -- q rows in the forward, k/v
  rows in the backward -- and has the other side of its head resident in
  VMEM (the whole padded length; in the forward a wide ``span`` of it when
  the whole does not fit).  It walks that side in chunks of ``block`` rows
  with a loop in the kernel body, so a step of the walk costs no grid step,
  DMA or pipeline stage.
* A tile is a group of q rows against *all* the columns of a chunk that
  the group needs, at once: the softmax's per-row bookkeeping (two lane
  reductions, the statistics, the accumulator's rescale) is paid once per
  row and chunk, not per 128-256 columns (square sub-tiles measured 2.6x
  slower than one-level 1024 tiles for that reason; PERF.md, PR 28).
* The causal structure is applied in the one chunk the diagonal crosses:
  there a group is ``sub`` rows against the columns up to its own diagonal.
  What lies above is not computed, and only the last ``sub`` columns of
  such a tile -- a piece of their own -- pay iota / compare / select.  The
  executed share of the S x S square is (n + 1) / 2n with n = S / sub,
  whatever the block (``walk_counts``).
* With one block to the head (S <= 2048) a tile is its rows' whole softmax:
  the forward keeps no running statistics and no scratch, and the backward
  writes dq straight out.
* The backward is one kernel call.  A program keeps fp32 dk/dv accumulators
  for its k/v block across its walk down the q rows, computes ``delta``
  from ``o`` per tile, and adds its dq contribution into a whole-head fp32
  accumulator that stays in VMEM while the grid steps through the head's
  k blocks (that grid axis is sequential): no dq partials and no ``delta``
  go through HBM.  Where the q side of a head does not fit VMEM
  (``Plan.resident_bwd`` false: S of 16k and more at D = 128) the classic
  two-pass backward (a dq pass and a dk/dv pass over a one-level grid)
  takes over.

At small head dim the matmuls are D-thin (they half-fill the MXU at D = 64)
and the fp32 softmax ops on each score tile weigh as much, so the structure
also minimizes VPU work per tile: q is pre-scaled once outside the kernel
(one [B,S,N,D] multiply) and dq post-scaled symmetrically; the padding mask
is compiled out unless the call is non-causal over a padded length (under
the causal mask a valid row never sees a padded column).

Arbitrary sequence lengths are handled by padding S up to the 128-lane tile
and masking padded *columns* out of the softmax (padded rows cost dead FLOPs
but keep >=1 valid column, so no NaNs; their dO is zero so they contribute
nothing to dK/dV).  LSE is stored lane-replicated ([BN, S, 128] fp32) --
the upstream TPU kernel's idiom -- so the backward reads it as a
sublane-aligned column with no relayout.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..pallas_utils import LANES, NEG_INF, interpret_mode

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b

# VMEM (v5e has 128 MiB): what a head's resident side may take before the
# plan splits it, and the most a call may state as its scoped limit
_VMEM_BUDGET = 48 << 20
_VMEM_LIMIT = 100 << 20


class Plan(NamedTuple):
    """Tile sizes of one ``mha`` call (all in rows of the padded length)."""
    block: int          # rows a grid program owns; chunk width of its walk
    sub: int            # granularity of the causal skip and of the mask
    rows: int           # q rows of a backward tile off the edge
    span: int           # k/v rows resident in the forward (multiple of block)
    resident_bwd: bool  # one-kernel backward (q side of a head fits VMEM)


def tile_plan(S, D, dtype, block=None):
    """Tile sizes from what the call can see.  ``block`` overrides the
    owner block (tests); everything else follows from the shapes.

    Measured on the v5e for causal bf16 at D = 64, S = 1024 / 2048 (the
    benchmark's cells) and at D = 96 / 128, S = 2048-8192 (PERF.md section 6,
    PR 28; BENCH_KERNELS.md, last section).
    """
    itemsize = jnp.dtype(dtype).itemsize
    s128 = -(-S // LANES) * LANES
    if block is None:
        # widest owner block that divides the 128-padded length (S=520 pads
        # to 640, not 1024): per-row bookkeeping is paid once per row and
        # chunk, so wide chunks keep it small; 2048 is what a [sub, block]
        # fp32 score tile and its temporaries leave room for
        block = next(b for b in (2048, 1024, 512, 256, LANES)
                     if s128 % b == 0)
    sp = -(-S // block) * block
    # row groups of the edge chunk: each tile costs about a quarter of a
    # microsecond beside its area (the MXU's fill and drain), so not the
    # finest; thin heads gain more from the skip, fat ones from the width
    sub = min(block, 256 if D <= 64 else 512)
    rows = min(block, 512)
    # forward: k and v of a span, double-buffered
    n = sp // block
    cps = next(c for c in range(n, 0, -1)
               if n % c == 0
               and 4 * c * block * D * itemsize <= _VMEM_BUDGET // 2)
    return Plan(block, sub, rows, cps * block,
                _bwd_resident_bytes(sp, D, itemsize) <= _VMEM_BUDGET)


def _bwd_resident_bytes(sp, d, itemsize):
    """VMEM the one-kernel backward holds per head: q, do, o and lse
    double-buffered, the fp32 dq accumulator, and the dq output block."""
    return (2 * 3 * sp * d * itemsize + 2 * sp * LANES * 4
            + sp * d * 4 + 2 * sp * d * itemsize)


def _edge_tiles(block, sub, causal):
    """The static walk of the last chunk of a program's range, as
    ``(row0, ncols)``: the ``sub`` rows from ``row0`` against the chunk's
    first ``ncols`` columns, of which the last ``sub`` are on the edge.
    Causal, the chunk the diagonal crosses: each group of rows against the
    columns up to its own diagonal.  Non-causal, the chunk that may hold
    padded columns: each group against all columns."""
    return [(r, r + sub if causal else block) for r in range(0, block, sub)]


def _pieces(ncols, sub, on_edge):
    """Column pieces ``(col0, ncols, masked)`` of a tile: the masked
    ``sub`` columns of a tile on the edge are a piece of their own, so that
    only they pay iota / compare / select."""
    if not on_edge:
        return [(0, ncols, False)]
    return [(0, ncols - sub, False)][:ncols > sub] + [(ncols - sub, sub, True)]


def walk_counts(plan, S, causal=True):
    """What one head's walk computes, in ``sub x sub`` squares:
    ``(executed, masked, total)``.  The same enumeration the kernels run
    (forward and one-kernel backward alike); a count, never a time."""
    block, sub = plan.block, plan.sub
    sp = -(-S // block) * block
    n = sp // block
    edge = _edge_tiles(block, sub, causal)
    executed = masked = 0
    for i in range(n):
        interior = i if causal else n - 1
        executed += interior * (block // sub) ** 2
        executed += sum(nc for _, nc in edge) // sub
        if causal or sp != S:
            masked += block // sub
    return executed, masked, (sp // sub) ** 2


def _mask(s, qi, ki, bq, bk, s_valid, causal):
    """Validity mask (pad + causal) for a [bq, bk] score tile; used by the
    sparse-attention kernels which mask every live tile."""
    return _tile_mask(s, qi, ki, bq, bk, s_valid, causal, pad=True)


def _tile_mask(s, qi, ki, bq, bk, s_valid, causal, pad):
    if not causal and not pad:
        return s
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if causal and pad:
        valid = jnp.logical_and(cols < s_valid, cols <= rows)
    elif causal:
        valid = cols <= rows
    else:
        valid = cols < s_valid
    return jnp.where(valid, s, NEG_INF)


def _mask_edge(s, causal, col0, s_valid):
    """Mask a ``sub x sub`` score piece on the edge of the walk.  Causal:
    its rows and columns start at the same position of the head, so the
    mask is one constant triangle.  Non-causal: its columns start at
    ``col0`` of the head, and those at or past ``s_valid`` are padding."""
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if causal:
        valid = cols <= jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    else:
        valid = cols < s_valid - col0
    return jnp.where(valid, s, NEG_INF)


def _ds(start, size):
    """A row range whose start may be traced (then a multiple of ``size``
    by construction of the walk, which Mosaic needs to be told)."""
    if not isinstance(start, int):
        start = pl.multiple_of(start, size)
    return pl.ds(start, size)


def _walk(lo, hi, chunk):
    """Run ``chunk(c)`` for c in [lo, hi): the loop inside the kernel body."""
    jax.lax.fori_loop(lo, hi, lambda c, carry: (chunk(c), carry)[1], 0)


# --------------------------------------------------------------------- fwd
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                causal, pad, s_valid, block, sub, n):
    """One q block against one resident span of its head's k/v.  With one
    block to the head (``n == 1``) a tile is its rows' whole softmax: no
    running statistics, no scratch."""
    qi, kj = pl.program_id(1), pl.program_id(2)
    cps = k_ref.shape[1] // block          # chunks per span
    if n > 1:
        m_scr, l_scr, acc_scr = scratch

        @pl.when(kj == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

    def tile(row0, col0, ncols, on_edge=False):
        """Softmax update of the ``sub`` rows from ``row0`` (static, within
        the q block) by ``ncols`` columns from ``col0`` (within the span):
        one update of the row statistics whatever the width."""
        rows = pl.ds(row0, sub)
        q = q_ref[0, rows, :]
        pieces = [(pl.ds(col0 + c0, nc), masked)
                  for c0, nc, masked in _pieces(ncols, sub, on_edge)]
        # q arrives pre-scaled; no per-tile scale multiply
        ss = [jax.lax.dot_general(q, k_ref[0, cols, :], _NT,
                                  preferred_element_type=jnp.float32)
              for cols, _ in pieces]
        ss = [_mask_edge(s, causal, n * block - sub, s_valid) if masked else s
              for s, (_, masked) in zip(ss, pieces)]
        m_new = functools.reduce(
            jnp.maximum, [jnp.max(s, axis=1, keepdims=True) for s in ss])
        if n > 1:
            m_prev = m_scr[rows, :1]
            m_new = jnp.maximum(m_prev, m_new)
        ps = [jnp.exp(s - m_new) for s in ss]
        l_new = sum(jnp.sum(p, axis=1, keepdims=True) for p in ps)
        acc = sum(
            jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0, cols, :],
                                _NN, preferred_element_type=jnp.float32)
            for p, (cols, _) in zip(ps, pieces))
        if n == 1:
            o_ref[0, rows, :] = (acc / l_new).astype(o_ref.dtype)
            lse_ref[0, rows, :] = jnp.broadcast_to(m_new + jnp.log(l_new),
                                                   (sub, LANES))
            return
        alpha = jnp.exp(m_prev - m_new)
        acc_scr[rows, :] = acc_scr[rows, :] * alpha + acc
        m_scr[rows, :] = jnp.broadcast_to(m_new, (sub, LANES))
        l_scr[rows, :] = jnp.broadcast_to(l_scr[rows, :1] * alpha + l_new,
                                          (sub, LANES))

    def interior(c):
        col0 = pl.multiple_of((c - kj * cps) * block, block)
        for row0 in range(0, block, sub):
            tile(row0, col0, block)

    # the chunk that ends this q block's walk: the one the diagonal crosses,
    # or (non-causal) the last of the head, where the padding is
    edge = qi if causal else n - 1

    def edge_chunk():
        col0 = pl.multiple_of((edge - kj * cps) * block, block)
        for row0, ncols in _edge_tiles(block, sub, causal):
            tile(row0, col0, ncols, on_edge=causal or pad)

    if n == 1:
        edge_chunk()
        return
    _walk(kj * cps, jnp.minimum((kj + 1) * cps, edge), interior)
    pl.when(edge // cps == kj)(edge_chunk)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l_scr[:])


# ---------------------------------------------------------------------- bwd
def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, *dq_scr,
                causal, pad, s_valid, block, sub, rows, n):
    """One k/v block against its head's resident q side: dk and dv of the
    block, and the block's share of the head's dq (all of it when the head
    is one block, ``n == 1``: then dq needs no accumulator)."""
    kj = pl.program_id(1)
    if n > 1:
        dq_scr, = dq_scr

        @pl.when(kj == 0)
        def _init_head():
            dq_scr[:] = jnp.zeros_like(dq_scr)

    dk_scr[:] = jnp.zeros_like(dk_scr)
    dv_scr[:] = jnp.zeros_like(dv_scr)

    def tile(row0, nrows, ncols, on_edge=False):
        """``nrows`` q rows from ``row0`` (within the head) against the
        first ``ncols`` columns of the k/v block."""
        rows = _ds(row0, nrows)
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        lse = lse_ref[0, rows, :1]
        # delta = rowsum(dO * O), recomputed per tile: [nrows, D] of work
        # beside the tile's [nrows, ncols]
        delta = jnp.sum(do.astype(jnp.float32)
                        * o_ref[0, rows, :].astype(jnp.float32),
                        axis=1, keepdims=True)
        dq = None
        for c0, nc, masked in _pieces(ncols, sub, on_edge):
            cols = pl.ds(c0, nc)
            k, v = k_ref[0, cols, :], v_ref[0, cols, :]
            s = jax.lax.dot_general(q, k, _NT,
                                    preferred_element_type=jnp.float32)
            if masked:
                s = _mask_edge(s, causal, n * block - sub, s_valid)
            p = jnp.exp(s - lse)
            # dV += P^T dO   (contracting the q rows)
            dv_scr[cols, :] += jax.lax.dot_general(
                p.astype(do.dtype), do, _TN,
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, _NT,
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(q.dtype)
            # dK += dS^T Q
            dk_scr[cols, :] += jax.lax.dot_general(
                ds, q, _TN, preferred_element_type=jnp.float32)
            part = jax.lax.dot_general(ds, k, _NN,
                                       preferred_element_type=jnp.float32)
            dq = part if dq is None else dq + part
        # dQ = dS K: this block's columns' share, into the head's accumulator
        if n == 1:
            dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)
        else:
            dq_scr[rows, :] += dq

    def interior(c, on_edge=False):
        for r in range(0, block, rows):
            tile(c * block + r, rows, block, on_edge)

    if causal:
        # the chunk the diagonal crosses, then every q chunk below it
        for row0, ncols in _edge_tiles(block, sub, True):
            tile(kj * block + row0, sub, ncols, on_edge=True)
        _walk(kj + 1, n, interior)
    elif pad:
        # only the head's last k/v block holds padded columns
        pl.when(kj < n - 1)(lambda: _walk(0, n, interior))
        pl.when(kj == n - 1)(
            lambda: _walk(0, n, functools.partial(interior, on_edge=True)))
    else:
        _walk(0, n, interior)

    dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
    dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    if n > 1:
        @pl.when(kj == n - 1)
        def _finalize_head():
            dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


# ------------------------------------------------- two-pass bwd (long S only)
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, causal, pad, s_valid, bq, bk):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _tile(masked):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32)
        if masked:
            s = _tile_mask(s, qi, ki, bq, bk, s_valid, causal, pad)
        p = jnp.exp(s - lse_ref[0][:, :1])
        dp = jax.lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1])
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    if causal:
        pl.when(ki < qi)(lambda: _tile(False))
        pl.when(ki == qi)(lambda: _tile(True))
    else:
        _tile(True)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, causal, pad, s_valid, bq, bk):
    ki, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _tile(masked):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32)
        if masked:
            s = _tile_mask(s, qi, ki, bq, bk, s_valid, causal, pad)
        p = jnp.exp(s - lse_ref[0][:, :1])
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, _TN, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0][:, :1])).astype(q.dtype)
        dk_scr[:] += jax.lax.dot_general(
            ds, q, _TN, preferred_element_type=jnp.float32)

    if causal:
        pl.when(qi > ki)(lambda: _tile(False))
        pl.when(qi == ki)(lambda: _tile(True))
    else:
        _tile(True)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# ------------------------------------------------------------------ calls
def _pad_seq(x, block):
    s = x.shape[1]
    sp = -(-s // block) * block
    if sp == s:
        return x
    return jnp.pad(x, ((0, 0), (0, sp - s), (0, 0)))


def _vmem_limit(need):
    """The scoped-VMEM limit a call states: none while Mosaic's default
    (16 MiB) holds what the call needs, else a quarter more than that.  Not
    simply the most the chip has: both kernels ran 3-14 % slower under a
    100 MiB limit than under the default, 32 or 48 MiB (PERF.md, PR 28)."""
    if need <= 12 << 20:
        return None
    return min(_VMEM_LIMIT, max(32 << 20, need * 5 // 4))


def _params(*semantics, vmem=None):
    """Mosaic grid annotations: the batch*head axis and the axis of owner
    blocks are independent; an axis that carries a scratch accumulator
    from step to step is "arbitrary" (sequential)."""
    from jax.experimental.pallas import tpu as pltpu

    return dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=vmem))


def _fwd_call(q, k, v, causal, s_valid, plan):
    bn, sp, d = q.shape
    block, span = plan.block, plan.span
    n = sp // block
    from jax.experimental.pallas import tpu as pltpu

    if causal:
        # a span wholly above the diagonal is not walked: name the last
        # needed one again, so it is not loaded either
        def kv_index(b, i, j):
            return (b, jnp.minimum(j, (i * block) // span), 0)
    else:
        def kv_index(b, i, j):
            return (b, j, 0)

    itemsize = q.dtype.itemsize
    need = (4 * span * d * itemsize             # k, v, double-buffered
            + 4 * block * d * itemsize          # q, o
            + 4 * block * LANES * 4             # lse out; m, l
            + block * d * 4                     # acc
            + 3 * plan.sub * block * 4)         # a score tile, its exp, slack
    kernel = functools.partial(
        _fwd_kernel, causal=causal, pad=s_valid != sp, s_valid=s_valid,
        block=block, sub=plan.sub, n=n)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bn, n, sp // span),
        in_specs=[
            pl.BlockSpec((1, block, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, span, d), kv_index),
            pl.BlockSpec((1, span, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn, sp, d), q.dtype),
            jax.ShapeDtypeStruct((bn, sp, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, LANES), jnp.float32),
            pltpu.VMEM((block, LANES), jnp.float32),
            pltpu.VMEM((block, d), jnp.float32),
        ] if n > 1 else [],
        interpret=interpret_mode(),
        **_params("parallel", "parallel", "arbitrary",
                  vmem=_vmem_limit(need)),
    )(q, k, v)
    return o, lse


def _bwd_call(q, k, v, do, o, lse, causal, s_valid, plan):
    """One-kernel backward: grid (head, k/v block), the q side resident."""
    bn, sp, d = q.shape
    block = plan.block
    n = sp // block
    from jax.experimental.pallas import tpu as pltpu

    head = pl.BlockSpec((1, sp, d), lambda b, j: (b, 0, 0))
    head_stat = pl.BlockSpec((1, sp, LANES), lambda b, j: (b, 0, 0))
    owned = pl.BlockSpec((1, block, d), lambda b, j: (b, j, 0))
    out = jax.ShapeDtypeStruct((bn, sp, d), q.dtype)
    itemsize = q.dtype.itemsize
    need = (_bwd_resident_bytes(sp, d, itemsize)
            + 8 * block * d * itemsize          # k, v, dk, dv
            + 2 * block * d * 4                 # dk, dv accumulators
            # s, p, dp, ds of the tallest tile, and their low-precision casts
            + 5 * max(plan.rows, plan.sub) * block * 4)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, causal=causal, pad=s_valid != sp,
                          s_valid=s_valid, block=block, sub=plan.sub,
                          rows=plan.rows, n=n),
        grid=(bn, n),
        in_specs=[head, owned, owned, head, head, head_stat],
        out_specs=[head, owned, owned],
        out_shape=[out, out, out],
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                        pltpu.VMEM((block, d), jnp.float32)]
        + [pltpu.VMEM((sp, d), jnp.float32)] * (n > 1),
        interpret=interpret_mode(),
        **_params("parallel", "arbitrary", vmem=_vmem_limit(need)),
    )(q, k, v, do, o, lse)


def _bwd_call_two_pass(q, k, v, do, o, lse, causal, s_valid):
    """dq pass + dk/dv pass over a one-level grid: for lengths whose q side
    does not fit VMEM (``Plan.resident_bwd`` false)."""
    bn, sp, d = q.shape
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    delta = jnp.broadcast_to(delta, (bn, sp, LANES))
    bq = bk = next(b for b in (1024, 512, 256, LANES) if sp % b == 0)
    nq, nk = sp // bq, sp // bk
    from jax.experimental.pallas import tpu as pltpu

    pad = s_valid != sp
    q_spec_i = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    k_spec_j = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0))
    lse_spec_i = pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, pad=pad,
                          s_valid=s_valid, bq=bq, bk=bk),
        grid=(bn, nq, nk),
        in_specs=[q_spec_i, k_spec_j, k_spec_j, q_spec_i, lse_spec_i,
                  lse_spec_i],
        out_specs=q_spec_i,
        out_shape=jax.ShapeDtypeStruct((bn, sp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret_mode(),
        **_params("parallel", "parallel", "arbitrary"),
    )(q, k, v, do, lse, delta)

    # dk/dv: grid's 2nd dim walks k tiles, 3rd dim scans q tiles
    q_spec_j = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, j, 0))
    k_spec_i = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, 0))
    lse_spec_j = pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, pad=pad,
                          s_valid=s_valid, bq=bq, bk=bk),
        grid=(bn, nk, nq),
        in_specs=[q_spec_j, k_spec_i, k_spec_i, q_spec_j, lse_spec_j,
                  lse_spec_j],
        out_specs=[k_spec_i, k_spec_i],
        out_shape=[jax.ShapeDtypeStruct((bn, sp, d), q.dtype),
                   jax.ShapeDtypeStruct((bn, sp, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret_mode(),
        **_params("parallel", "parallel", "arbitrary"),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------- public API
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _mha(q, k, v, causal, scale, plan):
    return _mha_fwd(q, k, v, causal, scale, plan)[0]


@jax.named_scope("flash_attention")
def _mha_fwd(q, k, v, causal, scale, plan):
    s_valid = q.shape[1]
    qp, kp, vp = (_pad_seq(t, plan.block) for t in (q, k, v))
    # pre-scale q once (one [BN, S, D] multiply) instead of scaling every
    # score tile inside the kernels; dq is post-scaled in _mha_bwd
    qp = qp * jnp.asarray(scale, qp.dtype)
    o, lse = _fwd_call(qp, kp, vp, causal, s_valid, plan)
    return o[:, :s_valid], (qp, kp, vp, o, lse)


@jax.named_scope("flash_attention")
def _mha_bwd(causal, scale, plan, res, do):
    qp, kp, vp, o, lse = res
    s_valid = do.shape[1]
    dop = _pad_seq(do, plan.block)
    if plan.resident_bwd:
        dq, dk, dv = _bwd_call(qp, kp, vp, dop, o, lse, causal, s_valid,
                               plan)
    else:
        dq, dk, dv = _bwd_call_two_pass(qp, kp, vp, dop, o, lse, causal,
                                        s_valid)
    # s was computed from the pre-scaled q, so d/dq gains the scale factor
    dq = dq * jnp.asarray(scale, dq.dtype)
    return dq[:, :s_valid], dk[:, :s_valid], dv[:, :s_valid]


def _mha_fwd_rule(q, k, v, causal, scale, plan):
    o, res = _mha_fwd(q, k, v, causal, scale, plan)
    return o, res


_mha.defvjp(_mha_fwd_rule, _mha_bwd)


def mha(q, k, v, causal=True, scale=None, block=None):
    """Blocked multi-head attention: [B, S, N, D] q/k/v -> [B, S, N, D].

    Any S (padded to the 128 tile internally); D should be a multiple of 8.
    Differentiable (custom VJP, FlashAttention-2 backward).  Tile sizes come
    from ``tile_plan``; ``block`` overrides the owner block only.
    """
    B, S, N, D = q.shape
    if scale is None:
        scale = float(D) ** -0.5
    plan = tile_plan(S, D, q.dtype, block)

    # the copies on both sides of the kernel, forward and backward, are
    # ``attention_layout`` in a device trace
    @jax.named_scope("attention_layout")
    def fold(t):
        return jnp.swapaxes(t, 1, 2).reshape(B * N, S, D)

    o = _mha(fold(q), fold(k), fold(v), causal, float(scale), plan)
    with jax.named_scope("attention_layout"):
        return jnp.swapaxes(o.reshape(B, N, S, D), 1, 2)


# keep the historical name used by ring attention / docs
mha_forward = mha

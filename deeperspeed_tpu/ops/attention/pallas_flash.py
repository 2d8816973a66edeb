"""In-tree Pallas blocked (flash) attention, forward + backward.

The framework's own MXU attention kernel -- the TPU re-design of the
reference's fused attention/softmax CUDA kernels
(``csrc/transformer/softmax_kernels.cu``, inference ``softmax.cu``): online
softmax over score tiles, so no [S, S] score matrix ever reaches HBM.
FlashAttention-2 style:

* forward saves only O and the per-row logsumexp (LSE);
* backward recomputes P = exp(S - LSE) per tile, seeded by
  ``delta = rowsum(dO * O)``.

Layout: the kernels read q, k, v (and dO) and write O, dQ, dK, dV as the
projections hold them, ``[B, S, N*D]``, with heads addressed as *column
groups*: the grid runs over ``(batch, group, ...)`` and a block is ``(1,
rows, width)`` at ``(b, i, g)`` -- whole 128-lane tiles, strided by the row.
``mha``'s ``[B, S, N, D] -> [B, S, N*D]`` is a reshape of contiguous
dimensions, so the kernels need no transpose on either side of a call (the
TPU compiler may still hold a model's 4-D q, k, v in another physical layout
and pay a lane-dense copy to bring them here: PERF.md section 6, PR 30).  A
group is one head where D is a multiple of 128, and two heads side by side
at D = 64: a program then runs both heads' walks on full-width operands, the
row-side operand zeroed outside the head's own lanes (see ``_own_lanes``).
Other shapes (``_heads_to_a_block`` says which, from N and D alone) are
folded to ``[B*N, S, D]`` by a copy each way, and run the same kernels with
one group a batch row.  The LSE is the kernels' own: float32
``[B' * G, heads, S]``, one value a row with the rows on lanes, a row of
statistics per head and the heads of a lane block together.

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

Grouped-query heads (k and v with fewer heads than q, a KV head read by
``rep`` query heads): where a head is a lane block of its own the kernels
address a query head's KV head where it lies.  q, o, do and dq stay
``[B, S, N*D]`` column groups; k, v, dk and dv are ``[B, S, N_kv*D]`` and
column group ``g`` reads KV column group ``g // rep`` (``_kv_group``), so
nothing copies k and v out to the query heads.  The one-kernel backward
walks a KV head's query heads one after the other (grid: batch, KV head,
query head of the group, k/v block) and adds each one's dk and dv of a block
into fp32 sums of the whole KV head that stay in VMEM; the bf16 block leaves
once, with the group's last query head, so no dk or dv of the query heads'
width reaches HBM and nothing is summed after the kernel.  The two-pass
backward's dk/dv pass walks the group between the k tile and the q tiles.
``rep`` is read from the operands' widths; at ``rep`` 1 every call is the
program it was.  The layouts that cannot address a KV head so (two heads of
64 to a lane block, the folded one) take a copy of k and v (``copy_kv_heads``),
and ``mha`` counts which it was (``grouped_<rep>`` | ``copied_<rep>``).

At small head dim the matmuls are D-thin (they half-fill the MXU at D = 64)
and the fp32 softmax ops on each score tile weigh as much, so the structure
also minimizes VPU work per tile: q is pre-scaled once outside the kernel
(one elementwise multiply) and dq post-scaled symmetrically; the padding mask
is compiled out unless the call is non-causal over a padded length (under
the causal mask a valid row never sees a padded column).

Arbitrary sequence lengths are handled by padding S up to the 128-lane tile
and masking padded *columns* out of the softmax (padded rows cost dead FLOPs
but keep >=1 valid column, so no NaNs; their dO is zero so they contribute
nothing to dK/dV).

The LSE is lane-replicated only in VMEM (the running m and l, and the copy
the backward's tiles read as a sublane-aligned column).  To HBM it goes as
one float a row: the forward transposes each row group's statistics onto
lanes once, the backward transposes a head's back once, before its walk
(``_rows_onto_lanes``, ``_rows_off_lanes``: 128 x 128 transposes on the XLU,
which the softmax's row reductions also use: the forward call alone moved by
-0.8 to +5 %, BENCH_KERNELS.md).  Replicated it was four times the bytes of
``o`` at D = 64, which is what made it too dear to keep: ``_mha_fwd`` names
``o`` and the LSE for ``jax.checkpoint`` policies (``SAVED_BY_REMAT``), and a
model whose remat wrap saves those two recomputes a block without running
the forward kernel a second time (``models/gpt_neox.py``).
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ..pallas_utils import LANES, NEG_INF, interpret_mode

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b

# VMEM (v5e has 128 MiB): what a head's resident side may take before the
# plan splits it, and the most a call may state as its scoped limit
_VMEM_BUDGET = 48 << 20
_VMEM_LIMIT = 100 << 20

# ``jax.ad_checkpoint`` names of the two residuals the forward kernel itself
# writes (its output and the lse: one bf16 [B, S, N*D] and a float a row).
# A model whose remat wrap saves exactly these
# (``jax.checkpoint_policies.save_only_these_names(*SAVED_BY_REMAT)``)
# recomputes a block without the kernel; q, k, v are still recomputed.
SAVED_BY_REMAT = ("flash_attention_out", "flash_attention_lse")


class Plan(NamedTuple):
    """Tile sizes of one ``mha`` call (all in rows of the padded length)."""
    block: int          # rows a grid program owns; chunk width of its walk
    sub: int            # granularity of the causal skip and of the mask
    rows: int           # q rows of a backward tile off the edge
    span: int           # k/v rows resident in the forward (multiple of block)
    resident_bwd: bool  # one-kernel backward (q side of a head fits VMEM)
    group: int          # heads to a lane block of [B, S, N*D]; 0 = folded
    width: int          # lanes of the block a program loads: group * D, or D
    window: int = 0     # causal window in rows (row i sees i-window < j <= i); 0 = none


def _heads_to_a_block(N, D):
    """How the kernel addresses heads, from ``(N, D)`` alone: as column
    groups of the projections' own ``[B, S, N*D]`` layout where those are
    whole 128-lane blocks -- one head of a multiple of 128, or two of 64
    side by side -- and else (0) on operands folded to ``[B*N, S, D]``:
    D = 80 or 96, an odd head count at D = 64 (the local heads under tensor
    or Ulysses sharding may be), or heads thinner than 64 (four heads'
    tiles at once overflowed VMEM in the TPU compiler at D = 32, and no
    model here has such heads)."""
    if D % LANES == 0:
        return 1
    if 2 * D == LANES and N % 2 == 0:
        return 2
    return 0


def tile_plan(S, D, dtype, block=None, N=1, window=None, kv_heads=None):
    """Tile sizes from what the call can see.  ``block`` overrides the
    owner block (tests); everything else follows from the shapes: the tiles
    from S and D, the layout the kernels take (``group``, ``width``) from
    the head count N and D alone (``_heads_to_a_block``).  ``window`` (a
    causal call's, in rows) changes no size: the same tiles, fewer of them
    (``_band_tiles``); one that reaches the whole length is no window.
    ``kv_heads`` (k's and v's head count where it is not N) changes one
    answer: the one-kernel backward of grouped-query heads also holds a KV
    head's dk and dv sums (``_bwd_resident_bytes``).

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
    group = _heads_to_a_block(N, D)
    heads = max(group, 1)
    width = heads * D
    # forward: k and v of a span, double-buffered
    n = sp // block
    cps = next(c for c in range(n, 0, -1)
               if n % c == 0
               and 4 * c * block * width * itemsize <= _VMEM_BUDGET // 2)
    grouped = group == 1 and N != (kv_heads or N)
    return Plan(block, sub, rows, cps * block,
                _bwd_resident_bytes(sp, width, itemsize, heads, grouped)
                <= _VMEM_BUDGET, group, width,
                int(window) if window and window < S else 0)


def _bwd_resident_bytes(sp, w, itemsize, heads=1, grouped=False):
    """VMEM the one-kernel backward holds per program: q, do, o and the
    heads' lse (a sublane tile of rows on lanes) double-buffered, each
    head's lse again as the tiles read it (lane-replicated), the fp32 dq
    accumulator, the dq output block; under grouped-query heads also the
    KV head's fp32 dk and dv, summed over its query heads."""
    return (2 * 3 * sp * w * itemsize + 2 * 8 * sp * 4
            + heads * sp * LANES * 4 + sp * w * 4 + 2 * sp * w * itemsize
            + grouped * 2 * sp * w * 4)


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


def _band(block, window):
    """Which column chunks a row block's band touches, by their distance
    ``d`` = row block - column chunk (0 is the chunk the diagonal crosses):
    ``(full, partial)``.  Chunks at distances 1..``full`` are seen whole by
    every row of the block; those at the distances in ``partial`` (at most
    two) are crossed by the band's left edge; farther ones are not
    visited."""
    full = max(0, window // block - 1)
    return full, list(range(full + 1, _reach(block, window) + 1))


def _reach(block, window):
    """The distance of the farthest column chunk a row block's band touches
    (a window of one block reaches the chunk before the diagonal's)."""
    return (window + block - 2) // block


def _band_tiles(block, sub, window, d):
    """The static walk of the chunk at distance ``d`` under a causal window,
    as ``(row0, [(col0, ncols, mask)])``: the ``sub`` rows from ``row0`` of
    the row block against column pieces of the chunk.  Row ``a`` of a group
    sees the chunk's column ``c`` iff ``off + a < c`` (the band's left edge,
    ``off = d * block + row0 - window``) and, in the chunk the diagonal
    crosses, ``c <= row0 + a``.  Columns no row of the group sees are not
    computed; ``mask`` is None where every row sees every column of a piece
    and else ``(left, right)``, either None or the bound of a piece's own
    columns against its rows (``_mask_band``): only the pieces an edge
    crosses pay iota / compare / select, as on the diagonal alone without a
    window.  All bounds are whole 128-lane tiles."""
    tiles = []
    for row0 in range(0, block, sub):
        off = d * block + row0 - window
        hi = block if d else row0 + sub
        lo = max(0, off + 1) // LANES * LANES
        if lo >= hi:
            continue
        # columns [lo, left_end) are hidden from some row, [diag, hi) too
        left_end = min(hi, max(lo, -(-(off + sub) // LANES) * LANES))
        diag = hi if d else row0
        cuts = sorted({lo, min(left_end, hi), max(lo, diag), hi})
        pieces = []
        for c0, c1 in zip(cuts[:-1], cuts[1:]):
            left = off - c0 if c0 < left_end else None
            right = row0 - c0 if c1 > diag else None
            pieces.append((c0, c1 - c0, None if left is None and right is None
                           else (left, right)))
        tiles.append((row0, pieces))
    return tiles


def band_pairs(S, window):
    """(row, column) pairs a causal window lets see each other in a length
    ``S``: the triangle's where ``window`` reaches the whole length."""
    w = min(int(window), S) if window else S
    return w * S - w * (w - 1) // 2


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


def _tile_mask(s, qi, ki, bq, bk, s_valid, causal, pad, window=0):
    if not causal and not pad:
        return s
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if window:
        valid = jnp.logical_and(cols <= rows, cols > rows - window)
    elif causal and pad:
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


def _mask_band(s, left, right):
    """Mask a score piece the band's edges cross: row ``a`` sees the
    piece's column ``c`` iff ``c > left + a`` and ``c <= right + a`` (either
    bound may be None: not in this piece).  Static bounds: one constant
    mask."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = None if right is None else cols <= rows + right
    if left is not None:
        seen = cols > rows + left
        valid = seen if valid is None else jnp.logical_and(valid, seen)
    return jnp.where(valid, s, NEG_INF)


def _masked(s, mask, causal, col0, s_valid):
    """A score piece under its mask: none, the edge's (True: the diagonal's
    constant triangle, or the padding), or a band's bounds."""
    if not mask:
        return s
    if mask is True:
        return _mask_edge(s, causal, col0, s_valid)
    return _mask_band(s, *mask)


def _ds(start, size):
    """A row range whose start may be traced (then a multiple of ``size``
    by construction of the walk, which Mosaic needs to be told)."""
    if not isinstance(start, int):
        start = pl.multiple_of(start, size)
    return pl.ds(start, size)


def _walk(lo, hi, chunk):
    """Run ``chunk(c)`` for c in [lo, hi): the loop inside the kernel body."""
    jax.lax.fori_loop(lo, hi, lambda c, carry: (chunk(c), carry)[1], 0)


# ------------------------------------------- heads side by side in a block
# A program whose block holds ``heads`` > 1 heads (D < 128) runs each head's
# walk on full-width operands: the row-side operand (q, do) is zeroed
# outside the head's own lanes, so a contraction over the block's 128 lanes
# is the head's own over its D, and a product with it as the right-hand side
# lands in the head's lanes of a 128-wide accumulator with zeros elsewhere.
# At D = 64 a matmul half-fills the 128-deep MXU either way, so the passes
# are the same, and every fp32 accumulator and output is lane-dense.  (Static
# lane slices of the refs, each head on [rows, 64] operands, were measured
# 3-9 % slower, kernel alone: BENCH_KERNELS.md, last section.)
def _own_lanes(x, h, heads):
    """``x`` with the lanes of the other heads of its block zeroed."""
    if heads == 1:
        return x
    d = x.shape[-1] // heads
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.where((lane >= h * d) & (lane < (h + 1) * d), x,
                     jnp.zeros_like(x))


def _each_its_lanes(parts, width):
    """One ``[rows, width]`` value that holds, in each head's lanes, that
    head's part (``[rows, width]``, or a ``[rows, 1]`` column)."""
    if len(parts) == 1:
        return jnp.broadcast_to(parts[0], (parts[0].shape[0], width))
    d = width // len(parts)
    lane = jax.lax.broadcasted_iota(
        jnp.int32, (parts[0].shape[0], width), 1)
    out = parts[-1]
    for h in range(len(parts) - 2, -1, -1):
        out = jnp.where(lane < (h + 1) * d, parts[h], out)
    return out


# ----------------------------------------------- the statistics' two layouts
# In VMEM a row statistic (m, l, the lse) is a sublane-aligned column,
# replicated over the 128 lanes, so a score tile reads it with no relayout.
# In HBM the lse is one float a row, rows on lanes: ``[B' * G, heads, S]``
# (replicated it was 128 times the bytes, four times ``o``'s, written by
# every forward call, read by every backward call and kept by a remat policy
# that saves it).  The two functions below pass between the layouts, once
# per row group and head, never per score tile.
def _rows_onto_lanes(x):
    """``[rows, 1]``, or ``[rows, 128]`` lane-replicated -> ``[1, rows]``."""
    return jnp.broadcast_to(x, (x.shape[0], LANES)).T[:1]


def _rows_off_lanes(x):
    """``[1, rows]`` -> ``[rows, 128]`` lane-replicated."""
    return jnp.broadcast_to(x, (LANES, x.shape[1])).T


# --------------------------------------------------------------------- fwd
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                causal, pad, s_valid, block, sub, n, heads, window=0):
    """One q block against one resident span of its heads' k/v.  With one
    block to the head (``n == 1``) a tile is its rows' whole softmax: no
    running statistics, no scratch."""
    qi, kj = pl.program_id(2), pl.program_id(3)
    cps = k_ref.shape[1] // block          # chunks per span
    width = q_ref.shape[2]
    if n > 1:
        m_scr, l_scr, acc_scr = scratch

        @pl.when(kj == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

    def tile(row0, col0, pieces):
        """Softmax update of the ``sub`` rows from ``row0`` (static, within
        the q block) by the column ``pieces`` (``(col0, ncols, mask)``, as
        ``_pieces`` and ``_band_tiles`` give them) of the chunk at ``col0``
        (within the span): one update of the row statistics whatever the
        width."""
        rows = pl.ds(row0, sub)
        pieces = [(pl.ds(col0 + c0, nc), mask) for c0, nc, mask in pieces]
        accs, alphas = [], []
        for h in range(heads):
            # q arrives pre-scaled; no per-tile scale multiply
            q = _own_lanes(q_ref[0, rows, :], h, heads)
            ss = [jax.lax.dot_general(q, k_ref[0, cols, :], _NT,
                                      preferred_element_type=jnp.float32)
                  for cols, _ in pieces]
            ss = [_masked(s, mask, causal, n * block - sub, s_valid)
                  for s, (_, mask) in zip(ss, pieces)]
            m_new = functools.reduce(
                jnp.maximum, [jnp.max(s, axis=1, keepdims=True) for s in ss])
            if n > 1:
                m_prev = m_scr[h, rows, :1]
                m_new = jnp.maximum(m_prev, m_new)
            ps = [jnp.exp(s - m_new) for s in ss]
            l_new = sum(jnp.sum(p, axis=1, keepdims=True) for p in ps)
            acc = sum(
                jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0, cols, :],
                                    _NN, preferred_element_type=jnp.float32)
                for p, (cols, _) in zip(ps, pieces))
            if n == 1:
                accs.append(acc / l_new)
                lse_ref[0, pl.ds(h, 1), rows] = _rows_onto_lanes(
                    m_new + jnp.log(l_new))
                continue
            alpha = jnp.exp(m_prev - m_new)
            accs.append(acc)
            alphas.append(alpha)
            m_scr[h, rows, :] = jnp.broadcast_to(m_new, (sub, LANES))
            l_scr[h, rows, :] = jnp.broadcast_to(
                l_scr[h, rows, :1] * alpha + l_new, (sub, LANES))
        acc = _each_its_lanes(accs, width)
        if n == 1:
            o_ref[0, rows, :] = acc.astype(o_ref.dtype)
        else:
            acc_scr[rows, :] = (acc_scr[rows, :]
                                * _each_its_lanes(alphas, width) + acc)

    def interior(c):
        col0 = pl.multiple_of((c - kj * cps) * block, block)
        for row0 in range(0, block, sub):
            tile(row0, col0, _pieces(block, sub, False))

    # the chunk that ends this q block's walk: the one the diagonal crosses,
    # or (non-causal) the last of the head, where the padding is
    edge = qi if causal else n - 1

    def band_chunk(d):
        """The chunk ``d`` chunks left of the diagonal's, under a window."""
        col0 = pl.multiple_of((edge - d - kj * cps) * block, block)
        for row0, pieces in _band_tiles(block, sub, window, d):
            tile(row0, col0, pieces)

    def edge_chunk():
        if window:
            return band_chunk(0)
        col0 = pl.multiple_of((edge - kj * cps) * block, block)
        for row0, ncols in _edge_tiles(block, sub, causal):
            tile(row0, col0, _pieces(ncols, sub, causal or pad))

    if n == 1:
        edge_chunk()
        return
    if window:
        # only the chunks the block's band touches: those every row sees
        # whole, then the one or two the band's left edge crosses
        full, partial = _band(block, window)
        if full:
            _walk(jnp.maximum(kj * cps, edge - full),
                  jnp.minimum((kj + 1) * cps, edge), interior)
        for d in partial:
            pl.when(jnp.logical_and(edge >= d, (edge - d) // cps == kj))(
                functools.partial(band_chunk, d))
    else:
        _walk(kj * cps, jnp.minimum((kj + 1) * cps, edge), interior)
    pl.when(edge // cps == kj)(edge_chunk)

    @pl.when(kj == pl.num_programs(3) - 1)
    def _finalize():
        l = _each_its_lanes([l_scr[h, :, :1] for h in range(heads)], width)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        for h in range(heads):
            for r in range(0, block, sub):
                rows = pl.ds(r, sub)
                lse_ref[0, pl.ds(h, 1), rows] = _rows_onto_lanes(
                    m_scr[h, rows, :] + jnp.log(l_scr[h, rows, :]))


# ---------------------------------------------------------------------- bwd
def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, lse_scr, dk_scr, dv_scr, *more,
                causal, pad, s_valid, block, sub, rows, n, heads, window=0,
                rep=1):
    """One k/v block against its heads' resident q side: dk and dv of the
    block, and the block's share of the heads' dq (all of it when a head
    is one block, ``n == 1``: then dq needs no accumulator).  Grouped-query
    heads (``rep`` > 1 query heads a KV head; the grid walks them one after
    the other, each over all the k/v blocks): dk and dv of the block are
    this query head's share, added to the KV head's fp32 sums (``more``'s
    last two, whole heads), which leave with the group's last query head."""
    kj = pl.program_id(2 + (rep > 1))
    width = q_ref.shape[2]
    if n > 1:
        dq_scr = more[0]

    @pl.when(kj == 0)
    def _init_head():
        # the heads' lse as the tiles read it, once for all their k/v blocks
        for h in range(heads):
            for r in range(0, n * block, rows):
                lse_scr[h, pl.ds(r, rows), :] = _rows_off_lanes(
                    lse_ref[0, pl.ds(h, 1), pl.ds(r, rows)])
        if n > 1:
            dq_scr[:] = jnp.zeros_like(dq_scr)

    dk_scr[:] = jnp.zeros_like(dk_scr)
    dv_scr[:] = jnp.zeros_like(dv_scr)

    def tile(row0, nrows, pieces):
        """``nrows`` q rows from ``row0`` (within the head) against the
        column ``pieces`` (``(col0, ncols, mask)``) of the k/v block."""
        rows = _ds(row0, nrows)
        dqs = []
        for h in range(heads):
            q = _own_lanes(q_ref[0, rows, :], h, heads)
            do = _own_lanes(do_ref[0, rows, :], h, heads)
            lse = lse_scr[h, rows, :1]
            # delta = rowsum(dO * O), recomputed per tile: [nrows, D] of
            # work beside the tile's [nrows, ncols]
            delta = jnp.sum(do.astype(jnp.float32)
                            * o_ref[0, rows, :].astype(jnp.float32),
                            axis=1, keepdims=True)
            dq = None
            for c0, nc, mask in pieces:
                cols = pl.ds(c0, nc)
                k, v = k_ref[0, cols, :], v_ref[0, cols, :]
                s = jax.lax.dot_general(q, k, _NT,
                                        preferred_element_type=jnp.float32)
                s = _masked(s, mask, causal, n * block - sub, s_valid)
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
            dqs.append(dq)
        # dQ = dS K: this block's columns' share, into the accumulator
        dq = _each_its_lanes(dqs, width)
        if n == 1:
            dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)
        else:
            dq_scr[rows, :] += dq

    def interior(c, on_edge=False):
        for r in range(0, block, rows):
            tile(c * block + r, rows, _pieces(block, sub, on_edge))

    def band_chunk(d):
        """The q chunk ``d`` chunks below the diagonal's, under a window."""
        for row0, pieces in _band_tiles(block, sub, window, d):
            tile((kj + d) * block + row0, sub, pieces)

    if window:
        # the chunk the diagonal crosses, the q chunks whose every row sees
        # the whole block, then the one or two the band's left edge crosses;
        # no row farther down sees a column of this block
        full, partial = _band(block, window)
        band_chunk(0)
        if full:
            _walk(kj + 1, jnp.minimum(kj + 1 + full, n), interior)
        for d in partial:
            pl.when(kj + d < n)(functools.partial(band_chunk, d))
    elif causal:
        # the chunk the diagonal crosses, then every q chunk below it
        for row0, ncols in _edge_tiles(block, sub, True):
            tile(kj * block + row0, sub, _pieces(ncols, sub, True))
        _walk(kj + 1, n, interior)
    elif pad:
        # only the head's last k/v block holds padded columns
        pl.when(kj < n - 1)(lambda: _walk(0, n, interior))
        pl.when(kj == n - 1)(
            lambda: _walk(0, n, functools.partial(interior, on_edge=True)))
    else:
        _walk(0, n, interior)

    if rep == 1:
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)
    else:
        r, block_rows = pl.program_id(2), _ds(kj * block, block)
        for out, own, total in zip((dk_ref, dv_ref), (dk_scr, dv_scr),
                                   more[-2:]):
            @pl.when(r == 0)
            def _first_of_group():
                total[block_rows, :] = own[:]

            @pl.when(jnp.logical_and(r > 0, r < rep - 1))
            def _add_to_group():
                total[block_rows, :] += own[:]

            @pl.when(r == rep - 1)
            def _leave_group():
                out[0] = (total[block_rows, :] + own[:]).astype(out.dtype)

    if n > 1:
        @pl.when(kj == n - 1)
        def _finalize_head():
            dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


# ------------------------------------------------- two-pass bwd (long S only)
def _band_of_tiles(d, block, window):
    """Of a one-level grid's tile at distance ``d`` (traced) under a window
    -> (every row sees it whole, an edge of the band crosses it)."""
    full, _ = _band(block, window)
    return (jnp.logical_and(d >= 1, d <= full),
            jnp.logical_or(d == 0, jnp.logical_and(
                d > full, d <= _reach(block, window))))


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, causal, pad, s_valid, bq, bk, heads, window=0):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _tile(masked):
        k, v = k_ref[0], v_ref[0]
        dqs = []
        for h in range(heads):
            q = _own_lanes(q_ref[0], h, heads)
            do = _own_lanes(do_ref[0], h, heads)
            s = jax.lax.dot_general(q, k, _NT,
                                    preferred_element_type=jnp.float32)
            if masked:
                s = _tile_mask(s, qi, ki, bq, bk, s_valid, causal, pad,
                               window)
            p = jnp.exp(s - lse_ref[h][:, :1])
            dp = jax.lax.dot_general(do, v, _NT,
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[h][:, :1])
            dqs.append(jax.lax.dot_general(
                ds.astype(k.dtype), k, _NN,
                preferred_element_type=jnp.float32))
        dq_scr[:] += _each_its_lanes(dqs, dq_scr.shape[1])

    if window:
        whole, crossed = _band_of_tiles(qi - ki, bq, window)
        pl.when(whole)(lambda: _tile(False))
        pl.when(crossed)(lambda: _tile(True))
    elif causal:
        pl.when(ki < qi)(lambda: _tile(False))
        pl.when(ki == qi)(lambda: _tile(True))
    else:
        _tile(True)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, causal, pad, s_valid, bq, bk, heads, window=0, rep=1):
    """Grouped-query heads (``rep`` > 1): the grid walks a KV head's query
    heads between the k tile and the q tiles, and the accumulators run over
    both walks."""
    walk = 3 + (rep > 1)
    ki, qi = pl.program_id(2), pl.program_id(walk)
    nq = pl.num_programs(walk)
    first = qi == 0
    if rep > 1:
        first = jnp.logical_and(first, pl.program_id(3) == 0)

    @pl.when(first)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _tile(masked):
        k, v = k_ref[0], v_ref[0]
        for h in range(heads):
            q = _own_lanes(q_ref[0], h, heads)
            do = _own_lanes(do_ref[0], h, heads)
            s = jax.lax.dot_general(q, k, _NT,
                                    preferred_element_type=jnp.float32)
            if masked:
                s = _tile_mask(s, qi, ki, bq, bk, s_valid, causal, pad,
                               window)
            p = jnp.exp(s - lse_ref[h][:, :1])
            dv_scr[:] += jax.lax.dot_general(
                p.astype(do.dtype), do, _TN,
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, _NT,
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[h][:, :1])).astype(q.dtype)
            dk_scr[:] += jax.lax.dot_general(
                ds, q, _TN, preferred_element_type=jnp.float32)

    if window:
        whole, crossed = _band_of_tiles(qi - ki, bq, window)
        pl.when(whole)(lambda: _tile(False))
        pl.when(crossed)(lambda: _tile(True))
    elif causal:
        pl.when(qi > ki)(lambda: _tile(False))
        pl.when(qi == ki)(lambda: _tile(True))
    else:
        _tile(True)

    last = qi == nq - 1
    if rep > 1:
        last = jnp.logical_and(last, pl.program_id(3) == rep - 1)

    @pl.when(last)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# ------------------------------------------------------------------ calls
# Operands are ``[B', S, G * width]``: ``G`` column groups of ``width``
# lanes, each ``heads`` heads side by side.  In place B' = B and
# G = N / heads; folded B' = B * N and G = 1.  The lse is the kernels' own,
# ``[B' * G, heads, S]``; the two-pass backward's kernels read it and their
# delta lane-replicated, ``[B' * G * heads, S, 128]``: a row per head.
# Grouped-query heads (in place at one head a group): k, v, dk and dv are
# ``[B, S, G / rep * width]`` and query group ``g`` reads KV group
# ``g // rep``, ``rep`` from the operands' widths.
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
    """Mosaic grid annotations: the batch axis, the axis of column groups
    and the axis of owner blocks are independent; an axis that carries a
    scratch accumulator from step to step is "arbitrary" (sequential)."""
    from jax.experimental.pallas import tpu as pltpu

    return dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=vmem))


def _kv_group(g, rep):
    """The KV column group of query column group ``g`` (at ``rep`` 1 the
    index itself: the program text of a call without grouped heads)."""
    return g if rep == 1 else g // rep


def _group_walk(rep, at):
    """``spec(shape, index)`` for a backward grid ``(b, g, *walk)`` whose
    block indices are ``index(b, g, gk, *walk)``, ``g`` the query column
    group and ``gk`` its KV group.  Grouped-query heads (``rep`` > 1) put
    the KV groups on the grid's second axis and a group's query heads on
    axis ``at``: ``(b, gk, ..., r, ...)``, ``g = gk * rep + r``."""
    if rep == 1:
        return lambda shape, index: pl.BlockSpec(
            shape, lambda b, g, *walk: index(b, g, g, *walk))

    def spec(shape, index):
        def by_group(b, gk, *walk):
            walk = list(walk)
            return index(b, gk * rep + walk.pop(at - 2), gk, *walk)

        return pl.BlockSpec(shape, by_group)

    return spec


def _cost(q, k, plan, causal, matmuls, tensors):
    """What a call costs, for XLA's scheduler: it decides what to run beside
    a kernel (the copies that bring the next operands) by this, and without
    it by the call's bytes, which the compact lse cut by half.  ``matmuls``
    of ``2 * S * S * D`` FLOPs a head, ``tensors`` through HBM, half of
    them of q's size and half of k's, and the lse."""
    b, sp, hw = q.shape
    heads = hw // (plan.width // max(plan.group, 1))
    square = b * heads * sp * sp // (2 if causal else 1)
    if plan.window:
        square = b * heads * band_pairs(sp, plan.window)
    return pl.CostEstimate(
        flops=2 * matmuls * square * (hw // heads), transcendentals=square,
        bytes_accessed=(tensors // 2 * (q.size + k.size) * q.dtype.itemsize
                        + 4 * b * heads * sp))


def _fwd_call(q, k, v, causal, s_valid, plan):
    b, sp, hw = q.shape
    block, span, w = plan.block, plan.span, plan.width
    heads, groups, n = max(plan.group, 1), hw // w, sp // block
    rep = hw // k.shape[2]
    from jax.experimental.pallas import tpu as pltpu

    if plan.window:
        # nor is a span wholly left of the band
        reach = _reach(block, plan.window)

        def kv_index(b, g, i, j):
            return (b, jnp.clip(j, jnp.maximum(i - reach, 0) * block // span,
                                (i * block) // span), _kv_group(g, rep))
    elif causal:
        # a span wholly above the diagonal is not walked: name the last
        # needed one again, so it is not loaded either
        def kv_index(b, g, i, j):
            return (b, jnp.minimum(j, (i * block) // span),
                    _kv_group(g, rep))
    else:
        def kv_index(b, g, i, j):
            return (b, j, _kv_group(g, rep))

    itemsize = q.dtype.itemsize
    need = (4 * span * w * itemsize             # k, v, double-buffered
            + 4 * block * w * itemsize          # q, o
            + 2 * heads * block * LANES * 4     # m, l
            + 2 * 8 * block * 4                 # lse out, a sublane tile
            + block * w * 4                     # acc
            + 3 * plan.sub * block * 4)         # a score tile, its exp, slack
    kernel = functools.partial(
        _fwd_kernel, causal=causal, pad=s_valid != sp, s_valid=s_valid,
        block=block, sub=plan.sub, n=n, heads=heads, window=plan.window)
    owned = pl.BlockSpec((1, block, w), lambda b, g, i, j: (b, i, g))
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, groups, n, sp // span),
        in_specs=[owned,
                  pl.BlockSpec((1, span, w), kv_index),
                  pl.BlockSpec((1, span, w), kv_index)],
        out_specs=[
            owned,
            pl.BlockSpec((1, heads, block),
                         lambda b, g, i, j: (b * groups + g, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sp, hw), q.dtype),
            jax.ShapeDtypeStruct((b * groups, heads, sp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, block, LANES), jnp.float32),
            pltpu.VMEM((heads, block, LANES), jnp.float32),
            pltpu.VMEM((block, w), jnp.float32),
        ] if n > 1 else [],
        cost_estimate=_cost(q, k, plan, causal, matmuls=2, tensors=4),
        interpret=interpret_mode(),
        **_params("parallel", "parallel", "parallel", "arbitrary",
                  vmem=_vmem_limit(need)),
    )(q, k, v)
    return o, lse


def _bwd_call(q, k, v, do, o, lse, causal, s_valid, plan):
    """One-kernel backward: grid (batch, column group, k/v block), the q
    side resident.  Grouped-query heads: grid (batch, KV column group, query
    head of the group, k/v block), so that a KV head's query heads follow
    one another and its dk and dv are summed in VMEM (``_bwd_kernel``)."""
    b, sp, hw = q.shape
    block, w = plan.block, plan.width
    heads, groups, n = max(plan.group, 1), hw // w, sp // block
    rep = hw // k.shape[2]
    from jax.experimental.pallas import tpu as pltpu

    spec = _group_walk(rep, at=2)
    owned = spec((1, block, w), lambda b, g, gk, j: (b, j, gk))
    if rep == 1:
        grid, walks, kv_out = (b, groups, n), ("arbitrary",), owned
    else:
        grid, walks = (b, groups // rep, rep, n), ("arbitrary", "arbitrary")
        # a block leaves when the next step names another: each does once,
        # after the group's last query head has written the sums to it
        kv_out = pl.BlockSpec((1, block, w), lambda b, gk, r, j: (
            b, jnp.where(r == rep - 1, j, 0), gk))

    head = spec((1, sp, w), lambda b, g, gk, j: (b, 0, g))
    head_stat = spec((1, heads, sp),
                     lambda b, g, gk, j: (b * groups + g, 0, 0))
    out = jax.ShapeDtypeStruct((b, sp, hw), q.dtype)
    kv_shape = jax.ShapeDtypeStruct(k.shape, k.dtype)
    itemsize = q.dtype.itemsize
    need = (_bwd_resident_bytes(sp, w, itemsize, heads, rep > 1)
            + 8 * block * w * itemsize          # k, v, dk, dv
            + 2 * block * w * 4                 # dk, dv accumulators
            # s, p, dp, ds of the tallest tile, and their low-precision casts
            + 5 * max(plan.rows, plan.sub) * block * 4)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, causal=causal, pad=s_valid != sp,
                          s_valid=s_valid, block=block, sub=plan.sub,
                          rows=plan.rows, n=n, heads=heads,
                          window=plan.window, rep=rep),
        grid=grid,
        in_specs=[head, owned, owned, head, head, head_stat],
        out_specs=[head, kv_out, kv_out],
        out_shape=[out, kv_shape, kv_shape],
        scratch_shapes=[pltpu.VMEM((heads, sp, LANES), jnp.float32),
                        pltpu.VMEM((block, w), jnp.float32),
                        pltpu.VMEM((block, w), jnp.float32)]
        + [pltpu.VMEM((sp, w), jnp.float32)] * ((n > 1) + 2 * (rep > 1)),
        cost_estimate=_cost(q, k, plan, causal, matmuls=5, tensors=8),
        interpret=interpret_mode(),
        **_params("parallel", "parallel", *walks, vmem=_vmem_limit(need)),
    )(q, k, v, do, o, lse)


def _bwd_call_two_pass(q, k, v, do, o, lse, causal, s_valid, plan):
    """dq pass + dk/dv pass over a one-level grid: for lengths whose q side
    does not fit VMEM (``Plan.resident_bwd`` false).  Grouped-query heads:
    the dk/dv pass walks a KV head's query heads too (``_dkv_kernel``)."""
    b, sp, hw = q.shape
    w = plan.width
    heads, groups = max(plan.group, 1), hw // w
    rep = hw // k.shape[2]
    # its kernels read both statistics lane-replicated, a row per head
    lse = jnp.broadcast_to(lse.reshape(b * groups * heads, sp, 1),
                           (b * groups * heads, sp, LANES))
    delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32))
                    .reshape(b, sp, groups * heads, w // heads), axis=-1)
    delta = jnp.broadcast_to(
        jnp.swapaxes(delta, 1, 2).reshape(b * groups * heads, sp, 1),
        (b * groups * heads, sp, LANES))
    bq = bk = next(t for t in (1024, 512, 256, LANES) if sp % t == 0)
    nq, nk = sp // bq, sp // bk
    from jax.experimental.pallas import tpu as pltpu

    static = dict(causal=causal, pad=s_valid != sp, s_valid=s_valid,
                  bq=bq, bk=bk, heads=heads, window=plan.window)
    out = jax.ShapeDtypeStruct((b, sp, hw), q.dtype)
    # under a window a tile outside the band is neither computed nor loaded:
    # the walked side names the nearest tile of the band again
    reach = _reach(bq, plan.window) if plan.window else None

    def near(i, j, ahead):
        """The walked side's tile ``j`` for the owner tile ``i``."""
        if reach is None:
            return j
        return (jnp.clip(j, i, jnp.minimum(i + reach, nq - 1)) if ahead
                else jnp.clip(j, jnp.maximum(i - reach, 0), i))

    q_spec_i = pl.BlockSpec((1, bq, w), lambda b, g, i, j: (b, i, g))
    k_spec_j = pl.BlockSpec((1, bk, w), lambda b, g, i, j: (
        b, near(i, j, False), _kv_group(g, rep)))
    stat_i = pl.BlockSpec((heads, bq, LANES),
                          lambda b, g, i, j: (b * groups + g, i, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        grid=(b, groups, nq, nk),
        in_specs=[q_spec_i, k_spec_j, k_spec_j, q_spec_i, stat_i, stat_i],
        out_specs=q_spec_i,
        out_shape=out,
        scratch_shapes=[pltpu.VMEM((bq, w), jnp.float32)],
        interpret=interpret_mode(),
        **_params("parallel", "parallel", "parallel", "arbitrary"),
    )(q, k, v, do, lse, delta)

    # dk/dv: grid's 3rd dim walks k tiles, the last scans q tiles; between
    # them, under grouped-query heads, the KV head's query heads
    spec = _group_walk(rep, at=3)
    grid, walks = (b, groups, nk, nq), ("arbitrary",)
    if rep > 1:
        grid = (b, groups // rep, nk, rep, nq)
        walks = ("arbitrary", "arbitrary")
    q_spec_j = spec((1, bq, w),
                    lambda b, g, gk, i, j: (b, near(i, j, True), g))
    k_spec_i = spec((1, bk, w), lambda b, g, gk, i, j: (b, i, gk))
    stat_j = spec((heads, bq, LANES),
                  lambda b, g, gk, i, j: (b * groups + g,
                                          near(i, j, True), 0))
    kv_shape = jax.ShapeDtypeStruct(k.shape, k.dtype)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, rep=rep, **static),
        grid=grid,
        in_specs=[q_spec_j, k_spec_i, k_spec_i, q_spec_j, stat_j, stat_j],
        out_specs=[k_spec_i, k_spec_i],
        out_shape=[kv_shape, kv_shape],
        scratch_shapes=[pltpu.VMEM((bk, w), jnp.float32),
                        pltpu.VMEM((bk, w), jnp.float32)],
        interpret=interpret_mode(),
        **_params("parallel", "parallel", "parallel", *walks),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def kernel_name(plan):
    """The scope a call's kernels run under, which names their events in a
    device trace and their counts in ``telemetry.kernel_paths()`` /
    ``kernel_passes()``: windowed calls apart from full ones."""
    return "flash_attention_window" if plan.window else "flash_attention"


def copy_kv_heads(k, v, N, kernel):
    """Grouped-query k and v ``[B, S, N_kv, D]`` copied out to the ``N``
    query heads, for a call that cannot address a KV head by the query
    head's group; counted under ``kernel + "_kv_heads"`` as ``copied_<query
    heads a KV head>``.  The copies are ``attention_layout`` in a device
    trace."""
    from ...telemetry.trace import count_kernel_path

    count_kernel_path(kernel + "_kv_heads", f"copied_{N // k.shape[2]}")
    with jax.named_scope("attention_layout"):
        return tuple(jnp.repeat(t, N // t.shape[2], axis=2) for t in (k, v))


# ------------------------------------------------------------- public API
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _mha(q, k, v, causal, scale, plan):
    return _mha_fwd(q, k, v, causal, scale, plan)[0]


def _mha_fwd(q, k, v, causal, scale, plan):
    with jax.named_scope(kernel_name(plan)):
        return _mha_fwd_scoped(q, k, v, causal, scale, plan)


def _mha_fwd_scoped(q, k, v, causal, scale, plan):
    s_valid = q.shape[1]
    qp, kp, vp = (_pad_seq(t, plan.block) for t in (q, k, v))
    # pre-scale q once (one elementwise multiply) instead of scaling every
    # score tile inside the kernels; dq is post-scaled in _mha_bwd
    qp = qp * jnp.asarray(scale, qp.dtype)
    o, lse = _fwd_call(qp, kp, vp, causal, s_valid, plan)
    # what a remat policy keeps (``SAVED_BY_REMAT``) so that a recomputed
    # block does not run the forward kernel again for its own residuals
    o, lse = (checkpoint_name(t, name) for t, name in
              zip((o, lse), SAVED_BY_REMAT))
    return o[:, :s_valid], (qp, kp, vp, o, lse)


def _mha_bwd(causal, scale, plan, res, do):
    with jax.named_scope(kernel_name(plan)):
        return _mha_bwd_scoped(causal, scale, plan, res, do)


def _mha_bwd_scoped(causal, scale, plan, res, do):
    qp, kp, vp, o, lse = res
    s_valid = do.shape[1]
    dop = _pad_seq(do, plan.block)
    call = _bwd_call if plan.resident_bwd else _bwd_call_two_pass
    dq, dk, dv = call(qp, kp, vp, dop, o, lse, causal, s_valid, plan)
    # s was computed from the pre-scaled q, so d/dq gains the scale factor
    dq = dq * jnp.asarray(scale, dq.dtype)
    return dq[:, :s_valid], dk[:, :s_valid], dv[:, :s_valid]


def _mha_fwd_rule(q, k, v, causal, scale, plan):
    o, res = _mha_fwd(q, k, v, causal, scale, plan)
    return o, res


_mha.defvjp(_mha_fwd_rule, _mha_bwd)


def mha(q, k, v, causal=True, scale=None, block=None, window=None):
    """Blocked multi-head attention: [B, S, N, D] q/k/v -> [B, S, N, D].

    Any S (padded to the 128 tile internally); D should be a multiple of 8.
    Grouped-query heads: k and v may be ``[B, S, N_kv, D]`` with N a
    multiple of N_kv, query head ``h`` on KV head ``h // (N // N_kv)``.
    Where a head is a lane block of its own (``Plan.group`` 1) the kernels
    read a query head's KV block where it lies and sum dk and dv over the
    group in VMEM, so k, v, dk and dv exist at N_kv heads only; the other
    layouts take a copy of k and v at N heads (``copy_kv_heads``).  Which
    it was is counted under ``kernel_name(plan) + "_kv_heads"``:
    ``grouped_<N // N_kv>`` or ``copied_<N // N_kv>``.
    Differentiable (custom VJP, FlashAttention-2 backward).  Tile sizes come
    from ``tile_plan``; ``block`` overrides the owner block only.

    ``window`` (static, causal calls only): row i sees the columns j with
    ``i - window < j <= i``.  The forward and both backward forms visit only
    the column chunks a row block's band touches and mask the band's left
    edge in the one or two chunks it crosses, as the diagonal is masked in
    its own (``_band_tiles``); a window that reaches the whole length is the
    full call.  Windowed calls run under the scope ``flash_attention_window``
    and count under that name.

    Where ``tile_plan`` finds that heads are whole lane blocks of the
    projections' output (``Plan.group``), the kernels take ``[B, S, N*D]``
    -- a reshape of contiguous dimensions -- and address heads as column
    groups: nothing is transposed in HBM on either side.  Else the operands
    are folded to ``[B*N, S, D]`` (a copy each, ``attention_layout`` in a
    device trace).
    """
    from ...telemetry.trace import count_kernel_path

    B, S, N, D = q.shape
    if scale is None:
        scale = float(D) ** -0.5
    if window is not None and (not causal or window < 1):
        raise ValueError("a window belongs to a causal call and is >= 1 row")
    if k.shape != v.shape or N % k.shape[2] or (
            k.shape[:2] + k.shape[3:] != (B, S, D)):
        raise ValueError(f"k and v {k.shape}, {v.shape} are no KV heads of "
                         f"q {q.shape}")
    plan = tile_plan(S, D, q.dtype, block, N, window, kv_heads=k.shape[2])
    count_kernel_path(kernel_name(plan),
                      f"in_place_{plan.group}" if plan.group else "folded")
    if k.shape[2] != N:
        if plan.group == 1:
            count_kernel_path(kernel_name(plan) + "_kv_heads",
                              f"grouped_{N // k.shape[2]}")
        else:
            k, v = copy_kv_heads(k, v, N, kernel_name(plan))
    if plan.group:
        # a reshape of contiguous dimensions; whatever the compiler still
        # pays for it (it may hold a 4-D operand in another physical layout)
        # is ``attention_layout`` in a device trace, as the folds below are
        with jax.named_scope("attention_layout"):
            q, k, v = (t.reshape(*t.shape[:2], -1) for t in (q, k, v))
        o = _mha(q, k, v, causal, float(scale), plan)
        with jax.named_scope("attention_layout"):
            return o.reshape(B, S, N, D)

    @jax.named_scope("attention_layout")
    def fold(t):
        return jnp.swapaxes(t, 1, 2).reshape(B * N, S, D)

    o = _mha(fold(q), fold(k), fold(v), causal, float(scale), plan)
    with jax.named_scope("attention_layout"):
        return jnp.swapaxes(o.reshape(B, N, S, D), 1, 2)


# keep the historical name used by ring attention / docs
mha_forward = mha

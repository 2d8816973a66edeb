"""Pallas kernels of learned sparse attention (DSA: a lightning indexer
scores every earlier key, a row keeps its ``topk`` best, one softmax over
those): the selection of a row block, the attention over the chosen forward
+ backward, the head-averaged probabilities the indexer's loss imitates, and
that loss with its gradients.  The equations are ``ops/attention/dsa.py``'s.

On this chip the selection is a MASK inside a causal walk and not a gather:
a row's 2048 keys are its own, and gathering them costs 4.2 MB a row against
the 0.5 GB a layer the dense walk streams at 16k (PERF.md section 4).  So
the kernels are ``pallas_flash``'s shape, grouped-query addressing as PR 50
left it (query column group ``h`` reads KV column group ``h // rep`` of
``[B, S, N_kv*D]`` where it lies), with one more operand:

* the selection, packed: int32 words ``[B, Sp, W]``, bit ``j`` of word
  ``(t, c)`` says that row ``t`` chose column ``j * W + c``.  ``W`` is the
  walk's column chunk (``sel_layout``: whole 128-lane tiles, at most 32
  chunks to the padded length ``Sp``; 512 at 16k), so the tile of rows ``i``
  against chunk ``j`` reads its rows' words WHOLE, lane for lane, and
  shifts them by ``j``: no relayout, 34 MB a layer at 16k where int8 is 268.
  A program holds its row block's words while it walks the chunks.
* a count of chosen pairs per (group of ``SelLayout.rows`` rows, chunk)
  tile, prefetched to SMEM: a tile in which no row chose anything is not
  computed.  Everything above the diagonal goes that way; inside the
  triangle few tiles do while the indexer is untrained
  (``dsa_tiles_skipped``).

Two-level tiling, in ``pallas_flash``'s words, for the three
``dsa_attention`` kernels: the block the grid *loads* and the tile a kernel
*computes* are separate sizes.

* A grid program owns one ``block`` of rows of a few query heads of a
  group side by side (forward and dq; ``attend_plan``, from the shapes
  alone: 512 rows of four heads at 16k and eight heads a KV head, 2,048 of
  one head without groups) with the block's words ``[block, W]`` in VMEM,
  and has k and v of its KV head resident: the whole padded length where
  that fits (to 16k at D = 128: the grid runs ``(batch, KV head, row block,
  query heads of the group)``, so k and v change with the KV head alone and
  the words with the row block), a wide ``span`` of it, on one more grid
  axis, where it does not.  The dk/dv pass owns a ``cols``-wide block of
  columns of one KV head and steps over the row blocks and the group's
  query heads, its float32 sums in VMEM.
* The tile stays the selection's: ``SelLayout.rows`` rows against one chunk
  of ``W`` columns, computed where its count is above zero.  A program
  walks the tiles of its block with two loops in the kernel body
  (``_walk_tiles``), so a step of the walk costs no grid step, DMA or
  pipeline stage, and the statistics change layout once a block and head.
  A tile's chosen pairs are unpacked once for the program's heads, whose
  chains are independent: one's products run under another's lane work.
* The edge: a wide block meets the diagonal a group of ``SelLayout.rows``
  rows at a time (each group's walk ends at the chunk its own diagonal
  crosses), so the pairs a pass computes are ``dsa_pairs_visited`` whatever
  the block.

``dsa_head_probs`` is tiled the same way with the HEADS walked in the body
(``head_probs_plan``): a grid program owns an output tile, the call's row
block of q at every head's columns stays resident for the whole call and a
chunk of k at the KV heads serves them all; a few heads of a group run side
by side, the rest in a loop, their probabilities are summed in float32 and
masked, scaled and stored once a tile, and their log-sum-exp leave the lanes
once a call.  ``dsa_select`` and ``dsa_loss_grads`` keep one-level grids of
their own sizes.

Kernels, each under the scope that names it in a device trace and in
``telemetry.kernel_passes()``:

* ``dsa_select``: a block of rows of the indexer's scores, resident in
  VMEM -> its words.  The ``k``-th largest of a row by bisection on the
  float32 bit pattern (32 counting passes over VMEM), equal scores by
  position (a second bisection, over the column), exact.
* ``dsa_attention``: forward (online softmax over the chosen of a tile,
  ``o`` and one float a row out), and a two-pass backward: dq over the
  forward's grid and walk, dk/dv over (KV head, column block, row blocks,
  the group's query heads), the KV head's sums in VMEM.
* ``dsa_head_probs``: ``mean_h softmax_{S_t}(q_h . k)`` of a chunk of rows
  from the saved log-sum-exp, the heads summed in VMEM: float32
  ``[B, rows, Sp]``, zero outside the chosen (a tile above the diagonal or
  of count 0 is written as zeros).
* ``dsa_loss_grads``: the indexer's loss of a chunk of rows against those
  probabilities, with its gradients.  A block of rows (its own size,
  ``loss_rows``: the loss shares nothing with the attention's block) walks
  the chunks up to its diagonal twice: the scores into VMEM and the rows'
  log-sum-exp over their chosen, then the KL's gradient and the three
  products it feeds (``dw``, ``dq^I`` held for the block, ``dk^I`` summed
  over the row blocks where the output block lies).  Nothing the size of
  ``[H_I, rows, cols]`` leaves VMEM.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..pallas_utils import LANES, NEG_INF, interpret_mode
from .pallas_flash import (_NN, _NT, _TN, _VMEM_BUDGET, _ds, _params,
                           _rows_off_lanes, _rows_onto_lanes, _vmem_limit,
                           _walk)

#: the scopes the kernels run under
ATTENTION, SELECT, HEAD_PROBS = "dsa_attention", "dsa_select", "dsa_head_probs"
LOSS_GRADS = "dsa_loss_grads"
_INT_MIN = -(1 << 31)


class SelLayout(NamedTuple):
    """How a length's selection is packed and walked (rows)."""
    chunk: int      # W: columns of a chunk = words of a row
    chunks: int     # n <= 32: bits used of a word
    padded: int     # Sp = n * W
    rows: int       # bq: rows of a grid program's block (divides Sp)


def sel_layout(S):
    chunk = max(LANES, -(-S // (32 * LANES)) * LANES)
    chunks = -(-S // chunk)
    padded = chunks * chunk
    rows = next(r for r in (512, 256, LANES) if padded % r == 0)
    return SelLayout(chunk, chunks, padded, rows)


def compiles_for_tpu(head_dim):
    """Whether the TPU compiler takes the attention kernels: a head a whole
    lane block (any length is padded to whole tiles)."""
    return head_dim % LANES == 0


# ---------------------------------------------------------------- selection
def sortable(scores):
    """float32 -> int32 with the same order (no NaN): the bit pattern, the
    negatives' magnitude bits flipped."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def masked_key(scores, row0, col0, seq):
    """A tile of scores (rows from ``row0``, columns from ``col0``) -> its
    sortable keys, ``INT_MIN`` where the row does not see the column."""
    t = row0 + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    return jnp.where((col <= t) & (t < seq), sortable(scores), _INT_MIN)


def select_rows(key, chunks, shape, row0, seq, topk):
    """``key(c)``: the masked keys ``[rows, W]`` of a block of rows (from
    ``row0``) against chunk ``c`` -> the block's words ``[rows, W]``: row
    ``t`` keeps the ``min(t + 1, topk)`` largest of its columns ``s <= t``,
    of equal scores the lower column first; a row at or past ``seq`` keeps
    nothing.  Exact: the threshold is the k-th largest bit pattern, found
    bit by bit by counting, and the equal scores are cut at the column
    where their count is reached.  The same code on tiles in VMEM
    (``_select_kernel``) and on slices of an array (``dsa.py``)."""
    rows, w = shape
    t = row0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    want = jnp.where(t < seq, jnp.minimum(t + 1, topk), 0).astype(jnp.float32)

    def count(test):
        """How many columns of each row pass ``test(key tile, chunk)``."""
        hits = sum(jnp.where(test(key(c), c), 1.0, 0.0)
                   for c in range(chunks))
        return jnp.sum(hits, axis=1, keepdims=True)

    def threshold(b, thr):
        # bits 31 .. 0: adding 2**31 to INT_MIN wraps to 0
        cand = thr + jnp.left_shift(jnp.int32(1), 31 - b)
        return jnp.where(count(lambda k, c: k >= cand) >= want, cand, thr)

    thr = jax.lax.fori_loop(0, 32, threshold,
                            jnp.full((rows, 1), _INT_MIN, jnp.int32))
    # of the equal ones the lowest columns, as many as are still short
    short = want - count(lambda k, c: k > thr)
    bits = max(1, (chunks * w - 1).bit_length())

    def cut(b, at):
        cand = at + jnp.left_shift(jnp.int32(1), bits - 1 - b)
        before = count(lambda k, c: (k == thr) & (c * w + lane < cand))
        return jnp.where(before < short, cand, at)

    at = jax.lax.fori_loop(0, bits, cut, jnp.zeros((rows, 1), jnp.int32))
    words = jnp.zeros(shape, jnp.int32)
    for c in range(chunks):
        k = key(c)
        chosen = ((k > thr) | ((k == thr) & (c * w + lane <= at))) & (want > 0)
        words = words | jnp.left_shift(chosen.astype(jnp.int32), c)
    return words


def unpack_rows(words, chunks):
    """The words ``[..., rows, W]`` -> bool ``[..., rows, chunks * W]``."""
    bits = (words[..., None, :] >> jnp.arange(chunks, dtype=jnp.int32)[
        :, None]) & 1
    return bits.reshape(*words.shape[:-2], words.shape[-2], -1).astype(bool)


def _select_kernel(q_ref, k_ref, w_ref, words_ref, key_scr, *, rows, chunk,
                   chunks, seq, topk, heads):
    """A block of rows: its scores ``I[t, s] = sum_j w[t, j] relu(q_j[t] .
    k[s])`` a chunk of columns at a time into VMEM as masked keys (the
    chunks past the block's diagonal are seen by no row), then the
    selection of the whole rows."""
    i = pl.program_id(1)
    row0 = i * rows
    last = (row0 + rows - 1) // chunk       # the chunk the last row ends in

    def scores(c, carry):
        k = k_ref[0, pl.ds(pl.multiple_of(c * chunk, chunk), chunk), :]
        acc = jnp.zeros((rows, chunk), jnp.float32)
        for j in range(heads):
            dots = jax.lax.dot_general(q_ref[0, j], k, _NT,
                                       preferred_element_type=jnp.float32)
            acc = acc + w_ref[0, :, j:j + 1] * jnp.maximum(dots, 0.0)
        key_scr[c] = masked_key(acc, row0, c * chunk, seq)
        return carry

    key_scr[...] = jnp.full_like(key_scr, _INT_MIN)
    jax.lax.fori_loop(0, last + 1, scores, 0)
    words_ref[0] = select_rows(lambda c: key_scr[c], chunks, (rows, chunk),
                               row0, seq, topk)


def select_call(qi, ki, w, seq, topk, layout):
    """The indexer's queries ``[B, H_I, Sp, D_I]``, its keys ``[B, Sp,
    D_I]`` and weights ``[B, Sp, H_I]`` float32 -> words ``[B, Sp, W]``."""
    from jax.experimental.pallas import tpu as pltpu

    b, heads, sp, d = qi.shape
    # a block of rows whose keys for the whole length stay in VMEM
    rows = min(LANES, layout.rows)
    need = (6 * rows * sp * 4 + 2 * sp * d * ki.dtype.itemsize
            + 4 * rows * heads * d * qi.dtype.itemsize)
    with jax.named_scope(SELECT):
        return pl.pallas_call(
            functools.partial(_select_kernel, rows=rows, chunk=layout.chunk,
                              chunks=layout.chunks, seq=seq, topk=topk,
                              heads=heads),
            grid=(b, sp // rows),
            in_specs=[
                pl.BlockSpec((1, heads, rows, d), lambda b, i: (b, 0, i, 0)),
                pl.BlockSpec((1, sp, d), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, rows, heads), lambda b, i: (b, i, 0))],
            out_specs=pl.BlockSpec((1, rows, layout.chunk),
                                   lambda b, i: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((b, sp, layout.chunk), jnp.int32),
            scratch_shapes=[pltpu.VMEM((layout.chunks, rows, layout.chunk),
                                       jnp.int32)],
            interpret=interpret_mode(),
            **_params("parallel", "parallel", vmem=_vmem_limit(need)),
        )(qi, ki, w)


# -------------------------------------------------- attention over the chosen
def _picked(words_ref, rows, j):
    """Which pairs of the tile (``rows`` of the block, chunk ``j``) are
    chosen."""
    return (words_ref[0, rows, :] & jnp.left_shift(jnp.int32(1), j)) != 0


def _chosen(words_ref, j):
    """``_picked`` of the block's every row."""
    return _picked(words_ref, slice(None), j)


def _tile_count(counts_ref, b, i, j, nq, n):
    return counts_ref[(b * nq + i) * n + j]


class AttendPlan(NamedTuple):
    """Sizes of one ``dsa_attention`` call's three kernels, in rows of the
    padded length: what a grid program LOADS.  What it computes at a time is
    the selection's tile, ``SelLayout.rows`` x ``SelLayout.chunk``."""
    block: int      # q rows a program owns: whole groups of SelLayout.rows
    span: int       # k/v rows resident in the forward and dq: whole chunks
    cols: int       # k/v rows a dk/dv program owns: whole chunks
    heads: int      # query heads of a group side by side in a program
    walk: str       # "resident": the span is the KV head's whole length


# the most rows a program's heads own together and the widest block of
# columns; the most VMEM the heads' float32 scores of a tile and a block's
# words ``[block, W]``, double-buffered, may take
_ATTEND_ROWS = 2048
_ATTEND_COLS = 4096
_TILES_BUDGET = 4 << 20
_WORDS_BUDGET = 16 << 20


def _side_by_side(layout, rep):
    """Query heads of a group side by side in a program: four, or two,
    where the group divides so and their float32 score tiles take
    ``_TILES_BUDGET`` together."""
    return next(h for h in (4, 2, 1) if rep % h == 0 and (
        h == 1 or 4 * h * layout.rows * layout.chunk <= _TILES_BUDGET))


def attend_plan(layout, d, rep, dtype, block=None, span=None, cols=None,
                heads=None):
    """The attention kernels' own sizes, from what a call can see: the
    selection's layout (the padded length, the tile), the head's width, the
    query heads a KV head and the operands' type.  ``block``, ``span``,
    ``cols`` and ``heads`` override (tests, ``tools/profile_dsa.py``'s
    sweep).  Measured at the Keye cell's shape (BENCH_KERNELS.md, PR 55).

    * ``heads``: four query heads of a group to a program, or two, where
      the group divides so and their score tiles take ``_TILES_BUDGET``
      together: a tile's chosen pairs are unpacked once for them all, and
      the heads' chains (a product, the softmax's lane work, a product) are
      independent, so one's products run under another's lane work;
    * ``block``: the most whole row groups that divide the length, up to
      ``_ATTEND_ROWS`` rows of the program's heads together and
      ``_WORDS_BUDGET`` of words (one block to the head where the length
      is shorter; the tile's own rows where nothing wider divides it);
    * ``span``: the KV head's whole length where k and v of it,
      double-buffered, take half of ``pallas_flash``'s budget (to 16k at
      D = 128 in bfloat16: the resident walk then holds ONE buffer of each,
      they change with the KV head alone), else the most whole chunks that
      do;
    * ``cols``: whole chunks up to ``_ATTEND_COLS`` columns."""
    sub, w, n, sp = layout.rows, layout.chunk, layout.chunks, layout.padded
    item = jnp.dtype(dtype).itemsize
    if heads is None:
        heads = _side_by_side(layout, rep)
    if block is None:
        nq = sp // sub
        block = sub * next(
            m for m in range(nq, 0, -1) if nq % m == 0 and (m == 1 or (
                heads * m * sub <= _ATTEND_ROWS
                and 8 * m * sub * w <= _WORDS_BUDGET)))
    if span is None:
        span = w * next(c for c in range(n, 0, -1) if n % c == 0
                        and (4 * c * w * d * item <= _VMEM_BUDGET // 2
                             or c == 1))
    if cols is None:
        cols = w * next(c for c in range(n, 0, -1) if n % c == 0
                        and (c * w <= _ATTEND_COLS or c == 1))
    return AttendPlan(block, span, cols, heads,
                      "resident" if span == sp else "span")


def _walk_tiles(counts_ref, b, i, c0, cn, tile, *, block, sub, w, nq, n):
    """The walk inside a program's body: ``tile(rows, cols, j)`` for every
    tile -- a group of ``sub`` rows of row block ``i`` against chunk ``j``
    of the ``cn`` chunks from ``c0`` that the program holds -- which some
    row chose in (its count, from SMEM).  A group ends at the chunk its own
    diagonal crosses: nothing above the diagonal is computed at the tile's
    granularity, however wide the block.  ``rows`` and ``cols`` are the
    tile's ranges within the program's blocks."""
    def group(s):
        ig = i * (block // sub) + s
        rows = _ds(s * sub, sub)

        def chunk(j):
            @pl.when(_tile_count(counts_ref, b, ig, j, nq, n) > 0)
            def _live():
                tile(rows, _ds((j - c0) * w, w), j)

        _walk(c0, jnp.minimum(c0 + cn, (ig * sub + sub - 1) // w + 1), chunk)

    _walk(0, block // sub, group)


def _fwd_kernel(counts_ref, q_ref, k_ref, v_ref, words_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, geometry, cps):
    """Grid (b, KV head, row block i, query heads of the group, span): the
    block's online softmax over the chosen of its tiles in the span, the
    program's heads one after the other a tile."""
    b, i, kj = pl.program_id(0), pl.program_id(2), pl.program_id(4)
    heads, d = m_scr.shape[0], k_ref.shape[2]

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(rows, cols, j):
        picked = _picked(words_ref, rows, j)
        k, v = k_ref[0, cols, :], v_ref[0, cols, :]
        for h in range(heads):
            lanes = pl.ds(h * d, d)
            # q arrives pre-scaled.  A row that has met no chosen column
            # yet holds exp(0) of its masked ones; its first chosen column's
            # alpha is exp(NEG_INF - m) = 0 and wipes them (every real row
            # chose one)
            s = jnp.where(picked, jax.lax.dot_general(
                q_ref[0, rows, lanes], k, _NT,
                preferred_element_type=jnp.float32), NEG_INF)
            m_prev = m_scr[h, rows, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            m_scr[h, rows, :] = jnp.broadcast_to(m_new, (s.shape[0], LANES))
            # a row's sum stays spread over the lanes until the block leaves
            l_scr[h, rows, :] = l_scr[h, rows, :] * alpha + _onto_lanes(p)
            acc_scr[rows, lanes] = (
                acc_scr[rows, lanes] * alpha + jax.lax.dot_general(
                    p.astype(v.dtype), v, _NN,
                    preferred_element_type=jnp.float32))

    _walk_tiles(counts_ref, b, i, kj * cps, cps, tile, **geometry)

    @pl.when(kj == pl.num_programs(4) - 1)
    def _finalize():
        for h in range(heads):
            lanes = pl.ds(h * d, d)
            l = jnp.sum(l_scr[h], axis=1, keepdims=True)
            l = jnp.where(l == 0.0, 1.0, l)         # rows past the length
            o_ref[0, :, lanes] = (acc_scr[:, lanes] / l).astype(o_ref.dtype)
            lse_ref[h] = _rows_onto_lanes(m_scr[h, :, :1] + jnp.log(l))


def _tile_probs(q, k, picked, lse):
    """A tile's probabilities from the rows' saved log-sum-exp: zero where
    the row did not choose the column."""
    s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
    return jnp.where(picked, jnp.exp(s - lse), 0.0)


def _statistics_off_lanes(lse_ref, delta_ref, lse_scr, delta_scr):
    """The heads' log-sum-exp and ``delta`` as the tiles read them, once a
    block and head."""
    for h in range(lse_scr.shape[0]):
        lse_scr[h] = _rows_off_lanes(lse_ref[h])
        delta_scr[h] = _rows_off_lanes(delta_ref[h])


def _dq_kernel(counts_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               words_ref, dq_ref, dq_scr, lse_scr, delta_scr, *, geometry,
               cps):
    """The forward's grid and walk: dq of the block, summed over the
    spans."""
    b, i, kj = pl.program_id(0), pl.program_id(2), pl.program_id(4)
    d = k_ref.shape[2]

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        _statistics_off_lanes(lse_ref, delta_ref, lse_scr, delta_scr)

    def tile(rows, cols, j):
        picked = _picked(words_ref, rows, j)
        k, v = k_ref[0, cols, :], v_ref[0, cols, :]
        for h in range(lse_scr.shape[0]):
            lanes = pl.ds(h * d, d)
            p = _tile_probs(q_ref[0, rows, lanes], k, picked,
                            lse_scr[h, rows, :1])
            dp = jax.lax.dot_general(do_ref[0, rows, lanes], v, _NT,
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_scr[h, rows, :1])).astype(k.dtype)
            dq_scr[rows, lanes] += jax.lax.dot_general(
                ds, k, _NN, preferred_element_type=jnp.float32)

    _walk_tiles(counts_ref, b, i, kj * cps, cps, tile, **geometry)

    @pl.when(kj == pl.num_programs(4) - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(counts_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                words_ref, dk_ref, dv_ref, dk_scr, dv_scr, lse_scr,
                delta_scr, *, geometry, cw):
    """Grid (b, KV head, column block jb, row block i, query heads of the
    group): a step walks the tiles of row block ``i`` against the ``cw``
    chunks the program owns, and the KV head's dk and dv of those chunks
    are summed in VMEM over the row blocks and the group."""
    b, jb, i, r = (pl.program_id(a) for a in (0, 2, 3, 4))
    block, w, d = geometry["block"], geometry["w"], k_ref.shape[2]

    @pl.when((i == 0) & (r == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def tile(rows, cols, j):
        picked = _picked(words_ref, rows, j)
        k, v = k_ref[0, cols, :], v_ref[0, cols, :]
        dk = dv = 0.0
        for h in range(lse_scr.shape[0]):
            lanes = pl.ds(h * d, d)
            q, do = q_ref[0, rows, lanes], do_ref[0, rows, lanes]
            p = _tile_probs(q, k, picked, lse_scr[h, rows, :1])
            dv = dv + jax.lax.dot_general(
                p.astype(do.dtype), do, _TN,
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, _NT,
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_scr[h, rows, :1])).astype(q.dtype)
            dk = dk + jax.lax.dot_general(
                ds, q, _TN, preferred_element_type=jnp.float32)
        dv_scr[cols, :] += dv
        dk_scr[cols, :] += dk

    @pl.when((i * block + block - 1) // w >= jb * cw)   # not wholly above
    def _step():
        _statistics_off_lanes(lse_ref, delta_ref, lse_scr, delta_scr)
        _walk_tiles(counts_ref, b, i, jb * cw, cw, tile, **geometry)

    @pl.when((i == pl.num_programs(3) - 1) & (r == pl.num_programs(4) - 1))
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _geometry(layout, plan):
    return dict(block=plan.block, sub=layout.rows, w=layout.chunk,
                nq=layout.padded // layout.rows, n=layout.chunks)


def _row_walk_specs(layout, plan, heads, rep, d):
    """Block specs of the grid ``(b, g, i, r, kj)`` of the forward and dq: a
    program owns row block ``i`` of the ``plan.heads`` query heads from
    ``g * rep + r * plan.heads`` and has span ``kj`` of KV head ``g``
    resident.  The group's heads follow one another over a row block, so
    its words are fetched once for them all, and k and v change with the KV
    head alone where the span is the whole length (one buffer each then).
    A span past the block's diagonal names the diagonal's again (never
    loaded)."""
    block, span, hp = plan.block, plan.span, plan.heads

    def near(i, kj):
        return jnp.minimum(kj, (i * block + block - 1) // span)

    once = dict(pipeline_mode=pl.Buffered(1)) if plan.walk == "resident" \
        else {}
    rows = pl.BlockSpec(
        (1, block, hp * d),
        lambda b, g, i, r, kj, *_: (b, i, g * (rep // hp) + r))
    cols = pl.BlockSpec((1, span, d),
                        lambda b, g, i, r, kj, *_: (b, near(i, kj), g), **once)
    words = pl.BlockSpec((1, block, layout.chunk),
                         lambda b, g, i, r, kj, *_: (b, i, 0))
    stat = pl.BlockSpec(
        (hp, 1, block),
        lambda b, g, i, r, kj, *_: ((b * heads + g * rep) // hp + r, 0, i))
    return rows, cols, words, stat


def _attend_need(layout, plan, d, itemsize, rowlike, tiles):
    """VMEM a forward or dq program holds: ``rowlike`` blocks of q's shape
    and the words double-buffered, k and v of a span (once where resident),
    three ``[block, 128]`` float32 scratches' worth a head and ``tiles``
    float32 temporaries of a tile."""
    block, w, hp = plan.block, layout.chunk, plan.heads
    kv = (2 if plan.walk == "resident" else 4) * plan.span * d * itemsize
    return (2 * rowlike * hp * block * d * itemsize + kv + 2 * block * w * 4
            + 3 * hp * block * LANES * 4 + tiles * layout.rows * w * 4)


# Each call is behind a ``jax.jit`` of its own, as ``loss_grads_call``: a
# model's layers share one trace and one lowered body a kernel.
@functools.partial(jax.jit, static_argnames=("heads", "layout", "plan"))
def fwd_call(q, k, v, words, counts, heads, layout, plan):
    """Pre-scaled q ``[B, Sp, N*D]``, k, v ``[B, Sp, N_kv*D]``, the words
    and the tiles' counts ``[B * nq * n]`` -> (o, lse ``[B*N, 1, Sp]``)."""
    from jax.experimental.pallas import tpu as pltpu

    b, sp, hw = q.shape
    d = hw // heads
    rep = hw // k.shape[2]
    block, hp = plan.block, plan.heads
    rows, cols, wspec, stat = _row_walk_specs(layout, plan, heads, rep, d)
    pairs = b * heads * sp * sp // 2
    statistic = pltpu.VMEM((hp, block, LANES), jnp.float32)
    with jax.named_scope(ATTENTION):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, geometry=_geometry(layout, plan),
                              cps=plan.span // layout.chunk),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, heads // rep, sp // block, rep // hp,
                      sp // plan.span),
                in_specs=[rows, cols, cols, wspec], out_specs=[rows, stat],
                scratch_shapes=[statistic, statistic,
                                pltpu.VMEM((block, hp * d), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                       jax.ShapeDtypeStruct((b * heads, 1, sp), jnp.float32)],
            cost_estimate=pl.CostEstimate(
                flops=4 * pairs * d, transcendentals=pairs,
                bytes_accessed=2 * q.size * q.dtype.itemsize),
            interpret=interpret_mode(),
            **_params("parallel", "parallel", "parallel", "parallel",
                      "arbitrary", vmem=_vmem_limit(_attend_need(
                          layout, plan, d, q.dtype.itemsize, 2, 3))),
        )(counts, q, k, v, words)


@functools.partial(jax.jit, static_argnames=("heads", "layout", "plan"))
def bwd_call(q, k, v, do, lse, delta, words, counts, heads, layout, plan):
    """-> (dq of the pre-scaled q, dk, dv), in two passes."""
    from jax.experimental.pallas import tpu as pltpu

    b, sp, hw = q.shape
    d = hw // heads
    kv = k.shape[2] // d
    rep = heads // kv
    block, size, w, hp = plan.block, plan.cols, layout.chunk, plan.heads
    item = q.dtype.itemsize
    geometry = _geometry(layout, plan)
    statistic = pltpu.VMEM((hp, block, LANES), jnp.float32)
    rows, cols, wspec, stat = _row_walk_specs(layout, plan, heads, rep, d)
    with jax.named_scope(ATTENTION):
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, geometry=geometry,
                              cps=plan.span // w),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, kv, sp // block, rep // hp, sp // plan.span),
                in_specs=[rows, cols, cols, rows, stat, stat, wspec],
                out_specs=rows,
                scratch_shapes=[pltpu.VMEM((block, hp * d), jnp.float32),
                                statistic, statistic]),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            interpret=interpret_mode(),
            **_params("parallel", "parallel", "parallel", "parallel",
                      "arbitrary", vmem=_vmem_limit(_attend_need(
                          layout, plan, d, item, 3, 3))),
        )(counts, q, k, v, do, lse, delta, words)

    def far(i, jb):     # a row block before the columns' first: never loaded
        return jnp.maximum(i, (jb * size) // block)

    rows_j = pl.BlockSpec(
        (1, block, hp * d),
        lambda b, g, jb, i, r, *_: (b, far(i, jb), g * (rep // hp) + r))
    cols_j = pl.BlockSpec((1, size, d), lambda b, g, jb, i, r, *_: (b, jb, g))
    words_j = pl.BlockSpec((1, block, w),
                           lambda b, g, jb, i, r, *_: (b, far(i, jb), 0))
    stat_j = pl.BlockSpec(
        (hp, 1, block), lambda b, g, jb, i, r, *_: (
            (b * heads + g * rep) // hp + r, 0, far(i, jb)))
    kv_shape = jax.ShapeDtypeStruct(k.shape, k.dtype)
    need = (4 * (hp * block + 2 * size) * d * item + 2 * block * w * 4
            + 2 * size * d * 4 + 2 * hp * block * LANES * 4
            + 5 * layout.rows * w * 4)
    with jax.named_scope(ATTENTION):
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, geometry=geometry, cw=size // w),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, kv, sp // size, sp // block, rep // hp),
                in_specs=[rows_j, cols_j, cols_j, rows_j, stat_j, stat_j,
                          words_j],
                out_specs=[cols_j, cols_j],
                scratch_shapes=[pltpu.VMEM((size, d), jnp.float32),
                                pltpu.VMEM((size, d), jnp.float32),
                                statistic, statistic]),
            out_shape=[kv_shape, kv_shape],
            interpret=interpret_mode(),
            **_params("parallel", "parallel", "parallel", "arbitrary",
                      "arbitrary", vmem=_vmem_limit(need)),
        )(counts, q, k, v, do, lse, delta, words)
    return dq, dk, dv


# ------------------------------------------------ head-averaged probabilities
class ProbsPlan(NamedTuple):
    """Sizes of one ``dsa_head_probs`` call.  A grid program owns, and
    computes at a time, the selection's tile of the output,
    ``SelLayout.rows`` x ``SelLayout.chunk``."""
    heads: int      # query heads of a group side by side in the body


def head_probs_plan(layout, rep, heads=None):
    """``dsa_head_probs``' own sizes, from what a call can see: the
    selection's layout (the tile) and the query heads a KV head.  ``heads``
    overrides (tests, ``tools/profile_dsa.py``'s sweep).  ``attend_plan``'s
    rule for the heads side by side: their chains (a product, an ``exp``)
    are independent, and their probabilities are summed before they meet
    the output block.  Measured at the Keye cell's shape: BENCH_KERNELS.md,
    PR 60."""
    return ProbsPlan(_side_by_side(layout, rep) if heads is None else heads)


def _head_probs_kernel(counts_ref, at_ref, q_ref, k_ref, lse_ref, words_ref,
                       out_ref, lse_scr, *, nq, n, d, rep, hp, heads):
    """Grid (b, row block i of the call's rows, chunk j): the program walks
    the query heads in its body, ``hp`` of a KV head's group side by side,
    and stores the mean of their probabilities of the tile over the chosen
    pairs.  A tile no row chose in (all above the diagonal) is zeros."""
    b, i, j = (pl.program_id(a) for a in range(3))
    bq = q_ref.shape[1]

    @pl.when(j == 0)
    def _statistics():
        # once a row block: head ``h`` of a side-by-side set in lane ``h``
        lane = jax.lax.broadcasted_iota(jnp.int32, (bq, LANES), 1)

        def side(s):
            slab = jnp.zeros((bq, LANES), jnp.float32)
            for h in range(hp):
                slab = jnp.where(lane == h,
                                 _rows_off_lanes(lse_ref[s * hp + h]), slab)
            lse_scr[s] = slab

        _walk(0, heads // hp, side)

    out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(_tile_count(counts_ref, b, at_ref[0] + i, j, nq, n) > 0)
    def _tile():
        def side(s):
            k = k_ref[0, :, _ds(s // (rep // hp) * d, d)]
            q = q_ref[0, :, _ds(s * hp * d, hp * d)]
            stat = lse_scr[s]
            # exp of a pair no row chose may be anything: masked below
            out_ref[0] += sum(jnp.exp(jax.lax.dot_general(
                q[:, h * d:(h + 1) * d], k, _NT,
                preferred_element_type=jnp.float32) - stat[:, h:h + 1])
                for h in range(hp))

        _walk(0, heads // hp, side)
        out_ref[0] = jnp.where(_chosen(words_ref, j),
                               out_ref[0] * (1.0 / heads), 0.0)


# Behind a ``jax.jit`` of its own, as the attention's calls: the scan's body
# and every layer share one trace and one lowered kernel body.
@functools.partial(jax.jit,
                   static_argnames=("rows", "heads", "layout", "plan"))
def head_probs_call(q, k, lse, words, counts, at, rows, heads, layout, plan):
    """``mean_h softmax_{S_t}(q_h . k)`` of the ``rows`` rows from row block
    ``at`` (a traced int32 ``[1]``, in blocks of ``layout.rows``) ->
    float32 ``[B, rows, Sp]``, zero where a row did not choose."""
    from jax.experimental.pallas import tpu as pltpu

    b, sp, hw = q.shape
    d = hw // heads
    kw = k.shape[2]
    bq, w, n = layout.rows, layout.chunk, layout.chunks
    hp, item = plan.heads, q.dtype.itemsize

    def near(i, j, at):         # a chunk past the diagonal's: never loaded
        return jnp.minimum(j, ((at[0] + i) * bq + bq - 1) // w)

    # q (one buffer: it changes with the row block alone), k, the heads'
    # log-sum-exp (a sublane tile each) and off the lanes, words and output,
    # the heads' float32 tiles
    need = (bq * hw * item + 2 * w * kw * item + 2 * heads * 8 * bq * 4
            + (heads // hp) * bq * LANES * 4 + 4 * bq * w * 4
            + (hp + 2) * bq * w * 4)
    with jax.named_scope(HEAD_PROBS):
        return pl.pallas_call(
            functools.partial(_head_probs_kernel, nq=sp // bq, n=n, d=d,
                              rep=hw // kw, hp=hp, heads=heads),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(b, rows // bq, n),
                in_specs=[
                    pl.BlockSpec((1, bq, hw), lambda b, i, j, c, at: (
                        b, at[0] + i, 0), pipeline_mode=pl.Buffered(1)),
                    pl.BlockSpec((1, w, kw), lambda b, i, j, c, at: (
                        b, near(i, j, at), 0)),
                    pl.BlockSpec((heads, 1, bq), lambda b, i, j, c, at: (
                        b, 0, at[0] + i)),
                    pl.BlockSpec((1, bq, w), lambda b, i, j, c, at: (
                        b, at[0] + i, 0))],
                out_specs=pl.BlockSpec((1, bq, w),
                                       lambda b, i, j, c, at: (b, i, j)),
                scratch_shapes=[pltpu.VMEM((heads // hp, bq, LANES),
                                           jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((b, rows, sp), jnp.float32),
            interpret=interpret_mode(),
            **_params("parallel", "parallel", "arbitrary",
                      vmem=_vmem_limit(need)),
        )(counts, at, q, k, lse, words)


# ------------------------------------------- the indexer's loss and gradients
def loss_rows(layout):
    """Rows of a ``dsa_loss_grads`` program's block: the most whose float32
    scores against the whole length take 8 MiB of VMEM (128 at 16k).  At
    twice that the kernel alone is a sixth faster, but the step is not:
    under its 48 MiB XLA no longer keeps the selection's words in VMEM for
    the attention kernels, which lose more (PERF.md, PR 54)."""
    fits = (r for r in (256, LANES)
            if layout.rows % r == 0 and r * layout.padded * 4 <= 8 << 20)
    return next(fits, LANES)


def _onto_lanes(x):
    """``[rows, W]`` -> ``[rows, 128]``: the lane blocks summed, so that a
    row's sum costs one reduction across lanes a block of rows and not one a
    tile."""
    return sum(x[:, c:c + LANES] for c in range(0, x.shape[1], LANES))


def _loss_grads_kernel(counts_ref, at_ref, q_ref, qt_ref, k_ref, kt_ref,
                       w_ref, pbar_ref, words_ref, _dq, _dw, loss_ref, dq_ref,
                       dkt_ref, dw_ref, sc_scr, m_scr, l_scr, loss_scr,
                       dq_scr, dw_scr, *, nq, n, bq, rows, chunk, heads,
                       total, grads):
    """Grid (b, row block i of the chunk of rows, step t): the block walks
    the chunks ``j = t mod n`` twice.  First the scores ``I[t, s] = sum_j
    w_j relu(q_j . k)`` of a tile into VMEM and the rows' running maximum
    and sum over their chosen; then, from the rows' log-sum-exp, the KL's
    terms and its gradient to the scores, and a head at a time what that
    sends to ``w`` (row sums), ``q^I`` (held for the block) and ``k^I``
    (transposed, ``[D, W]`` a chunk: summed over the row blocks in the
    output block, which stays where it is)."""
    b, i, t = (pl.program_id(a) for a in range(3))
    j = jax.lax.rem(t, n)
    row0 = at_ref[0] * bq + i * rows
    live = (j <= (row0 + rows - 1) // chunk) & (
        _tile_count(counts_ref, b, row0 // bq, j, nq, n) > 0)

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        for scr in (l_scr, loss_scr, dq_scr, dw_scr):
            scr[...] = jnp.zeros_like(scr)

    @pl.when((t == 0) & (i == 0))
    def _init_dk():
        dkt_ref[...] = jnp.zeros_like(dkt_ref)

    def dots(h):
        return jax.lax.dot_general(q_ref[0, h], kt_ref[0, 0], _NN,
                                   preferred_element_type=jnp.float32)

    @pl.when((t < n) & live)
    def _scores():
        # the heads unrolled: a loop of 4 heads a body reads 17.7 ms a layer
        # where this reads 15.6, one of 1 head 23.3 (PERF.md, PR 54)
        acc = jnp.zeros((rows, chunk), jnp.float32)
        for h in range(heads):
            acc = acc + w_ref[0, :, h:h + 1] * jnp.maximum(dots(h), 0.0)
        sc_scr[j] = acc
        chosen = _chosen(words_ref, j)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(
            jnp.where(chosen, acc, NEG_INF), axis=1, keepdims=True))
        p = jnp.where(chosen, jnp.exp(acc - m_new), 0.0)
        l_scr[...] = jnp.broadcast_to(
            l_scr[:, :1] * jnp.exp(m_prev - m_new)
            + jnp.sum(p, axis=1, keepdims=True), l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(t == n)
    def _stat():
        # a row that chose nothing (past the length) has 0, and gives zeros
        l = l_scr[...]
        m_scr[...] = jnp.where(
            l > 0.0, m_scr[...] + jnp.log(jnp.where(l > 0.0, l, 1.0)), 0.0)

    @pl.when((t >= n) & live)
    def _grads():
        logp = sc_scr[j] - m_scr[:, :1]
        pbar = pbar_ref[0]
        seen = pbar > 0.0
        loss_scr[...] += _onto_lanes(jnp.where(
            seen, pbar * (jnp.log(jnp.where(seen, pbar, 1.0)) - logp), 0.0))
        if not grads:
            return
        ds = (jnp.where(_chosen(words_ref, j), jnp.exp(logp), 0.0)
              - pbar) / total
        dkt = jnp.zeros(dkt_ref.shape[2:], jnp.float32)
        for h in range(heads):
            d = dots(h)
            dw_scr[h] += _onto_lanes(ds * jnp.maximum(d, 0.0))
            # rounded once to the compute type, for both products
            through = jnp.where(d > 0.0, ds * w_ref[0, :, h:h + 1],
                                0.0).astype(q_ref.dtype)
            dq_scr[h] += jax.lax.dot_general(
                through, k_ref[0], _NN, preferred_element_type=jnp.float32)
            dkt = dkt + jax.lax.dot_general(
                qt_ref[0, h], through, _NN,
                preferred_element_type=jnp.float32)
        dkt_ref[0, j] += dkt

    @pl.when(t == 2 * n - 1)
    def _finalize():
        loss_ref[0, 0] = jnp.broadcast_to(
            jnp.sum(jnp.sum(loss_scr[...], axis=1, keepdims=True), axis=0,
                    keepdims=True), loss_ref.shape[2:])
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, heads), 1)
        dw = jnp.zeros((rows, heads), jnp.float32)
        for h in range(heads):
            dw = jnp.where(lane == h, jnp.sum(dw_scr[h], axis=1,
                                              keepdims=True), dw)
        dw_ref[0] = dw


# Behind a ``jax.jit`` of its own, as ``pallas_eva_pool.py``'s calls: the
# kernel's body is long straight-line code (two loops over the heads), and
# lowered at every layer's call site it cost a process 19 s of set-up
# (PERF.md, PR 54); a model's layers are one trace and one lowered body.
@functools.partial(jax.jit, static_argnames=("total", "layout", "grads"))
def loss_grads_call(qi, qt, ki, kt, w, pbar, words, counts, at, dq, dw,
                    total, layout, grads=True):
    """The indexer's loss of the rows ``pbar [B, R, Sp]`` (float32,
    ``head_probs_call``'s) describes, from row block ``at`` (a traced int32
    ``[1]``, in blocks of ``layout.rows``), against ``q^I [B, H_I, Sp,
    D_I]``, ``k^I [B, Sp, D_I]`` (and both transposed, so that every product
    is a plain one: ``[B, H_I, D_I, Sp]``, and a chunk at a time ``[B, n,
    D_I, W]``) and ``w [B, Sp, H_I]`` float32 -> (the KL's terms summed,
    one float a row block in ``[B, R / rows, 8, 128]`` at ``[..., 0, 0]``;
    ``dq`` and ``dw``, the whole length's (``q^I``'s and ``w``'s shapes and
    types), with these rows' gradients written where they lie (in place: a
    scan that STACKED a call's outputs had XLA fuse the stack into the
    call, under its own 16 MiB of scoped VMEM and not the call's);
    ``dk^I`` of these rows alone, float32, in ``kt``'s layout), the
    gradients already over ``total`` rows.  Without ``grads`` the three are
    not made (zeros)."""
    from jax.experimental.pallas import tpu as pltpu

    b, heads, sp, d = qi.shape
    size = pbar.shape[1]
    bq, w_, n = layout.rows, layout.chunk, layout.chunks
    rows = loss_rows(layout)

    def near(i, t, at):
        return jnp.minimum(t % n, (at[0] * bq + i * rows + rows - 1) // w_)

    def block(i, at):           # the kernel's blocks divide the layout's
        return at[0] * (bq // rows) + i

    item = qi.dtype.itemsize
    need = (n * rows * w_ * 4 + 2 * n * d * w_ * 4          # scores, dk^I
            + 4 * heads * rows * LANES * (item + 2)          # q^I, dq^I, sums
            + 2 * heads * d * rows * item + 12 * rows * w_ * 4)
    with jax.named_scope(LOSS_GRADS):
        return pl.pallas_call(
            functools.partial(_loss_grads_kernel, nq=sp // bq, n=n, bq=bq,
                              rows=rows, chunk=w_, heads=heads,
                              total=float(total), grads=grads),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(b, size // rows, 2 * n),
                in_specs=[
                    pl.BlockSpec((1, heads, rows, d), lambda b, i, t, c, at: (
                        b, 0, block(i, at), 0)),
                    pl.BlockSpec((1, heads, d, rows), lambda b, i, t, c, at: (
                        b, 0, 0, block(i, at))),
                    pl.BlockSpec((1, w_, d), lambda b, i, t, c, at: (
                        b, near(i, t, at), 0)),
                    pl.BlockSpec((1, 1, d, w_), lambda b, i, t, c, at: (
                        b, near(i, t, at), 0, 0)),
                    pl.BlockSpec((1, rows, heads), lambda b, i, t, c, at: (
                        b, block(i, at), 0)),
                    # read on the second walk alone
                    pl.BlockSpec((1, rows, w_), lambda b, i, t, c, at: (
                        b, i, jnp.where(t < n, 0, near(i, t, at)))),
                    pl.BlockSpec((1, rows, w_), lambda b, i, t, c, at: (
                        b, block(i, at), 0)),
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=[
                    pl.BlockSpec((1, 1, 8, LANES),
                                 lambda b, i, t, c, at: (b, i, 0, 0)),
                    pl.BlockSpec((1, heads, rows, d), lambda b, i, t, c, at: (
                        b, 0, block(i, at), 0)),
                    pl.BlockSpec((1, n, d, w_),
                                 lambda b, i, t, c, at: (b, 0, 0, 0)),
                    pl.BlockSpec((1, rows, heads), lambda b, i, t, c, at: (
                        b, block(i, at), 0))],
                scratch_shapes=[
                    pltpu.VMEM((n, rows, w_), jnp.float32),
                    pltpu.VMEM((rows, LANES), jnp.float32),
                    pltpu.VMEM((rows, LANES), jnp.float32),
                    pltpu.VMEM((rows, LANES), jnp.float32),
                    pltpu.VMEM((heads, rows, d), jnp.float32),
                    pltpu.VMEM((heads, rows, LANES), jnp.float32)]),
            out_shape=[
                jax.ShapeDtypeStruct((b, size // rows, 8, LANES),
                                     jnp.float32),
                jax.ShapeDtypeStruct(dq.shape, dq.dtype),
                jax.ShapeDtypeStruct((b, n, d, w_), jnp.float32),
                jax.ShapeDtypeStruct(dw.shape, dw.dtype)],
            input_output_aliases={9: 1, 10: 3},
            interpret=interpret_mode(),
            **_params("parallel", "arbitrary", "arbitrary",
                      vmem=_vmem_limit(need)),
        )(counts, at, qi, qt, ki, kt, w, pbar, words, dq, dw)

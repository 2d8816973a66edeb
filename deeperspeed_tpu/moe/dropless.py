"""Dropless top-k routing over a chip's share of the experts.

Beside ``sharded_moe.py``'s capacity-mask path (a dense ``[S, E, C]``
dispatch, top-1 / top-2, every expert here): a layer that is TOLD which
experts it holds -- a contiguous range ``[first_expert, first_expert +
experts_held)`` of ``num_experts`` -- scores every token over ALL the
experts, keeps each token's top ``k``, and computes the part of the result
that its own experts give.  What the experts on other chips would add is
theirs to add (expert parallelism's exchange is not here; on one chip the
layer runs without it and nothing stands in for the absent chips).

No token is dropped at any load.  A token picks ``k`` different experts, so
it sends the experts held here at most ``min(k, experts_held)`` slots, and
``tokens x experts_held`` is the worst case the shapes allow.  The slots are
laid out expert by expert, each expert's in the order of its tokens
(``slot_plan``: one sort of the ``[held x tokens]`` mask's positions), and
walked an expert at a time, ``ROWS_PER_CHUNK`` slots a chunk: a chunk
gathers its tokens' rows, multiplies them by ITS expert's two matrices and
adds its weighted rows to the output.  The walk is a loop of as many chunks
as the slots routed here need (``sum_e ceil(slots_e / ROWS_PER_CHUNK)``), up
to the worst case, so the cost follows the load and the memory is a
chunk's.  The backward pass walks the same chunks (a custom VJP).  A chunk
is one expert's, so its matmuls are plain ones, its tokens are all different
and in order (the gathers and scatter-adds are told so), and an expert's
weight gradient is added to in place.

Two forms, chosen by the shapes alone (``walk_form``).  By *slots* (above)
a chunk is some of ONE expert's slots, read by index and added to the output
by XLA's scatter-add: on a v5e that is a pass over the whole ``[tokens,
width]`` float32 table plus 0.63 us a row (1.55 ms for 1024 rows into [32768,
2304]), twice a chunk.  Its fixed costs are small, so it is the way of a
lightly loaded share of a narrow table (a few per cent of the (token, held
expert) pairs chosen, rows of 1,024: the hybrid model's top-22 of 512).
What it pays grows with the table and not with the rows, so the rule
reckons, besides the pairs' share, the bytes one forward walk by slots would
pass over under even routing (``slots_walk_bytes``: the held experts' chunks
times the float32 table), and from ``GROUPED_FROM_TABLE_BYTES`` on the layer
walks grouped however few the pairs (Laguna's top-10 of 256 chooses 3.9 %,
and its 24 chunks would pass 4.83 GB of a ``[16384, 3072]`` table).  The
*grouped* form is the way of a heavily loaded share (Mellum's top-8 of 64
over 16 held) and of a wide one: the sorted
slots are walked ``rows`` at a time ACROSS experts, a chunk's rows gathered
in that order and multiplied by a grouped matmul (``ops/pallas_gmm.py``: a
row tile by the matrix of the expert it falls in by the counts, a tile that
straddles two experts visited once for each, no expert padded to a
capacity; only the tiles under the slots routed here are visited), and
each chunk's results are written, unweighted and in the rows' own type, to
their places in a buffer of ``tokens x min(k, held)`` rows in sorted order
(handed over unwritten: a slot's place is written before it is read).
The output is then a *gather*: a token has at most ``min(k, held)`` slots,
so it reads its own slots' rows by their sorted positions (a zero row where
a choice is not held), weights them and sums them in float32: nothing is
scatter-added into a ``[tokens, width]`` table, and only rows that are
written are touched.  The backward pass is the transpose: a token's
cotangent gathered in sorted order, the rows and the hidden recomputed a
chunk at a time, the matrices' gradients by the transposed grouped matmul
(an expert's rows' outer products, float32, in place), ``d_x`` by the same
gather-and-sum over a buffer of the chunks' ``d_rows``.  The same sums in
the same types either way; the memory is a chunk's plus that one buffer.
What the grouped form costs a program's set-up is held down on purpose
(PERF.md section 6, PRs 40 and 41): its forward, its backward and its plan
are each behind one ``jax.jit``, so that a model's layers of one shape are
one trace and one body of the lowered step; and every sort but one is in
the plan, which a recomputed layer keeps (``PLAN_SAVED_BY_REMAT``): a sort
is how the TPU permutes narrow rows, and each is a megabyte or two of the
step's executable, which is loaded from the compile cache in every process.

Two things of a layer are arguments of the one walk.  The scoring:
``sigmoid_topk`` is DeepSeek-V3's, as the Nemotron-H family uses it
(``sigmoid`` of float32 logits, the choice by ``score + selection_bias``,
the weights the chosen scores themselves, normalised over the chosen and
scaled); ``softmax_topk`` is the older one (Mixtral, Qwen-MoE, Mellum: the
softmax over all the experts, its ``k`` largest, normalised over the
chosen).  And an expert's body between its two matrices, ``activation``:
``relu2`` on ``w_in`` [L, F], or ``gated_silu`` on a fused ``gate | up``
``w_in`` [L, 2 F] (``silu(gate) * up``); either way one matmul in, one out.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..ops import pallas_gmm
from ..telemetry.trace import count_kernel_path

# a grouped chunk is whole tiles of the kernels' rows, and the fewest rows a
# tile has (a bfloat16 tile's 16 sublanes)
_ROWS_STEP = 128

#: Slots a chunk of the routed walk, all of one expert.  An expert with a
#: few slots costs a chunk all the same, and a chunk costs by its rows: on a
#: v5e, 16,384 tokens in a 1024-wide latent, forward and backward, a chunk
#: takes 0.42 / 0.72 / 0.9 ms at 256 / 512 / 1024 rows, most of it the two
#: scatter-adds of its rows (0.5 us a row; the matmuls are a tenth), so 8
#: experts with 40 slots each cost 3.4 / 5.9 / 7.3 ms and 8 with 704 each
#: 9.7 / 11.5 / 7.3 ms (PERF.md, PR 34).
ROWS_PER_CHUNK = 256
#: Sorted slots a chunk of the grouped walk (rows of any experts), and the
#: share of the (token, held expert) pairs that even routing must choose
#: (``k / num_experts``) for a layer's walk to take the grouped form.
#: Both forms on a v5e at both models' shapes, forward and backward, ms a
#: layer (`tools/profile_moe_walk.py`; PERF.md, PR 40).  Mellum's layer
#: (32,768 tokens of 2,304 floats, 16 gated experts of 896) at 2,048 / 4,096 /
#: 6,250 slots an expert (6.25 % of the pairs chosen, its own 12.5 %, 19 %):
#: grouped 26.3 / 43.2 / 64.2 in chunks of 8,192 rows (4,096 and 16,384 rows
#: read 4 % less and 8 % more before the tokens were taken by load; tiles of
#: 256, 512 and 1,024 rows alike), of it the kernels 9.6 / 18.8 / 31.1, 81-88 %
#: of the MXU's peak at the slots' rows; by slots, 1,024 a chunk, 131.7 /
#: 240.7.  The hybrid model's layer (16,384 tokens of 1,024 floats, 8 relu2
#: experts of 2,688) at 40 / 704 slots an expert (0.24 %, its own 4.3 %):
#: grouped 5.15 / 6.22, by slots, 256 a chunk, 4.57 / 10.88.  They cross
#: under 5 % at either model's shapes; the threshold is held at 5 % (ISSUE
#: 40), which keeps the hybrid model's walk what it was.
ROWS_PER_GROUPED_CHUNK = 8192
GROUPED_FROM_SHARE = 1 / 20
#: ... or the bytes one forward walk by slots would pass over under even
#: routing (``slots_walk_bytes``) from which a layer takes the grouped form
#: however few the pairs: what the walk by slots pays is the table, a pass
#: over ``[tokens, width]`` float32 or three a chunk, and the share does not
#: see the width.  The three models' layers reckon 24 x 67.1 MB = 1.61 GB
#: (the hybrid: 16,384 x 1,024, top-22 of 512, 8 held), 24 x 201.3 MB = 4.83
#: GB (Laguna: 16,384 x 3,072, top-10 of 256, 8 held) and 256 x 302.0 MB =
#: 77.3 GB (Mellum: 32,768 x 2,304, top-8 of 64, 16 held).  Both forms on
#: one v5e in one call, forward and backward, ms a layer
#: (`tools/profile_moe_walk.py`; BENCH_KERNELS.md, PR 48).  Laguna's layer
#: (8 gated experts of 1,024) at 80 / 400 / 640 / 1,200 slots an expert and
#: at one expert's 3,200 over seven of 300 (a collapsed load, the lightest
#: world's, even routing, the heaviest drift, a fullest expert five times the
#: mean): grouped 9.87 / 11.07 / 11.60 / 16.14 / 12.01 in one chunk or two
#: (the kernels 2.59-6.15 of it), by slots, 256 a chunk, 19.68 / 34.66 /
#: 49.11 / 77.11 / 55.19: 2.0-2.5 ms a chunk where the hybrid's chunk is
#: 0.45, and grouped ahead at every load.  The hybrid's layer at 40 / 350 /
#: 704: grouped 5.02 / 5.68 / 6.27, by slots 4.42 / 7.68 / 10.73, as PR 40
#: read them.  By slots a layer costs 6.7-10 ms for every GB reckoned here,
#: grouped 5-6 ms at even routing on a narrow table, so UNDER EVEN ROUTING
#: the forms cross near 0.8 GB, under all three models; the hybrid's load
#: collapses to 44 slots an expert within its window, where the walk by
#: slots is 0.6 ms a layer ahead, which no rule on shapes can see.  The
#: constant is held between the hybrid's 1.61 GB and Laguna's 4.83 GB (ISSUE
#: 48), which keeps the hybrid model's walk what it was.
GROUPED_FROM_TABLE_BYTES = 3e9
#: The name a ``jax.checkpoint`` policy keeps the grouped walk's plan by
#: (``save_only_these_names``): a few int32 a token (4.3 MB of Mellum's
#: layer) in place of the plan's sorts again in the recomputed layer.
PLAN_SAVED_BY_REMAT = "moe_grouped_plan"


def slots_walk_bytes(tokens, k, num_experts, width, experts_held):
    """The bytes one forward walk by slots would pass over under even
    routing, from the shapes alone: every chunk (each held expert's
    ``tokens * k / num_experts`` slots, ``ROWS_PER_CHUNK`` a chunk) is a pass
    over the float32 ``[tokens, width]`` table."""
    chunks = experts_held * -(-tokens * k // (num_experts * ROWS_PER_CHUNK))
    return chunks * tokens * width * 4


def walk_form(tokens, k, num_experts, widths=(), experts_held=0):
    """-> (whether the walk is the grouped one, rows a chunk), from the
    shapes alone: grouped where the kernels take the ``widths`` (the
    tokens', the two matrices') and either even routing chooses a twentieth
    or more of the pairs or the walk by slots would pass over
    ``GROUPED_FROM_TABLE_BYTES`` (``slots_walk_bytes`` of the tokens' width
    and the ``experts_held``); else an expert's slots, ``ROWS_PER_CHUNK`` a
    chunk."""
    loaded = k / num_experts >= GROUPED_FROM_SHARE
    wide = bool(widths) and slots_walk_bytes(
        tokens, k, num_experts, widths[0],
        experts_held) >= GROUPED_FROM_TABLE_BYTES
    if (loaded or wide) and pallas_gmm.takes(*widths):
        # no more rows a chunk than the slots there can be, in whole tiles
        most = -(-tokens * k // _ROWS_STEP) * _ROWS_STEP
        return True, min(ROWS_PER_GROUPED_CHUNK, most)
    return False, min(ROWS_PER_CHUNK, tokens)


def sigmoid_topk(logits, k, selection_bias=None, normalize=True, scale=1.0):
    """``logits`` [T, E] float32 -> (chosen experts [T, k] int32, their
    weights [T, k] float32).  The choice is by ``sigmoid(logits) +
    selection_bias``; the weight is the score without the bias, over the sum
    of the chosen scores if ``normalize``, times ``scale``."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    if selection_bias is None:
        # the k largest scores ARE the weights: no gather of [T, k] from
        # [T, E] (18 ms a step of the 8k cell on a v5e, PERF.md PR 34)
        weights, chosen = jax.lax.top_k(scores, k)
    else:
        _, chosen = jax.lax.top_k(scores + selection_bias, k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), weights * scale


def softmax_topk(logits, k, selection_bias=None, normalize=True, scale=1.0):
    """``logits`` [T, E] float32 -> (chosen experts [T, k] int32, their
    weights [T, k] float32): the softmax over ALL the experts, its ``k``
    largest (by ``probability + selection_bias`` where a bias is given),
    over the sum of the chosen if ``normalize`` (``norm_topk_prob``), times
    ``scale``."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if selection_bias is None:
        weights, chosen = jax.lax.top_k(probs, k)
    else:
        _, chosen = jax.lax.top_k(probs + selection_bias, k)
        weights = jnp.take_along_axis(probs, chosen, axis=-1)
    if normalize:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), weights * scale


def held_weights(chosen, weights, first_expert, experts_held):
    """The choices that fall on the experts held here, as a small dense
    table: -> (weights [T, held] float32, 0 where not chosen; chosen [T,
    held] bool).  ``held`` is a handful, so this is the whole routing."""
    local = chosen - first_expert                             # [T, k]
    hit = local[..., None] == jnp.arange(experts_held)        # [T, k, held]
    return (jnp.sum(jnp.where(hit, weights[..., None], 0.0), axis=1),
            jnp.any(hit, axis=1))


def slot_plan(is_chosen):
    """``is_chosen`` [T, held] bool -> (``order`` [held * T] int32: the
    chosen (expert, token) pairs as ``expert * T + token``, expert by expert
    and each expert's by token, then ``held * T`` in every place past the
    last slot; each expert's number of slots [held])."""
    T, held = is_chosen.shape
    flat = is_chosen.T.reshape(-1)
    at = jnp.arange(held * T, dtype=jnp.int32)
    return (jnp.sort(jnp.where(flat, at, held * T)),
            jnp.sum(is_chosen.astype(jnp.int32), axis=0))


def relu2(x):
    r = jax.nn.relu(x)
    return r * r


def gated_silu(x):
    """``silu(gate) * up`` of a fused ``[..., gate | up]``."""
    gate, up = jnp.split(x, 2, axis=-1)
    return jax.nn.silu(gate) * up


def _slots_chunk(plan, c, rows, tokens):
    """Chunk ``c`` of the walk by slots -> (its expert, the (expert, token)
    pair and the token of each of its rows, how many of its rows are slots).
    Rows that are no slots point past the arrays' ends, each at a place of
    its own, so a chunk's indices are all different and ascending."""
    order, counts = plan
    per = -(-counts // rows)                     # chunks an expert needs
    upto = jnp.cumsum(per)
    e = jnp.sum(c >= upto).astype(jnp.int32)
    j = c - (upto[e] - per[e])                   # the chunk within its expert
    n = jnp.minimum(rows, counts[e] - j * rows)
    lo = jnp.cumsum(counts)[e] - counts[e] + j * rows
    r = jnp.arange(rows, dtype=jnp.int32)
    pair = jax.lax.dynamic_slice(order, (lo,), (rows,))
    pair = jnp.where(r < n, pair, order.shape[0] + r)
    token = jnp.where(r < n, pair - e * tokens, tokens + r)
    return e, pair, token, n


#: what every gather and scatter-add of a chunk may be told of its indices
_ONE_EXPERTS_TOKENS = dict(unique_indices=True, indices_are_sorted=True)


class _Slots:
    """A chunk is some of one expert's slots: its rows are ``table[index]``,
    zero where the index points past the end, and ``table[index] += rows``
    drops such a row.  As many chunks as the slots routed here need."""
    chunk = staticmethod(_slots_chunk)

    @staticmethod
    def chunks(plan, rows, tokens, held):
        return jnp.sum(-(-plan[1] // rows))

    @staticmethod
    def read(table, index, rows):
        return table.at[index].get(mode="fill", fill_value=0,
                                   **_ONE_EXPERTS_TOKENS)

    @staticmethod
    def add(table, index, values):
        return table.at[index].add(values.astype(table.dtype), mode="drop",
                                   **_ONE_EXPERTS_TOKENS)


def _add_to_expert(table, e, d):
    """``table[e] += d`` in place: the one expert's slab is read and
    written, the others are not touched."""
    slab = jax.lax.dynamic_index_in_dim(table, e, keepdims=True)
    return jax.lax.dynamic_update_index_in_dim(
        table, slab + d[None].astype(table.dtype), e, axis=0)


def _expert(rows, weight, w_in, w_out, activation):
    """One expert on a chunk's rows, times each row's routing weight ->
    [R, L] float32."""
    with jax.named_scope("moe_experts"):
        hidden = jnp.dot(rows, w_in.astype(rows.dtype),
                         preferred_element_type=rows.dtype)
        y = jnp.dot(activation(hidden), w_out.astype(rows.dtype),
                    preferred_element_type=rows.dtype)
    with jax.named_scope("moe_route"):
        return y.astype(jnp.float32) * weight[:, None]


def _walk(form, plan, rows, x, w_in, body, carry):
    """``body(c, carry)`` for every chunk of the walk."""
    return jax.lax.fori_loop(
        0, form.chunks(plan, rows, x.shape[0], w_in.shape[0]), body, carry)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _routed(x, pair_w, w_in, w_out, plan, activation, rows, form):
    return _routed_fwd(x, pair_w, w_in, w_out, plan, activation, rows,
                       form)[0]


def _routed_fwd(x, pair_w, w_in, w_out, plan, activation, rows, form):
    def body(c, carry):
        out, done = carry
        with jax.named_scope("moe_route"):
            e, pair, token, n = form.chunk(plan, c, rows, x.shape[0])
            x_rows = form.read(x, token, rows)
            weight = form.read(pair_w, pair, rows)
        y = _expert(x_rows, weight, w_in[e], w_out[e], activation)
        with jax.named_scope("moe_route"):
            return form.add(out, token, y), done + n

    out, done = _walk(form, plan, rows, x, w_in, body,
                      (jnp.zeros(x.shape, jnp.float32), jnp.int32(0)))
    return (out, done), (x, pair_w, w_in, w_out, plan)


def _routed_bwd(activation, rows, form, kept, cotangents):
    """The walk again, chunk by chunk: each chunk's rows are recomputed and
    transposed, and the gradients add up in float32 where they belong: a
    token's row, a pair's weight, the one expert's matrices.  Nothing of a
    chunk outlives it, in either direction."""
    x, pair_w, w_in, w_out, plan = kept
    d_out = cotangents[0]

    def body(c, grads):
        d_x, d_pair_w, d_w_in, d_w_out = grads
        with jax.named_scope("moe_route"):
            e, pair, token, _ = form.chunk(plan, c, rows, x.shape[0])
            x_rows = form.read(x, token, rows)
            weight = form.read(pair_w, pair, rows)
            d_y = form.read(d_out, token, rows)
        _, transpose = jax.vjp(
            lambda *ops: _expert(*ops, activation),
            x_rows, weight, w_in[e], w_out[e])
        d_rows, d_weight, d_in, d_out_e = transpose(d_y)
        with jax.named_scope("moe_route"):
            return (form.add(d_x, token, d_rows),
                    form.add(d_pair_w, pair, d_weight),
                    _add_to_expert(d_w_in, e, d_in),
                    _add_to_expert(d_w_out, e, d_out_e))

    operands = (x, pair_w, w_in, w_out)
    grads = _walk(form, plan, rows, x, w_in, body, tuple(
        jnp.zeros(op.shape, jnp.float32) for op in operands))
    no_gradient = jax.tree_util.tree_map(
        lambda a: np.zeros(a.shape, jax.dtypes.float0), plan)
    return tuple(g.astype(op.dtype) for g, op in zip(grads, operands)) + (
        no_gradient,)


_routed.defvjp(_routed_fwd, _routed_bwd)


# ------------------------------------------------------- the grouped form
class _Plan(NamedTuple):
    """What the grouped walk needs of the routing (``_grouped_plan``)."""
    pairs: jax.Array        # [held * T + rows] ``slot_plan``'s sorted pairs
    counts: jax.Array       # [held] slots an expert
    pos: jax.Array          # [T, most a token] sorted places of its slots
    expert: jax.Array       # [T, most a token] and their experts
    rank: jax.Array         # [T] the token's place by falling number of slots
    by_load: jax.Array      # [T, most a token] ``pos``, its rows in that order


def _sorted_chunk(plan, c, rows, tokens):
    """Chunk ``c`` of the walk over the sorted slots, ``rows`` of them from
    ``c * rows`` on, of whichever experts -> (the (expert, token) pair and
    the token of each row, the rows each held expert has in the chunk, how
    many rows are slots).  Rows that are no slots point past the arrays'
    ends."""
    order, counts = plan.pairs, plan.counts
    lo = c * rows
    ends = jnp.cumsum(counts)
    sizes = jnp.clip(ends - lo, 0, rows) - jnp.clip(ends - counts - lo, 0,
                                                    rows)
    n = jnp.sum(sizes)
    r = jnp.arange(rows, dtype=jnp.int32)
    pair = jax.lax.dynamic_slice(order, (lo,), (rows,))
    pair = jnp.where(r < n, pair, order.shape[0] + r)
    return pair, jnp.where(r < n, pair % tokens, tokens + r), sizes, n


def _rows_of(table, index):
    """``table[index]``, a zero row where the index points past the end."""
    return table.at[index].get(mode="fill", fill_value=0)


def _tokens_a_block(tokens):
    return next((b for b in (1024, 512, 256, 128, 64, 32, 16, 8)
                 if tokens % b == 0), tokens)


def _by_load(rank, table):
    """A table of a row a token, [T, n] -> its rows by the tokens' ``rank``
    (a sort: XLA's gather of narrow rows by index is a loop of a row a turn
    on the TPU, its sort is not)."""
    key = jnp.broadcast_to(rank, table.shape[::-1])
    return jax.lax.sort((key, table.T), dimension=1, num_keys=1)[1].T


def _sum_of_slots(table, plan, rows, weight=None):
    """``out[t] = sum_j weight[t, j] * table[pos[t, j]]`` in float32: each
    token GATHERS the rows of its own slots from the sorted ``table`` -- what
    takes the place of a scatter-add of rows into ``[tokens, width]``.  The
    tokens are taken by falling number of slots (``plan.by_load``), a block
    of them at a time, and a block reads a row a token for as many slots as
    its first token has, so the rows read follow the load and not ``tokens x
    min(k, held)``; a last gather of ``tokens`` whole rows puts them in the
    tokens' own order.  -> [tokens, width] in ``table``'s type where no
    ``weight`` is given (the sums still float32), else float32."""
    tokens, most = plan.pos.shape
    block = _tokens_a_block(tokens)
    width = table.shape[1]
    tables = (plan.by_load,) if weight is None else (
        plan.by_load, _by_load(plan.rank, weight))

    def one(args):
        def add_slot(j, acc):
            rows = _rows_of(table, jax.lax.dynamic_index_in_dim(
                args[0], j, axis=1, keepdims=False)).astype(jnp.float32)
            if weight is not None:
                rows = rows * jax.lax.dynamic_index_in_dim(args[1], j, axis=1)
            return acc + rows

        slots = jnp.sum(args[0][0] < _room(plan.pos, rows))
        out = jax.lax.fori_loop(0, slots, add_slot,
                                jnp.zeros((block, width), jnp.float32))
        return out if weight is not None else out.astype(table.dtype)

    out = jax.lax.map(one, tuple(t.reshape(-1, block, most)
                                 for t in tables)).reshape(tokens, width)
    # whole rows by index are the gather XLA is good at
    return out[plan.rank]


def _grouped_chunks(plan, rows):
    return -(-jnp.sum(plan.counts) // rows)


def _grouped_walk(plan, rows, body, carry):
    """``body(c, carry)`` for every chunk of the sorted slots."""
    return jax.lax.fori_loop(0, _grouped_chunks(plan, rows), body, carry)


def _room(pos, rows):
    """Rows of the sorted slots' buffer: the most slots the tokens can have
    (``pos`` [T, most a token]), in whole chunks."""
    return -(-pos.shape[0] * pos.shape[1] // rows) * rows


def _tile(rows):
    """Rows a tile of the kernels, for chunks of ``rows``."""
    return next(t for t in (pallas_gmm.TILE_ROWS, 256, 128, 64, 32, 16, 8, 1)
                if rows % t == 0)


def _gmm(visits, tile):
    """The grouped matmul of a chunk's visits.  One form of call for both
    passes: calls of one signature share a trace and a lowering."""
    return functools.partial(pallas_gmm.grouped_matmul, visits=visits,
                             tile_rows=tile)


def _slot_weights(held_w, plan, rows):
    """The routing weight of each token's slots, [T, most] float32, zero
    where a token has fewer."""
    mine = plan.expert[..., None] == jnp.arange(held_w.shape[1])
    return jnp.where(plan.pos < _room(plan.pos, rows), jnp.sum(
        jnp.where(mine, held_w[:, None, :], 0.0), axis=-1), 0.0)


# The walk's two halves and its plan are jitted: a model's layers of one
# shape, and their replay under ``remat``, share ONE trace of each and the
# step's lowering emits each body once and calls it.  Traced in Python under
# flax, ``remat`` and the custom VJP for every layer and pass, the same walk
# cost the cell seconds of set-up (PERF.md section 6, PRs 40 and 41).
@functools.partial(jax.jit, static_argnames=("activation", "rows"))
def _grouped_forward(x, held_w, w_in, w_out, plan, activation, rows):
    """-> (out [T, L] float32, the slots the chunks counted, the rows the
    kernels multiplied)."""
    tile, room = _tile(rows), _room(plan.pos, rows)
    with jax.named_scope("moe_route"):
        w_in_, w_out_ = w_in.astype(x.dtype), w_out.astype(x.dtype)

    def body(c, carry):
        ys, done, computed = carry
        with jax.named_scope("moe_route"):
            _, token, sizes, n = _sorted_chunk(plan, c, rows, x.shape[0])
            visits = pallas_gmm.visit_plan(sizes, rows, tile)
            x_rows = _rows_of(x, token)
        gmm = _gmm(visits, tile)
        with jax.named_scope("moe_experts"):
            hidden = gmm(x_rows, w_in_)
            y = gmm(activation(hidden), w_out_)
        with jax.named_scope("moe_route"):
            return (jax.lax.dynamic_update_slice_in_dim(ys, y, c * rows, 0),
                    done + n, computed + visits.count * tile)

    with jax.named_scope("moe_route"):
        slots = pallas_gmm.unwritten((room, x.shape[1]), x.dtype, after=x)
    ys, done, computed = _grouped_walk(plan, rows, body, (
        slots, jnp.int32(0), jnp.int32(0)))
    with jax.named_scope("moe_route"):
        out = _sum_of_slots(ys, plan, rows,
                            _slot_weights(held_w, plan, rows))
    return out, done, computed


@functools.partial(jax.jit, static_argnames=("activation", "rows"))
def _grouped_backward(x, held_w, w_in, w_out, plan, d_out, activation, rows):
    """The chunks again: a chunk's rows and hidden are recomputed, its
    cotangents gathered in sorted order, the matrices' gradients summed in
    float32 by the transposed grouped matmul, and ``d_rows`` and the slots'
    weights' gradients written to their sorted places; then every token
    gathers its own.  -> the gradients of ``x``, ``held_w``, ``w_in`` and
    ``w_out``."""
    pos, expert = plan.pos, plan.expert
    tile, room = _tile(rows), _room(pos, rows)
    with jax.named_scope("moe_route"):
        w_in_, w_out_ = w_in.astype(x.dtype), w_out.astype(x.dtype)
        pair_w = held_w.T.reshape(-1)
        # every slot's place in the first two is written before it is read
        sums = (pallas_gmm.unwritten((room, x.shape[1]), x.dtype,
                                     after=d_out),
                jnp.zeros(room, jnp.float32),
                jnp.zeros(w_in.shape, jnp.float32),
                jnp.zeros(w_out.shape, jnp.float32))

    def body(c, grads):
        d_xs, d_ws, d_w_in, d_w_out = grads
        with jax.named_scope("moe_route"):
            pair, token, sizes, _ = _sorted_chunk(plan, c, rows, x.shape[0])
            visits = pallas_gmm.visit_plan(sizes, rows, tile)
            x_rows = _rows_of(x, token)
            d_y = _rows_of(d_out, token)
            weight = _rows_of(pair_w, pair)
        gmm = _gmm(visits, tile)
        with jax.named_scope("moe_experts"):
            hidden = gmm(x_rows, w_in_)
            act, transpose = jax.vjp(activation, hidden)
            y = gmm(act, w_out_)
        with jax.named_scope("moe_route"):
            d_weight = jnp.sum(d_y * y.astype(jnp.float32), axis=-1)
            d_y = (d_y * weight[:, None]).astype(y.dtype)
        with jax.named_scope("moe_experts"):
            d_hidden, = transpose(gmm(d_y, w_out_, transpose_rhs=True))
            d_rows = gmm(d_hidden, w_in_, transpose_rhs=True)
            d_w_in = pallas_gmm.grouped_outer(x_rows, d_hidden, visits,
                                              d_w_in, tile_rows=tile)
            d_w_out = pallas_gmm.grouped_outer(act, d_y, visits, d_w_out,
                                               tile_rows=tile)
        with jax.named_scope("moe_route"):
            at = c * rows
            return (jax.lax.dynamic_update_slice_in_dim(d_xs, d_rows, at, 0),
                    jax.lax.dynamic_update_slice_in_dim(d_ws, d_weight, at, 0),
                    d_w_in, d_w_out)

    d_xs, d_ws, d_w_in, d_w_out = _grouped_walk(plan, rows, body, sums)
    with jax.named_scope("moe_route"):
        d_w_in, d_w_out = d_w_in.astype(w_in.dtype), d_w_out.astype(
            w_out.dtype)
        d_x = _sum_of_slots(d_xs, plan, rows)
        # a slot's weight is its token's weight for the slot's expert; a
        # pair nobody chose has no slot and no gradient
        d_slot = _rows_of(d_ws, pos)
        d_held_w = jnp.sum(jnp.where(
            expert[..., None] == jnp.arange(held_w.shape[1]),
            d_slot[..., None], 0.0), axis=1)
    return d_x, d_held_w.astype(held_w.dtype), d_w_in, d_w_out


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _grouped(x, held_w, w_in, w_out, plan, activation, rows):
    return _grouped_forward(x, held_w, w_in, w_out, plan, activation, rows)


def _grouped_fwd(x, held_w, w_in, w_out, plan, activation, rows):
    """Keeps the inputs and the plan, and nothing of a chunk."""
    return (_grouped_forward(x, held_w, w_in, w_out, plan, activation, rows),
            (x, held_w, w_in, w_out, plan))


def _grouped_bwd(activation, rows, kept, cotangents):
    no_gradient = jax.tree_util.tree_map(
        lambda a: np.zeros(jnp.shape(a), jax.dtypes.float0), kept[-1])
    return _grouped_backward(*kept, cotangents[0], activation, rows) + (
        no_gradient,)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


@functools.partial(jax.jit, static_argnames=("rows", "per_token"))
def _grouped_plan(is_chosen, rows, per_token):
    """What the grouped walk needs of the routing, once a layer and step
    (``PLAN_SAVED_BY_REMAT``): the sorted pairs and the counts
    (``slot_plan``); for every token the sorted positions of its own slots
    and their experts, [T, per_token], by expert, a place past the slots'
    buffer (``_room``) where the token has fewer; each token's place among
    the tokens by falling number of slots, and the positions in that order.
    Every sort of the walk but one is here (a sort is the TPU's way to
    permute narrow rows, and a megabyte or two of the step's executable)."""
    T, held = is_chosen.shape
    order, counts = slot_plan(is_chosen)
    room = -(-T * per_token // rows) * rows
    # the sorted order IS the pairs' own order, so a pair's place is the
    # number of chosen pairs before it
    place = jnp.cumsum(is_chosen.T.reshape(-1).astype(jnp.int32)) - 1
    place = jnp.where(is_chosen, place.reshape(held, T).T, room)
    pos, expert = jax.lax.sort(
        (place, jnp.broadcast_to(jnp.arange(held, dtype=jnp.int32),
                                 place.shape)), dimension=1, num_keys=1)
    pos, expert = pos[:, :per_token], expert[:, :per_token]
    # a token has 0 .. held slots, so its place by falling number of slots
    # (tokens of one number in their own order) is a count, not a sort: the
    # tokens with more slots, and the earlier ones with as many
    slots = jnp.sum(is_chosen.astype(jnp.int32), axis=1)
    same = (slots[None, :] == jnp.arange(held, -1, -1, dtype=jnp.int32)[
        :, None]).astype(jnp.int32)                     # [held + 1, T]
    earlier = jnp.cumsum(same, axis=1) - same
    as_many = jnp.sum(same, axis=1)
    more = jnp.cumsum(as_many) - as_many
    rank = jnp.sum(same * (earlier + more[:, None]), axis=0)
    return _Plan(
        jnp.concatenate([order, jnp.full(rows, held * T, jnp.int32)]),
        counts, pos, expert, rank, _by_load(rank, pos))


def routed_experts(x, held_w, is_chosen, w_in, w_out, activation=relu2,
                   rows=None, grouped=False, per_token=None):
    """``sum_e held_w[t, e] * act(x[t] @ w_in[e]) @ w_out[e]`` over the
    experts held here, for the chosen (token, expert) pairs only.

    ``x`` [T, L] tokens (in the experts' own width), ``held_w`` / ``is_chosen``
    [T, held] from ``held_weights``, ``w_in`` [held, L, F] (``[held, L, 2 F]``
    for a gated ``activation``), ``w_out`` [held, F, L]; ``rows`` slots a
    chunk (``ROWS_PER_CHUNK`` unless given): one expert's, or with
    ``grouped`` sorted slots of any experts through the grouped matmul
    (whole tiles of its rows; ``per_token`` is the most slots a token can
    have here, ``min(k, held)``, all the held experts unless given): the
    two forms of the module docstring, the same sums either way
    -> (out [T, L] float32, counters: ``slots`` routed here, ``done``
    slots the walk's chunks counted as they computed them, ``counts`` [held]
    per expert and, of the grouped form, ``computed``: the rows its kernels
    multiplied, straddled and partly filled tiles included).  ``slots -
    done`` is what was dropped: zero, because the walk is as long as the
    slots need.  The backward pass walks the chunks again (a custom VJP): a
    chunk's rows live only while it is computed, forward and backward."""
    T, held = held_w.shape
    count_kernel_path("grouped_matmul", "pallas" if grouped else "slots")
    if grouped:
        rows = rows or ROWS_PER_GROUPED_CHUNK
        with jax.named_scope("moe_route"):
            plan = _Plan(*(checkpoint_name(a, PLAN_SAVED_BY_REMAT)
                           for a in _grouped_plan(
                               is_chosen, rows, min(per_token or held, held))))
        out, done, computed = _grouped(x, held_w, w_in, w_out, plan,
                                       activation, rows)
        return out, {"slots": jnp.sum(plan.counts), "done": done,
                     "counts": plan.counts, "computed": computed}
    rows = min(rows or ROWS_PER_CHUNK, T)
    with jax.named_scope("moe_route"):
        order, counts = slot_plan(is_chosen)
        # a chunk's rows are read ``rows`` at a time from any slot on
        plan = (jnp.concatenate(
            [order, jnp.full(rows, held * T, jnp.int32)]), counts)
        pair_w = held_w.T.reshape(-1)            # as the plan counts pairs
    out, done = _routed(x, pair_w, w_in, w_out, plan, activation, rows,
                        _Slots)
    return out, {"slots": jnp.sum(counts), "done": done, "counts": counts}


def load_counters(per_layer):
    """What a step says of its expert layers, from each layer's
    ``routed_experts`` counters: the mean number of slots a layer held, the
    fullest held expert over the mean one (the largest over the layers), the
    slots dropped (zero) and, where every layer walked the grouped form, the
    mean number of rows a layer's kernels multiplied (over
    ``moe_slots_held``: what straddled and partly filled tiles cost)."""
    slots = jnp.stack([c["slots"] for c in per_layer]).astype(jnp.float32)
    done = jnp.stack([c["done"] for c in per_layer]).astype(jnp.float32)
    counts = jnp.stack([c["counts"] for c in per_layer]).astype(jnp.float32)
    skew = jnp.max(counts, axis=1) / jnp.maximum(jnp.mean(counts, axis=1),
                                                 1.0)
    told = {"moe_slots_held": jnp.mean(slots),
            "moe_load_max_over_mean": jnp.max(skew),
            "moe_slots_dropped": jnp.sum(slots - done)}
    if all("computed" in c for c in per_layer):
        told["moe_rows_computed"] = jnp.mean(jnp.stack(
            [c["computed"] for c in per_layer]).astype(jnp.float32))
    return told


def dropless_moe(x, logits, w_in, w_out, *, k, first_expert, experts_held,
                 selection_bias=None, normalize=True, scale=1.0,
                 scoring=sigmoid_topk, activation=relu2):
    """Route ``x`` [T, L] by ``logits`` [T, E] over all E experts and return
    what the experts ``[first_expert, first_expert + experts_held)`` give:
    (out [T, L] float32, counters, chosen-here mask [T, held]).  ``scoring``
    (``sigmoid_topk`` | ``softmax_topk``) and an expert's ``activation``
    (``relu2`` | ``gated_silu`` on a fused gate | up ``w_in``) are the
    layer's own; the walk is one."""
    with jax.named_scope("moe_route"):
        chosen, weights = scoring(logits, k, selection_bias, normalize, scale)
        held_w, is_chosen = held_weights(chosen, weights, first_expert,
                                         experts_held)
    grouped, rows = walk_form(
        x.shape[0], k, logits.shape[-1],
        (x.shape[-1], w_in.shape[-1], w_out.shape[-2]), experts_held)
    out, counters = routed_experts(x, held_w, is_chosen, w_in, w_out,
                                   activation, rows, grouped,
                                   min(k, experts_held))
    return out, counters, is_chosen


def sequence_balance(logits, chosen, seqs):
    """DeepSeek-V3's sequence-wise balance term (its section 2.1.2, before
    the coefficient ``alpha``), a reduction over what the router computed:
    ``logits`` [T, E] float32 of ``seqs`` sequences of equal length one
    after the other and ``chosen`` [T, k], the experts each token took ->
    the mean over the sequences of ``sum_i f_i P_i`` over ALL E experts,
    with ``s' = sigmoid(logits) / sum_j sigmoid(logits)_j``, ``P_i`` the
    sequence's mean of ``s'[t, i]`` and ``f_i = E / (k T_seq) x`` the
    sequence's tokens that took expert i (a count: no gradient)."""
    tokens, experts = logits.shape
    k, length = chosen.shape[-1], tokens // seqs
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    share = scores / jnp.sum(scores, axis=-1, keepdims=True)
    took = jnp.sum(chosen[..., None] == jnp.arange(experts), axis=1)
    per_seq = lambda t: jnp.sum(  # noqa: E731
        t.reshape(seqs, length, experts).astype(jnp.float32), axis=1)
    f = jax.lax.stop_gradient(per_seq(took)) * (experts / (k * length))
    return jnp.mean(jnp.sum(f * per_seq(share) / length, axis=-1))

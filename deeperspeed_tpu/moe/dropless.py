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

The walk is one loop, one expert body and one custom VJP; what differs by
the shapes alone (``walk_form``) is how a chunk finds its rows.  By *slots*
(above) a chunk is some of ONE expert's slots, read and added to by index,
and the walk is as long as the load: the way of a lightly loaded share (a few
per cent of the (token, held expert) pairs chosen).  By *blocks* a chunk is
``rows`` consecutive tokens against one held expert, ALL the block's tokens
with weight zero where the token did not choose the expert, so its rows are
slices and its results are added in place, with no gather, no scatter-add and
no sort, at the price of the unchosen pairs' matmuls; the walk is ``held x
tokens / rows`` chunks whatever the load, and its time does not move with
it.  On a v5e a scatter-add of a chunk's rows costs a pass over the whole
``[tokens, width]`` float32 table it adds to plus 0.63 us a row (1.55 ms for
1024 rows into [32768, 2304]), twice a chunk; both ways are measured at both
models' shapes beside ``BLOCKS_FROM_SHARE`` (PERF.md, PR 38).

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

import jax
import jax.numpy as jnp
import numpy as np

#: Slots a chunk of the routed walk, all of one expert.  An expert with a
#: few slots costs a chunk all the same, and a chunk costs by its rows: on a
#: v5e, 16,384 tokens in a 1024-wide latent, forward and backward, a chunk
#: takes 0.42 / 0.72 / 0.9 ms at 256 / 512 / 1024 rows, most of it the two
#: scatter-adds of its rows (0.5 us a row; the matmuls are a tenth), so 8
#: experts with 40 slots each cost 3.4 / 5.9 / 7.3 ms and 8 with 704 each
#: 9.7 / 11.5 / 7.3 ms (PERF.md, PR 34).
ROWS_PER_CHUNK = 256
#: Tokens a block, where a chunk of the walk is a block of consecutive tokens
#: against one held expert, and the share of the (token, held expert) pairs
#: that even routing must choose (``k / num_experts``) for a layer's walk to
#: go by blocks.  Both ways measured on a v5e at both models' shapes, forward
#: and backward, ms a layer (PERF.md, PR 38).  Mellum's layer (32,768 tokens
#: of 2,304 floats, 16 gated experts of 896): by blocks 275 / 225 / 199 at
#: 512 / 1024 / 2048 tokens a block, at any load; by slots, 1024 a chunk,
#: 132 at 6.25 % of the pairs chosen and 241 at Mellum's own 12.5 % (top-8 of
#: 64): they cross at 10 %.  The hybrid model's layer (16,384 tokens of 1,024
#: floats, 8 relu2 experts of 2,688): by blocks 43.6 at any load; by slots,
#: 256 a chunk, 4.8 at 0.24 % and 11.2 at its own 4.3 % (top-22 of 512):
#: they would cross near 25 %.
ROWS_PER_BLOCK = 2048
BLOCKS_FROM_SHARE = 1 / 10


def walk_form(tokens, k, num_experts):
    """-> (whether the walk goes by blocks of tokens, rows a chunk), from
    the shapes alone: blocks where even routing chooses a tenth or more of
    the pairs and the tokens divide into blocks; else an expert's slots,
    ``ROWS_PER_CHUNK`` a chunk."""
    if k / num_experts >= BLOCKS_FROM_SHARE:
        rows = next((r for r in (ROWS_PER_BLOCK, 1024, 512, 256, 128)
                     if tokens % r == 0), tokens)
        if rows <= ROWS_PER_BLOCK:
            return True, rows
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


def _block_chunk(plan, c, rows, tokens):
    """Chunk ``c`` of the walk by blocks -> (its expert, the first of its
    pairs in the ``[held * tokens]`` pair order, its first token, how many
    of its tokens chose the expert): expert by expert, each expert's blocks
    in token order, so that an expert's matrices stay put while its gradient
    adds up."""
    is_slot, _ = plan
    e, j = c // (tokens // rows), c % (tokens // rows)
    pair = e * tokens + j * rows
    return e, pair, j * rows, jnp.sum(
        jax.lax.dynamic_slice_in_dim(is_slot, pair, rows))


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


class _Blocks:
    """A chunk is ``rows`` consecutive tokens against one held expert: its
    rows are a slice from ``lo`` on, added to in place.  ``held * tokens /
    rows`` chunks whatever the load."""
    chunk = staticmethod(_block_chunk)

    @staticmethod
    def chunks(plan, rows, tokens, held):
        return held * (tokens // rows)

    @staticmethod
    def read(table, lo, rows):
        return jax.lax.dynamic_slice_in_dim(table, lo, rows, axis=0)

    @staticmethod
    def add(table, lo, values):
        there = _Blocks.read(table, lo, values.shape[0])
        return jax.lax.dynamic_update_slice_in_dim(
            table, there + values.astype(table.dtype), lo, axis=0)


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


def routed_experts(x, held_w, is_chosen, w_in, w_out, activation=relu2,
                   rows=None, blocks=False):
    """``sum_e held_w[t, e] * act(x[t] @ w_in[e]) @ w_out[e]`` over the
    experts held here, for the chosen (token, expert) pairs only.

    ``x`` [T, L] tokens (in the experts' own width), ``held_w`` / ``is_chosen``
    [T, held] from ``held_weights``, ``w_in`` [held, L, F] (``[held, L, 2 F]``
    for a gated ``activation``), ``w_out`` [held, F, L]; ``rows`` slots a
    chunk (``ROWS_PER_CHUNK`` unless given), or with ``blocks`` the tokens
    of a block (which must divide the tokens): the two ways the one walk
    finds a chunk's rows (the module docstring), the same sums either way
    -> (out [T, L] float32, counters: ``slots`` routed here, ``done``
    slots the walk's chunks counted as they computed them, ``counts`` [held]
    per expert).  ``slots - done`` is what was dropped: zero, because the
    walk is as long as the slots need.  The backward pass walks the chunks
    again (a custom VJP): a chunk's rows live only while it is computed,
    forward and backward."""
    T, held = held_w.shape
    rows = min(rows or ROWS_PER_CHUNK, T)
    with jax.named_scope("moe_route"):
        if blocks:
            counts = jnp.sum(is_chosen.astype(jnp.int32), axis=0)
            plan = (is_chosen.T.reshape(-1).astype(jnp.int32), counts)
        else:
            order, counts = slot_plan(is_chosen)
            # a chunk's rows are read ``rows`` at a time from any slot on
            plan = (jnp.concatenate(
                [order, jnp.full(rows, held * T, jnp.int32)]), counts)
        pair_w = held_w.T.reshape(-1)            # as the plan counts pairs
    out, done = _routed(x, pair_w, w_in, w_out, plan, activation, rows,
                        _Blocks if blocks else _Slots)
    return out, {"slots": jnp.sum(counts), "done": done, "counts": counts}


def load_counters(per_layer):
    """What a step says of its expert layers, from each layer's
    ``routed_experts`` counters: the mean number of slots a layer held, the
    fullest held expert over the mean one (the largest over the layers), and
    the slots dropped (zero)."""
    slots = jnp.stack([c["slots"] for c in per_layer]).astype(jnp.float32)
    done = jnp.stack([c["done"] for c in per_layer]).astype(jnp.float32)
    counts = jnp.stack([c["counts"] for c in per_layer]).astype(jnp.float32)
    skew = jnp.max(counts, axis=1) / jnp.maximum(jnp.mean(counts, axis=1),
                                                 1.0)
    return {"moe_slots_held": jnp.mean(slots),
            "moe_load_max_over_mean": jnp.max(skew),
            "moe_slots_dropped": jnp.sum(slots - done)}


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
    blocks, rows = walk_form(x.shape[0], k, logits.shape[-1])
    out, counters = routed_experts(x, held_w, is_chosen, w_in, w_out,
                                   activation, rows, blocks)
    return out, counters, is_chosen

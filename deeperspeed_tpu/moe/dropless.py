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

Scoring is DeepSeek-V3's, as the Nemotron-H family uses it: ``sigmoid`` of
float32 logits, the choice by ``score + selection_bias``, the weights the
chosen scores themselves, normalised over the chosen and scaled.
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


def _chunk_rows(plan, c, rows, tokens):
    """Chunk ``c`` of the walk -> (its expert, the (expert, token) pair and
    the token of each of its rows, how many of its rows are slots).  Rows
    that are no slots point past the arrays' ends, each at a place of its
    own, so a chunk's indices are all different and ascending."""
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


def _gather(table, index):
    """``table[index]``, zero where the index points past the end."""
    return table.at[index].get(mode="fill", fill_value=0,
                               **_ONE_EXPERTS_TOKENS)


def _add_at(table, index, rows):
    """``table[index] += rows``; a row whose index points past the end is
    dropped."""
    return table.at[index].add(rows.astype(table.dtype), mode="drop",
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


def _walk(plan, rows, body, carry):
    """``body(c, carry)`` for every chunk the slots routed here need."""
    n_chunks = jnp.sum(-(-plan[1] // rows))
    return jax.lax.fori_loop(0, n_chunks, body, carry)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _routed(x, pair_w, w_in, w_out, plan, activation, rows):
    return _routed_fwd(x, pair_w, w_in, w_out, plan, activation, rows)[0]


def _routed_fwd(x, pair_w, w_in, w_out, plan, activation, rows):
    def body(c, carry):
        out, done = carry
        with jax.named_scope("moe_route"):
            e, pair, token, n = _chunk_rows(plan, c, rows, x.shape[0])
            x_rows, weight = _gather(x, token), _gather(pair_w, pair)
        y = _expert(x_rows, weight, w_in[e], w_out[e], activation)
        with jax.named_scope("moe_route"):
            return _add_at(out, token, y), done + n

    out, done = _walk(plan, rows, body,
                      (jnp.zeros(x.shape, jnp.float32), jnp.int32(0)))
    return (out, done), (x, pair_w, w_in, w_out, plan)


def _routed_bwd(activation, rows, kept, cotangents):
    """The walk again, chunk by chunk: each chunk's rows are recomputed and
    transposed, and the gradients add up in float32 where they belong: a
    token's row, a pair's weight, the one expert's matrices.  Nothing of a
    chunk outlives it, in either direction."""
    x, pair_w, w_in, w_out, plan = kept
    d_out = cotangents[0]

    def body(c, grads):
        d_x, d_pair_w, d_w_in, d_w_out = grads
        with jax.named_scope("moe_route"):
            e, pair, token, _ = _chunk_rows(plan, c, rows, x.shape[0])
            x_rows, weight = _gather(x, token), _gather(pair_w, pair)
            d_y = _gather(d_out, token)
        _, transpose = jax.vjp(
            lambda *ops: _expert(*ops, activation),
            x_rows, weight, w_in[e], w_out[e])
        d_rows, d_weight, d_in, d_out_e = transpose(d_y)
        with jax.named_scope("moe_route"):
            return (_add_at(d_x, token, d_rows),
                    _add_at(d_pair_w, pair, d_weight),
                    _add_to_expert(d_w_in, e, d_in),
                    _add_to_expert(d_w_out, e, d_out_e))

    operands = (x, pair_w, w_in, w_out)
    grads = _walk(plan, rows, body, tuple(
        jnp.zeros(op.shape, jnp.float32) for op in operands))
    no_gradient = jax.tree_util.tree_map(
        lambda a: np.zeros(a.shape, jax.dtypes.float0), plan)
    return tuple(g.astype(op.dtype) for g, op in zip(grads, operands)) + (
        no_gradient,)


_routed.defvjp(_routed_fwd, _routed_bwd)


def routed_experts(x, held_w, is_chosen, w_in, w_out, activation=relu2):
    """``sum_e held_w[t, e] * act(x[t] @ w_in[e]) @ w_out[e]`` over the
    experts held here, for the chosen (token, expert) pairs only.

    ``x`` [T, L] tokens (in the experts' own width), ``held_w`` / ``is_chosen``
    [T, held] from ``held_weights``, ``w_in`` [held, L, F], ``w_out`` [held,
    F, L] -> (out [T, L] float32, counters: ``slots`` routed here, ``done``
    slots computed, ``counts`` [held] per expert).  ``slots - done`` is what
    was dropped: zero, because the walk is as long as the slots need.  The
    backward pass walks the chunks again (a custom VJP): a chunk's rows live
    only while it is computed, forward and backward."""
    T, held = held_w.shape
    rows = min(ROWS_PER_CHUNK, T)
    with jax.named_scope("moe_route"):
        order, counts = slot_plan(is_chosen)
        # a chunk's rows are read ``rows`` at a time from any slot on
        plan = (jnp.concatenate([order, jnp.full(rows, held * T, jnp.int32)]),
                counts)
        pair_w = held_w.T.reshape(-1)            # as ``order`` counts pairs
    out, done = _routed(x, pair_w, w_in, w_out, plan, activation, rows)
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
                 selection_bias=None, normalize=True, scale=1.0):
    """Route ``x`` [T, L] by ``logits`` [T, E] over all E experts and return
    what the experts ``[first_expert, first_expert + experts_held)`` give:
    (out [T, L] float32, counters, chosen-here mask [T, held])."""
    with jax.named_scope("moe_route"):
        chosen, weights = sigmoid_topk(logits, k, selection_bias, normalize,
                                       scale)
        held_w, is_chosen = held_weights(chosen, weights, first_expert,
                                         experts_held)
    out, counters = routed_experts(x, held_w, is_chosen, w_in, w_out)
    return out, counters, is_chosen

"""Chunked fused-linear cross entropy: the head GEMM and the cross entropy
over chunks of tokens inside one scan, so that the ``[tokens, vocab]`` logits
never exist whole (at 8 x 2048 tokens and 50k rows they are 3.3 GB in
float32; four exits of a looped model would be four times that).  Only a
chunk's ``[C, V]`` logits are live.

Two forms share the chunk body.  A training loss is a weighted sum of the
tokens' log-probabilities whose weights are known before the head runs
(``-mask / count``: ``mean_linear_cross_entropy``; a looped model's
``-p * mask / count``), so
``weighted_linear_cross_entropy`` makes the gradient in the walk that
makes the logits: three head GEMMs a chunk (logits, ``d_x``, ``d_w``), and a
backward pass that only scales what the forward pass left.  The per-token
form cannot know a token's cotangent before the backward pass and recomputes
the logits there (``jax.checkpoint``: a fourth GEMM); it is for forward-only
users (a model's ``logprobs`` / ``exits``).

A head that predicts several tokens a position (a byte model's ``K`` slices
of one matrix, slice ``i`` for the token ``i + 1`` ahead) goes the same
walk, ``multi_label_linear_cross_entropy``: ONE product of ``K * V`` columns
a chunk, ``K`` softmaxes of ``V`` over it, float32 from the product's
accumulator on.
"""

import functools

import jax
import jax.numpy as jnp

from ...telemetry.trace import count_kernel_path


def _in_chunks(chunk_tokens, x, *per_token):
    """``x`` [T, H] and arrays [T] or [T, K] cut to ``[n, C, H]`` and
    ``[n, C]`` or ``[n, C, K]``, the tail chunk padded with zeros."""
    T = x.shape[0]
    C = min(int(chunk_tokens), T)
    n_chunks = -(-T // C)
    pad = n_chunks * C - T
    if pad:
        x, *per_token = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                         for a in (x, *per_token))
    return tuple(a.reshape(n_chunks, C, *a.shape[1:])
                 for a in (x, *per_token))


def _chunk_logits(xc, w, lc):
    """A chunk's float32 logits, which of them are the labels', their
    logsumexp and the labels' log-probabilities: the GEMM in ``xc``'s dtype.
    The label's logit is a masked sum, not a gather: a gather wants the
    float32 logits written out whole, the sum rides the pass that reads them
    for the logsumexp."""
    logits = (xc @ w).astype(jnp.float32)
    onehot = jnp.arange(w.shape[1]) == lc[:, None]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    return logits, onehot, lse, gold - lse


def chunked_linear_cross_entropy(x, w, labels, chunk_tokens):
    """Log-probability of ``labels`` [T] under ``softmax(x @ w)`` for hidden
    states ``x`` [T, H] and a head ``w`` [H, V], ``chunk_tokens`` tokens at a
    time (the tail chunk is padded; ``labels`` pads with 0) -> the per-token
    values [T], float32.

    For forward-only users.  A training loss should not differentiate it:
    ``jax.checkpoint`` recomputes each chunk's logits in the backward pass,
    one head GEMM more than ``weighted_linear_cross_entropy`` needs."""
    count_kernel_path("head_ce", "per_token")
    T = x.shape[0]
    chunks = _in_chunks(chunk_tokens, x, labels)

    def chunk(_, op):
        return None, _chunk_logits(op[0], w.astype(x.dtype), op[1])[3]

    _, token_ll = jax.lax.scan(jax.checkpoint(chunk), None, chunks)
    return token_ll.reshape(-1)[:T]


def weighted_linear_cross_entropy(x, w, labels, weights, chunk_tokens):
    """``sum_t weights[t] * log softmax(x[t] @ w)[labels[t]]`` for hidden
    states ``x`` [T, H], a head ``w`` [H, V], ``labels`` [T] and float32
    ``weights`` [T], ``chunk_tokens`` tokens at a time (the tail chunk pads
    with weight 0) -> (the sum, float32; the chunks the walk ran, int32,
    counted on the device).

    Under differentiation the forward walk makes the gradient too: a chunk's
    ``weights * (onehot - softmax)`` while its logits are there, ``d_x`` from
    it, and ``d_w`` added up over the chunks in float32; the backward pass
    scales them by the cotangent that arrives.  The GEMMs run in ``x``'s
    dtype; ``weights`` gets its gradient (the tokens' log-probabilities)."""
    # float32 in and out of the rule: ``w``'s own cast hands the gradient back
    # in ``w``'s dtype, rounded once, after the sum over the chunks
    return _weighted_sum(x, w.astype(jnp.float32), labels,
                         weights.astype(jnp.float32), int(chunk_tokens))


def mean_linear_cross_entropy(x, w, labels, mask, chunk_tokens):
    """Mean cross entropy of ``labels`` [...] under ``softmax(x @ w)`` for
    ``x`` [..., H] over the tokens a loss ``mask`` [...] keeps (None: all):
    the weighted sum at ``-mask / count``."""
    mask = (jnp.ones(labels.shape, jnp.float32) if mask is None
            else mask.astype(jnp.float32))
    weights = -mask / jnp.maximum(jnp.sum(mask), 1.0)
    return weighted_linear_cross_entropy(
        x.reshape(-1, x.shape[-1]), w, labels.reshape(-1),
        weights.reshape(-1), chunk_tokens)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _weighted_sum(x, w, labels, weights, chunk_tokens):
    chunks = _in_chunks(chunk_tokens, x, labels, weights)
    w = w.astype(x.dtype)

    def chunk(carry, op):
        xc, lc, wc = op
        total, ran = carry
        return (total + jnp.sum(wc * _chunk_logits(xc, w, lc)[3]),
                ran + 1), None

    carry, _ = jax.lax.scan(chunk, (jnp.float32(0.0), jnp.int32(0)), chunks)
    return carry


def _weighted_sum_fwd(x, w, labels, weights, chunk_tokens):
    count_kernel_path("head_ce", "fused")
    T, H = x.shape
    chunks = _in_chunks(chunk_tokens, x, labels, weights)
    w = w.astype(x.dtype)

    def chunk(carry, op):
        xc, lc, wc = op
        total, ran, d_w = carry
        logits, onehot, lse, ll = _chunk_logits(xc, w, lc)
        # the logits' cotangent, cast where autodiff casts it: at the GEMM's
        # float32 result
        d_logits = (wc[:, None] * (onehot - jnp.exp(logits - lse[:, None]))
                    ).astype(xc.dtype)
        d_xc = jax.lax.dot_general(d_logits, w, (((1,), (1,)), ((), ())))
        d_w = d_w + jax.lax.dot_general(
            xc, d_logits, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return (total + jnp.sum(wc * ll), ran + 1, d_w), (d_xc, ll)

    (total, ran, d_w), (d_x, ll) = jax.lax.scan(
        chunk, (jnp.float32(0.0), jnp.int32(0),
                jnp.zeros(w.shape, jnp.float32)), chunks)
    return (total, ran), (d_x.reshape(-1, H)[:T], d_w, ll.reshape(-1)[:T])


def _weighted_sum_bwd(chunk_tokens, residuals, cotangents):
    d_x, d_w, ll = residuals
    g = cotangents[0]           # the chunk count's is float0: nothing
    return (g * d_x).astype(d_x.dtype), g * d_w, None, g * ll


_weighted_sum.defvjp(_weighted_sum_fwd, _weighted_sum_bwd)


# ------------------------------------------------- several labels a token
def _chunk_slices(xc, w, lc):
    """A chunk's float32 logits by slice ``[C, K, V]`` from ONE product of
    ``K * V`` columns (accumulated and left in float32: the logits are never
    rounded to ``xc``'s dtype), which of them are the labels' ``lc`` [C, K],
    every slice's logsumexp and the labels' log-probabilities [C, K]."""
    C, K = lc.shape
    logits = jnp.dot(xc, w, preferred_element_type=jnp.float32).reshape(
        C, K, w.shape[1] // K)
    onehot = jnp.arange(logits.shape[-1]) == lc[..., None]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    return logits, onehot, lse, gold - lse


def multi_label_logprobs(x, w, labels, chunk_tokens):
    """Log-probability of ``labels`` [T, K] under the ``K`` softmaxes of the
    slices of ``x @ w`` (``w`` [H, K * V], slice ``i`` its columns
    ``i V .. (i + 1) V - 1``), ``chunk_tokens`` tokens at a time -> [T, K]
    float32.  For forward-only users, as ``chunked_linear_cross_entropy``."""
    count_kernel_path("head_ce", "per_token_multi_label")
    T = x.shape[0]
    chunks = _in_chunks(chunk_tokens, x, labels)

    def chunk(_, op):
        return None, _chunk_slices(op[0], w.astype(x.dtype), op[1])[3]

    _, token_ll = jax.lax.scan(chunk, None, chunks)
    return token_ll.reshape(-1, labels.shape[1])[:T]


def multi_label_linear_cross_entropy(x, w, labels, weights, chunk_tokens):
    """``sum_{t, i} weights[t, i] * log softmax(slice_i(x[t] @ w))[labels[t,
    i]]`` for hidden states ``x`` [T, H], a head ``w`` [H, K * V] of ``K``
    slices, ``labels`` and float32 ``weights`` [T, K] (0 where a token has no
    such target), ``chunk_tokens`` tokens at a time -> (the sum, float32; the
    chunks the walk ran, int32, counted on the device).

    The fused form: under differentiation the forward walk makes ``d_x`` and
    ``d_w`` while a chunk's ``[C, K * V]`` float32 logits are there (three
    GEMMs a chunk, operands in ``x``'s dtype, the product accumulated in
    float32), and the backward pass scales what it left.  No ``[T, K * V]``
    buffer exists."""
    return _multi_label_sum(x, w.astype(jnp.float32), labels,
                            weights.astype(jnp.float32), int(chunk_tokens))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _multi_label_sum(x, w, labels, weights, chunk_tokens):
    chunks = _in_chunks(chunk_tokens, x, labels, weights)
    w = w.astype(x.dtype)

    def chunk(carry, op):
        xc, lc, wc = op
        total, ran = carry
        return (total + jnp.sum(wc * _chunk_slices(xc, w, lc)[3]),
                ran + 1), None

    carry, _ = jax.lax.scan(chunk, (jnp.float32(0.0), jnp.int32(0)), chunks)
    return carry


def _multi_label_sum_fwd(x, w, labels, weights, chunk_tokens):
    count_kernel_path("head_ce", "fused_multi_label")
    T, H = x.shape
    chunks = _in_chunks(chunk_tokens, x, labels, weights)
    w = w.astype(x.dtype)

    def chunk(carry, op):
        xc, lc, wc = op
        total, ran, d_w = carry
        logits, onehot, lse, ll = _chunk_slices(xc, w, lc)
        d_logits = (wc[..., None] * (onehot - jnp.exp(logits - lse[..., None]))
                    ).reshape(xc.shape[0], -1).astype(xc.dtype)
        d_xc = jax.lax.dot_general(d_logits, w, (((1,), (1,)), ((), ())))
        d_w = d_w + jax.lax.dot_general(
            xc, d_logits, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return (total + jnp.sum(wc * ll), ran + 1, d_w), (d_xc, ll)

    (total, ran, d_w), (d_x, ll) = jax.lax.scan(
        chunk, (jnp.float32(0.0), jnp.int32(0),
                jnp.zeros(w.shape, jnp.float32)), chunks)
    return (total, ran), (d_x.reshape(-1, H)[:T], d_w,
                          ll.reshape(-1, labels.shape[1])[:T])


def _multi_label_sum_bwd(chunk_tokens, residuals, cotangents):
    d_x, d_w, ll = residuals
    g = cotangents[0]
    return (g * d_x).astype(d_x.dtype), g * d_w, None, g * ll


_multi_label_sum.defvjp(_multi_label_sum_fwd, _multi_label_sum_bwd)

"""Chunked fused-linear cross entropy: the head GEMM and the cross entropy
over chunks of tokens inside one scan, so that the ``[tokens, vocab]`` logits
never exist whole (at 8 x 2048 tokens and 50k rows they are 3.3 GB in
float32; four exits of a looped model would be four times that).

Only a chunk's ``[C, V]`` logits are live; ``jax.checkpoint`` recomputes them
in the backward pass, so the residuals kept are the chunk's ``[C, H]``
inputs: about one extra head GEMM for the logits' traffic.  One function
serves both callers: ``GPTNeoX.loss_fn`` folds each chunk into a running
(sum, count) as it always did, a looped model takes the per-token values of
every exit and weights them itself.
"""

import jax
import jax.numpy as jnp


def chunked_linear_cross_entropy(x, w, labels, chunk_tokens, extras=(),
                                 fold=None, init=None):
    """Log-probability of ``labels`` [T] under ``softmax(x @ w)`` for hidden
    states ``x`` [T, H] and a head ``w`` [H, V], ``chunk_tokens`` tokens at a
    time (the tail chunk is padded; ``labels`` pads with 0).

    Without ``fold`` -> the per-token values [T], float32.  With ``fold`` the
    scan carries ``fold(carry, token_ll [C], *extras' chunks)`` from ``init``
    and returns the last carry: ``extras`` are further per-token arrays [T]
    (a loss mask), padded with 0 and chunked alongside.

    The GEMM runs in ``x``'s dtype; the gradient of ``w`` adds up over the
    chunks in ``w``'s own dtype (hand a bfloat16 head over as float32 and the
    sum over the chunks is kept in float32)."""
    T, H = x.shape
    C = min(int(chunk_tokens), T)
    n_chunks = -(-T // C)
    pad = n_chunks * C - T
    per_token = (labels,) + tuple(extras)
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        per_token = tuple(jnp.pad(a, (0, pad)) for a in per_token)
    x = x.reshape(n_chunks, C, H)
    per_token = tuple(a.reshape(n_chunks, C) for a in per_token)

    def chunk(carry, op):
        xc, lc, *ec = op
        logits = (xc @ w.astype(xc.dtype)).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        if fold is None:
            return carry, gold - lse
        return fold(carry, gold - lse, *ec), None

    carry, token_ll = jax.lax.scan(jax.checkpoint(chunk), init,
                                   (x,) + per_token)
    if fold is None:
        return token_ll.reshape(-1)[:T]
    return carry

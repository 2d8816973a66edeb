"""EVA attention (EvaByte's ``attention_class`` "eva"): exact inside a
window, softmax-pooled summaries of the chunks of earlier windows, one
softmax over both.

With ``W`` the window and ``C`` the chunk (``W`` a multiple of ``C``),
position ``t`` lies in window ``w(t) = t // W`` and chunk ``c`` (positions
``cC .. cC + C - 1``) in window ``c // (W / C)``.  Per head, on rotated keys:

* chunk summaries, by learned directions ``mu, phi`` in R^D:
  ``kb_c = sum_{j in c} softmax_{j in c}(mu . k_j) k_j``,
  ``vb_c = sum_{j in c} softmax_{j in c}(phi . k_j) v_j``
  (``chunk_summaries``; the logits unscaled; logits, weights and sums
  float32);
* row ``t`` attends, in ONE softmax at scale ``D^-1/2`` with float32
  statistics, over ``L_t = {j : w(j) = w(t), j <= t}`` (exact keys) and
  ``R_t = {c : window of c < w(t)}`` (the summaries of every earlier window:
  all of a window's or none, and none of the row's own)
  (``eva_attention``).

On a TPU, where the shapes are whole tiles, each is a kernel pair: the
summaries ``pallas_eva_pool.py`` (k and v read once where the projections
left them), the attention ``pallas_eva.py``.  Elsewhere, and for shapes the
kernels do not take, plain forms: the pooling on ``[B, S / C, C, N, D]``
views, the attention a window block at a time (a window's
``[W, W + S / C]`` scores, never a sequence's).  The equations with what a
published config leaves to assumption:
``benchmarks/reference/evabyte_ref.py``.
"""

import functools

import jax
import jax.numpy as jnp

from ...accelerator import get_accelerator
from ...parallel.topology import BATCH_AXES, SP_AXIS, TP_AXIS
from ..pallas_utils import shard_kernel
from . import pallas_eva, pallas_eva_pool

# heads are independent (the directions are a head's own): each shard runs
# its own batch rows and heads over the whole sequence
_HEADS_SPEC = (BATCH_AXES, None, (SP_AXIS, TP_AXIS), None)


def chunk_summaries(k, v, mu, phi, chunk, use_pallas=None):
    """``k, v`` [B, S, N, D] (``k`` rotated), ``mu, phi`` [N, D] -> the
    summaries ``(kb, vb)`` [B, S / chunk, N, D] in ``k``'s dtype; the
    pooling logits, weights and sums float32.  ``use_pallas`` as
    ``eva_attention``'s: None takes the kernels on a TPU where the shapes
    are whole tiles (``pallas_eva_pool.compiles_for_tpu``)."""
    from ...telemetry.trace import count_kernel_path

    B, S, N, D = k.shape
    if S % chunk:
        raise ValueError(f"{S} rows are not whole chunks of {chunk}")
    if use_pallas is None:
        use_pallas = (get_accelerator().use_pallas_kernels()
                      and k.dtype == v.dtype
                      and pallas_eva_pool.compiles_for_tpu(S, chunk, D,
                                                           k.dtype))
    if use_pallas:
        rows, heads = _HEADS_SPEC, _HEADS_SPEC[2:]
        return shard_kernel(
            functools.partial(pallas_eva_pool.pool, chunk=chunk),
            (k, v, mu, phi), (rows, rows, heads, heads), out_like=(0, 0))
    count_kernel_path(pallas_eva_pool.KERNEL_NAME, "plain")
    kc = k.reshape(B, S // chunk, chunk, N, D).astype(jnp.float32)
    vc = v.reshape(B, S // chunk, chunk, N, D).astype(jnp.float32)

    def pooled(direction, values):
        logits = jnp.sum(kc * direction.astype(jnp.float32), axis=-1)
        weights = jax.nn.softmax(logits, axis=2)
        return jnp.sum(weights[..., None] * values, axis=2).astype(k.dtype)

    return pooled(mu, kc), pooled(phi, vc)


def pairs_needed(seq, window, chunk):
    """(row, key) pairs a head's rows see in a length ``seq``: each row the
    keys of its window up to itself and ``W / C`` summaries for every
    earlier window (the last window may be short)."""
    n, rest = divmod(seq, window)
    per = window // chunk
    return (n * window * (window + 1) // 2 + rest * (rest + 1) // 2
            + per * (window * n * (n - 1) // 2 + rest * n))


def pairs_visited(seq, window, chunk, head_dim, use_pallas=None):
    """(row, key) pairs the form ``eva_attention`` takes for these shapes
    computes for one head: the kernel's walk, or the plain form's whole
    blocks (every row of a window against its window and all summaries)."""
    if _takes_kernel(seq, window, chunk, head_dim, use_pallas):
        return pallas_eva.pairs_visited(
            seq, pallas_eva.eva_plan(window, chunk, head_dim))
    padded = -(-seq // window) * window
    return padded * (window + padded // chunk)


def _takes_kernel(seq, window, chunk, head_dim, use_pallas):
    if use_pallas is None:
        return (get_accelerator().use_pallas_kernels()
                and pallas_eva.compiles_for_tpu(seq, window, chunk, head_dim))
    return bool(use_pallas)


def _plain(q, k, v, kb, vb, window, chunk, scale):
    """The equations a window block at a time, scores in float32."""
    B, S, N, D = q.shape
    n = -(-S // window)
    pad = n * window - S
    if pad:     # rows past the end: seen by no real row, cut off below
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (q, k, v))
    qw, kw, vw = (t.reshape(B, n, window, N, D) for t in (q, k, v))
    local = jnp.einsum("bwqnd,bwknd->bwnqk", qw, kw,
                       preferred_element_type=jnp.float32) * scale
    causal = jnp.tril(jnp.ones((window, window), bool))
    local = jnp.where(causal, local, -jnp.inf)
    far = jnp.einsum("bwqnd,bcnd->bwnqc", qw, kb,
                     preferred_element_type=jnp.float32) * scale
    # summary c belongs to window c // (W / C): seen by LATER windows only
    earlier = (jnp.arange(kb.shape[1])[None, :] // (window // chunk)
               < jnp.arange(n)[:, None])
    far = jnp.where(earlier[None, :, None, None, :], far, -jnp.inf)
    weights = jax.nn.softmax(jnp.concatenate([local, far], axis=-1), axis=-1)
    weights = weights.astype(q.dtype)
    out = (jnp.einsum("bwnqk,bwknd->bwqnd", weights[..., :window], vw)
           + jnp.einsum("bwnqc,bcnd->bwqnd", weights[..., window:], vb))
    return out.reshape(B, n * window, N, D)[:, :S]


def eva_attention(q, k, v, kb, vb, window, chunk, scale=None,
                  use_pallas=None):
    """``[B, S, N, D]`` rotated q, k, v and the summaries of
    ``chunk_summaries`` ``[B, S / chunk, N, D]`` -> ``[B, S, N, D]``.
    ``use_pallas`` None: the kernels on a TPU where the shapes are whole
    tiles (``pallas_eva.compiles_for_tpu``), else the plain form; True asks
    for the kernels (off a TPU in interpret mode)."""
    B, S, N, D = q.shape
    if scale is None:
        scale = float(D) ** -0.5
    if _takes_kernel(S, window, chunk, D, use_pallas):
        return shard_kernel(
            functools.partial(pallas_eva.eva_mha, window=window, chunk=chunk,
                              scale=scale), (q, k, v, kb, vb),
            (_HEADS_SPEC,) * 5)
    return _plain(q, k, v, kb, vb, window, chunk, scale)

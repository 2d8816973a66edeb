"""Attention dispatch: Pallas flash kernel on TPU, fused XLA math elsewhere.

Plays the role of the reference's fused attention kernels
(``csrc/transformer/softmax_kernels.cu`` etc. and the Triton
``ops/sparse_attention``), re-expressed for the MXU: one Pallas
flash-attention kernel with online softmax (no [S,S] materialization) when on
TPU, and a jnp reference path that XLA fuses reasonably on CPU for tests.
"""

import functools

import jax
import jax.numpy as jnp

from ...accelerator import get_accelerator
from ...parallel.topology import BATCH_AXES, SP_AXIS, TP_AXIS
from ..pallas_utils import kernel_spec, shard_kernel


def _reference_attention(q, k, v, mask=None, causal=True, scale=None, dropout_rng=None,
                         dropout_rate=0.0, window=None):
    """jnp reference path: [B, S, N, D] q/k/v -> [B, S, N, D]; k and v may
    hold fewer heads than q (grouped-query: repeated here)."""
    *_, seq_q, num_heads, head_dim = q.shape
    seq_k = k.shape[-3]
    if k.shape[-2] != num_heads:
        k, v = (jnp.repeat(t, num_heads // t.shape[-2], axis=-2)
                for t in (k, v))
    if scale is None:
        scale = 1.0 / jnp.sqrt(head_dim).astype(q.dtype)
    # [B, N, Sq, Sk]
    logits = jnp.einsum("bqnd,bknd->bnqk", q, k).astype(jnp.float32) * scale
    if causal:
        causal_mask = jnp.tril(jnp.ones((seq_q, seq_k), bool), k=seq_k - seq_q)
        logits = jnp.where(causal_mask[None, None], logits, jnp.finfo(jnp.float32).min)
    if window is not None:
        # row i sees the columns j with i - window < j (and, causal, j <= i)
        seen = (jnp.arange(seq_k)[None, :] + (seq_q - seq_k)
                > jnp.arange(seq_q)[:, None] - window)
        logits = jnp.where(seen[None, None], logits, jnp.finfo(jnp.float32).min)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = probs * keep / (1.0 - dropout_rate)
    return jnp.einsum("bnqk,bknd->bqnd", probs, v)


def _kv_heads_of(q, k, v):
    """k and v are KV heads of q's query heads: the same batch, length and
    head dim, the query heads a multiple of theirs (grouped-query), which
    the flash kernel takes as they are."""
    return (k.shape == v.shape and q.ndim == k.ndim == 4
            and q.shape[:2] + q.shape[3:] == k.shape[:2] + k.shape[3:]
            and q.shape[2] % k.shape[2] == 0)


def dot_product_attention(q, k, v, mask=None, causal=True, scale=None, dropout_rng=None,
                          dropout_rate=0.0, use_pallas=None, window=None):
    """Multi-head attention over [batch, seq, heads, head_dim] tensors; k
    and v may hold fewer heads than q (grouped-query: query head ``h`` on
    KV head ``h // (heads // kv_heads)``), and are copied nowhere the flash
    kernel can address them by group (``pallas_flash.mha``).
    ``window``: a causal call's sliding window in rows (row i sees the
    columns ``i - window < j <= i``); the flash kernel skips what lies left
    of the band, the plain path masks it."""
    if window is not None and not causal:
        raise ValueError("a window belongs to a causal call")
    if use_pallas is None:
        use_pallas = get_accelerator().use_pallas_kernels()
    if use_pallas and mask is None and dropout_rate == 0.0:
        from .flash import flash_attention, flash_attention_supported

        if flash_attention_supported(q.shape, q.dtype) and _kv_heads_of(q, k, v):
            # each shard attends over the whole sequence for its own batch
            # rows and heads (heads over sp is the Ulysses layout)
            spec = (BATCH_AXES, None, (SP_AXIS, TP_AXIS), None)
            if kernel_spec(spec, k.shape) != kernel_spec(spec, q.shape):
                # the query heads lie over more devices than the KV heads
                # divide into: a shard's query heads take their copies along
                from .pallas_flash import (copy_kv_heads, kernel_name,
                                           tile_plan)

                k, v = copy_kv_heads(k, v, q.shape[2], kernel_name(tile_plan(
                    q.shape[1], q.shape[3], q.dtype, N=q.shape[2],
                    window=window)))
            return shard_kernel(
                functools.partial(flash_attention, causal=causal, scale=scale,
                                  window=window),
                (q, k, v), (spec, spec, spec))
    return _reference_attention(q, k, v, mask=mask, causal=causal, scale=scale,
                                dropout_rng=dropout_rng, dropout_rate=dropout_rate,
                                window=window)


causal_attention = functools.partial(dot_product_attention, causal=True)


def _reference_latent_attention(q_nope, q_rope, k_nope, k_rope, v, scale=None):
    """jnp reference path of ``latent_attention``: the rotary key broadcast
    over the heads by the einsum, the [S, S] scores materialised."""
    seq = q_nope.shape[1]
    if scale is None:
        scale = float(q_nope.shape[-1] + q_rope.shape[-1]) ** -0.5
    logits = (jnp.einsum("bqnd,bknd->bnqk", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqnd,bkd->bnqk", q_rope, k_rope,
                           preferred_element_type=jnp.float32)) * scale
    logits = jnp.where(jnp.tril(jnp.ones((seq, seq), bool))[None, None],
                       logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bnqk,bknd->bqnd", probs, v)


def latent_attention(q_nope, q_rope, k_nope, k_rope, v, scale=None,
                     use_pallas=None):
    """Causal attention whose score is the sum of two products (latent
    attention's training half): ``softmax((q_nope . k_nope + q_rope .
    k_rope) * scale) v`` with ``q_nope``, ``k_nope`` [B, S, N, d_nope],
    ``q_rope`` [B, S, N, d_rope], ``k_rope`` [B, S, d_rope] -- ONE rotary
    key, shared by the heads and copied to none -- and ``v`` [B, S, N, d_v]
    -> [B, S, N, d_v]; ``scale`` defaults to ``(d_nope + d_rope) ** -0.5``.
    The flash kernel of ``pallas_flash_mla`` where it takes the shapes, the
    plain path elsewhere."""
    if use_pallas is None:
        use_pallas = get_accelerator().use_pallas_kernels()
    if use_pallas:
        from .flash import flash_attention_supported, flash_latent_attention

        if flash_attention_supported(q_nope.shape, q_nope.dtype,
                                     rope_dim=q_rope.shape[-1],
                                     v_dim=v.shape[-1]):
            heads = (BATCH_AXES, None, (SP_AXIS, TP_AXIS), None)
            return shard_kernel(
                functools.partial(flash_latent_attention, scale=scale),
                (q_nope, q_rope, k_nope, k_rope, v),
                (heads, heads, heads, (BATCH_AXES, None, None), heads),
                out_like=4)
    from ...telemetry.trace import count_kernel_path

    # which form a traced call took: ``in_place_<heads to a rotary lane
    # block>`` (the kernel's own count) or this
    count_kernel_path("flash_attention_mla", "plain")
    return _reference_latent_attention(q_nope, q_rope, k_nope, k_rope, v,
                                       scale=scale)

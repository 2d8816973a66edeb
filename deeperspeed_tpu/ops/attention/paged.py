"""Paged-KV decode attention (Pallas), fp or int8 block-scaled pools.

TPU-native equivalent of the reference FastGen blocked flash-attention over
a paged KV cache (``inference/v2/kernels/ragged_ops/``): single-token decode
reads ONLY each sequence's live cache blocks.  The block table is a
scalar-prefetch operand, so the grid's ``BlockSpec`` index map dereferences
it directly -- block j of sequence b DMAs pool row ``block_tables[b, j]``
from HBM into VMEM, and dead blocks (beyond the sequence length) are skipped
with ``pl.when``.  This replaces the dense
``pool[block_tables] -> [B, max_blocks*bs, N, D]`` gather the round-1 model
used, which materialized (and masked) the whole padded table per layer.

int8 mode (``kv_cache.dtype: "int8"``): the pools hold int8 values and the
per-(slot, head) fp32 scales ride as additional VMEM operands indexed by the
SAME block-table indirection; dequantization happens inside the online-
softmax block walk (``k = int8 * scale`` right before the score reduce), so
a dequantized fp copy of the cache never exists in HBM -- the fusion that
makes the 2x capacity win free at decode time instead of paying it back as
a dequant pass.

Layout: pool [P, bs, N, D] (as written by the model's scatter), scales
[P, bs, N], q [B, N, D], online softmax per (sequence, head) with the m/l
running stats in VMEM scratch across the block-walk grid dimension.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..pallas_utils import LANES, NEG_INF, interpret_mode


def _decode_kernel(bt_ref, sl_ref, q_ref, k_ref, v_ref, *rest,
                   bs, scale, quantized):
    # Mosaic rejects batched (per-head) dot_generals in-kernel, and decode
    # attention is HBM-bandwidth-bound anyway: everything here is VPU
    # elementwise + reductions -- scores as a masked multiply-reduce over D,
    # context as a p-weighted reduce over the block's tokens.
    if quantized:
        sk_ref, sv_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b, j = pl.program_id(0), pl.program_id(1)
    nj = pl.num_programs(1)
    seq_len = sl_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j * bs < seq_len)
    def _block():
        q = q_ref[0].astype(jnp.float32)            # [N, D]
        k = k_ref[0].astype(jnp.float32)            # [bs, N, D]
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            # fused dequant: one fp32 scale per (slot, head), applied in
            # VMEM inside the walk -- the block's int8 payload came over
            # the HBM wire, the fp expansion never goes back
            k = k * sk_ref[0].astype(jnp.float32)[:, :, None]
            v = v * sv_ref[0].astype(jnp.float32)[:, :, None]
        n = q.shape[0]
        # s[t, n] = sum_d q[n, d] * k[t, n, d]
        s = jnp.sum(k * q[None], axis=2) * scale    # [bs, N]
        t_global = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(t_global < seq_len, s, NEG_INF)
        m_prev = m_scr[:1, :n]                      # [1, N]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)                      # [bs, N]
        alpha = jnp.exp(m_prev - m_new)             # [1, N]
        l_scr[:1, :n] = l_scr[:1, :n] * alpha + jnp.sum(p, axis=0,
                                                        keepdims=True)
        # acc[n, d] = alpha * acc + sum_t p[t, n] * v[t, n, d]
        acc_scr[:] = (acc_scr[:] * alpha[0][:, None]
                      + jnp.sum(p[:, :, None] * v, axis=0))
        m_scr[:1, :n] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        n = acc_scr.shape[0]
        o_ref[0] = (acc_scr[:] / l_scr[:1, :n][0][:, None]).astype(o_ref.dtype)


def _spec_decode_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                        bs, scale, quantized, S):
    # Multi-token variant of ``_decode_kernel`` for speculative rounds: the
    # row carries S = k+1 query tokens (the sequence's last committed token
    # plus k drafts) and every query walks the SAME blocks, so the k-draft
    # verification costs one block-walk, not k.  The S loop is unrolled at
    # trace time (S <= 8); per-query causality comes from the absolute
    # positions rather than one seq_len: query sq attends t <= pos[b, sq].
    if quantized:
        sk_ref, sv_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b, j = pl.program_id(0), pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # positions ascend within a row, so the last query bounds the walk
    @pl.when(j * bs <= pos_ref[b, S - 1])
    def _block():
        q = q_ref[0].astype(jnp.float32)            # [S, N, D]
        k = k_ref[0].astype(jnp.float32)            # [bs, N, D]
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            k = k * sk_ref[0].astype(jnp.float32)[:, :, None]
            v = v * sv_ref[0].astype(jnp.float32)[:, :, None]
        n = q.shape[1]
        for sq in range(S):
            s = jnp.sum(k * q[sq][None], axis=2) * scale    # [bs, N]
            t_global = (j * bs
                        + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
            s = jnp.where(t_global <= pos_ref[b, sq], s, NEG_INF)
            m_prev = m_scr[sq:sq + 1, :n]                   # [1, N]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[sq:sq + 1, :n] = (l_scr[sq:sq + 1, :n] * alpha
                                    + jnp.sum(p, axis=0, keepdims=True))
            acc_scr[sq * n:(sq + 1) * n, :] = (
                acc_scr[sq * n:(sq + 1) * n, :] * alpha[0][:, None]
                + jnp.sum(p[:, :, None] * v, axis=0))
            m_scr[sq:sq + 1, :n] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        n = o_ref.shape[2]
        for sq in range(S):
            o_ref[0, sq] = (acc_scr[sq * n:(sq + 1) * n, :]
                            / l_scr[sq:sq + 1, :n][0][:, None]
                            ).astype(o_ref.dtype)


def _decode_reference(q, pool_k, pool_v, block_tables, seq_lens, scale,
                      k_scale=None, v_scale=None):
    """Vectorized XLA path: gather the table'd blocks densely and mask.

    Same math as the kernel (incl. the int8 dequant); used off-TPU, where
    interpret-mode Pallas executes the grid as a Python loop (~seconds per
    call at serving shapes) while this is one fused XLA program.  The
    kernel-vs-dense parity is pinned by
    ``tests/unit/ops/test_paged_attention.py``, which calls the kernel
    explicitly with ``force_kernel=True``.
    """
    B, N, D = q.shape
    P, bs, _, _ = pool_k.shape
    K = pool_k[block_tables].reshape(B, -1, N, D).astype(jnp.float32)
    V = pool_v[block_tables].reshape(B, -1, N, D).astype(jnp.float32)
    if k_scale is not None:
        K = K * k_scale[block_tables].reshape(B, -1, N)[..., None]
        V = V * v_scale[block_tables].reshape(B, -1, N)[..., None]
    s = jnp.einsum("bnd,btnd->btn", q.astype(jnp.float32), K) * scale
    t = jnp.arange(K.shape[1])
    s = jnp.where((t[None, :] < seq_lens[:, None])[..., None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=1)
    return jnp.einsum("btn,btnd->bnd", p, V).astype(q.dtype)


def _spec_decode_reference(q, pool_k, pool_v, block_tables, positions, scale,
                           k_scale=None, v_scale=None):
    """Dense XLA path for the multi-token walk (same math, same masking)."""
    B, S, N, D = q.shape
    K = pool_k[block_tables].reshape(B, -1, N, D).astype(jnp.float32)
    V = pool_v[block_tables].reshape(B, -1, N, D).astype(jnp.float32)
    if k_scale is not None:
        K = K * k_scale[block_tables].reshape(B, -1, N)[..., None]
        V = V * v_scale[block_tables].reshape(B, -1, N)[..., None]
    s = jnp.einsum("bsnd,btnd->bstn", q.astype(jnp.float32), K) * scale
    t = jnp.arange(K.shape[1])
    mask = t[None, None, :] <= positions[:, :, None]          # [B, S, T]
    s = jnp.where(mask[..., None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=2)
    return jnp.einsum("bstn,btnd->bsnd", p, V).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "force_kernel"))
@jax.named_scope("paged_spec_decode_attention")
def paged_spec_decode_attention(q, pool_k, pool_v, block_tables, positions,
                                scale=None, force_kernel=False,
                                k_scale=None, v_scale=None):
    """Speculative decode: S = k+1 query tokens per row over a blocked pool.

    q            [B, S, N, D]  queries (last committed token + k drafts)
    positions    [B, S] int32  ascending absolute position of each query;
                               query sq attends pool tokens t <= positions[b, sq]
                               (S == 1 with positions = seq_lens - 1 is
                               exactly ``paged_decode_attention``)
    -> [B, S, N, D]
    """
    from jax.experimental.pallas import tpu as pltpu

    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    quantized = k_scale is not None
    B, S, N, D = q.shape
    P, bs, _, _ = pool_k.shape
    max_blocks = block_tables.shape[1]
    if scale is None:
        scale = float(D) ** -0.5
    block_tables = jnp.asarray(block_tables, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    if interpret_mode() and not force_kernel:
        return _spec_decode_reference(q, pool_k, pool_v, block_tables,
                                      positions, float(scale),
                                      k_scale, v_scale)

    pool_spec = pl.BlockSpec((1, bs, N, D),
                             lambda b, j, bt, pos: (bt[b, j], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, S, N, D), lambda b, j, bt, pos: (b, 0, 0, 0)),
        pool_spec,
        pool_spec,
    ]
    operands = [q, pool_k, pool_v]
    if quantized:
        scale_spec = pl.BlockSpec((1, bs, N),
                                  lambda b, j, bt, pos: (bt[b, j], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, S, N, D), lambda b, j, bt, pos: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((S, LANES), jnp.float32),
            pltpu.VMEM((S, LANES), jnp.float32),
            pltpu.VMEM((S * N, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_spec_decode_kernel, bs=bs, scale=float(scale),
                               quantized=quantized, S=S)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, N, D), q.dtype),
        interpret=interpret_mode(),
    )(block_tables, positions, *operands)


@functools.partial(jax.jit, static_argnames=("scale", "force_kernel"))
@jax.named_scope("paged_decode_attention")
def paged_decode_attention(q, pool_k, pool_v, block_tables, seq_lens,
                           scale=None, force_kernel=False,
                           k_scale=None, v_scale=None):
    """One decode step over a blocked KV pool.

    q            [B, N, D]    current-token queries
    pool_k/v     [P, bs, N, D] shared cache pools (fp, or int8 when scales
                               are given)
    block_tables [B, max_blocks] int32 pool-row ids per sequence
    seq_lens     [B] int32    live tokens per sequence (incl. current)
    k_scale/v_scale [P, bs, N] fp32 per-(slot, head) dequant scales for
                               int8 pools (both or neither)
    -> [B, N, D]
    """
    from jax.experimental.pallas import tpu as pltpu

    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    quantized = k_scale is not None
    B, N, D = q.shape
    P, bs, _, _ = pool_k.shape
    max_blocks = block_tables.shape[1]
    if scale is None:
        scale = float(D) ** -0.5
    block_tables = jnp.asarray(block_tables, jnp.int32)
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    if interpret_mode() and not force_kernel:
        return _decode_reference(q, pool_k, pool_v, block_tables, seq_lens,
                                 float(scale), k_scale, v_scale)

    pool_spec = pl.BlockSpec((1, bs, N, D),
                             lambda b, j, bt, sl: (bt[b, j], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, N, D), lambda b, j, bt, sl: (b, 0, 0)),
        pool_spec,
        pool_spec,
    ]
    operands = [q, pool_k, pool_v]
    if quantized:
        # scales fetched through the same block-table indirection -- the
        # "second VMEM operand" of the fused dequant-attend walk
        scale_spec = pl.BlockSpec((1, bs, N),
                                  lambda b, j, bt, sl: (bt[b, j], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, N, D), lambda b, j, bt, sl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((N, LANES), jnp.float32),
            pltpu.VMEM((N, LANES), jnp.float32),
            pltpu.VMEM((N, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, bs=bs, scale=float(scale),
                               quantized=quantized)
    out_dtype = q.dtype
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, N, D), out_dtype),
        interpret=interpret_mode(),
    )(block_tables, seq_lens, *operands)


# --------------------------------------------------------------------------
# Long-context partial attention (inference/v2/longctx.py).
#
# When a sequence's KV no longer fits HBM, attention over it runs as a
# sequence of PARTIAL passes -- one over the blocks still resident in the
# pool, one per segment streamed back from the host tier -- each returning
# unnormalized online-softmax state ``(acc, m, l)`` in fp32 instead of a
# normalized output.  ``combine_attention_partials`` merges any number of
# such triples with the standard running-max rescale, which is exactly the
# cross-block recurrence the Pallas decode kernel runs internally, lifted
# to the host-orchestrated segment walk (T3-style transfer/compute overlap:
# segment s+1's H2D is issued while segment s computes).
#
# These are XLA-level implementations: the segment walk is HBM-bandwidth
# bound on the streamed operand (which just paid a PCIe hop), so there is
# no kernel-fusion win to chase before the transfer itself is hidden.
# --------------------------------------------------------------------------

def _partial_from_scores(s, mask, V):
    """Shared epilogue: masked scores -> unnormalized softmax state.

    s [B, S, N, T] fp32, mask broadcastable to it, V [B, T, N, D] fp32
    -> (acc [B, S, N, D], m [B, S, N], l [B, S, N]), all fp32.  Fully
    masked rows come back as (0, NEG_INF, 0) so they are identity under
    ``combine_attention_partials``.
    """
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=3)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=3)
    acc = jnp.einsum("bsnt,btnd->bsnd", p, V)
    return acc, m, l


@functools.partial(jax.jit, static_argnames=("scale", "rep"))
def paged_partial_attention(q, pool_k, pool_v, block_tables, block_pos,
                            positions, scale=None, k_scale=None,
                            v_scale=None, rep=1):
    """Partial attention over the RESIDENT pool blocks of a long sequence.

    Unlike ``paged_decode_attention`` the table may be PARTIAL: column j of
    ``block_tables`` [B, M] holds a pool row whose *logical* block index is
    ``block_pos[b, j]`` (-1 = dead column), so a 256k-token sequence whose
    cold middle spilled to host presents only its hot prefix + recent
    window here.  Causality comes from global token positions:
    ``block_pos * bs + slot <= positions[b, s]``.

    q [B, S, N, D]; pool_k/v [P, bs, KV, D]; positions [B, S] absolute;
    k_scale/v_scale [P, bs, KV] fp32 (int8/fp8 pools); ``rep`` = N // KV
    repeats GQA KV heads.  Returns fp32 ``(acc, m, l)`` partials.
    """
    B, S, N, D = q.shape
    P, bs, KV, _ = pool_k.shape
    M = block_tables.shape[1]
    if scale is None:
        scale = float(D) ** -0.5
    bt = jnp.asarray(block_tables, jnp.int32)
    bp = jnp.asarray(block_pos, jnp.int32)
    live = bp >= 0
    safe = jnp.where(live, bt, 0)
    K = pool_k[safe].reshape(B, M * bs, KV, D).astype(jnp.float32)
    V = pool_v[safe].reshape(B, M * bs, KV, D).astype(jnp.float32)
    if k_scale is not None:
        K = K * k_scale[safe].reshape(B, M * bs, KV)[..., None]
        V = V * v_scale[safe].reshape(B, M * bs, KV)[..., None]
    if rep > 1:
        K = jnp.repeat(K, rep, axis=2)
        V = jnp.repeat(V, rep, axis=2)
    t_global = (bp[:, :, None] * bs
                + jnp.arange(bs)[None, None, :]).reshape(B, M * bs)
    valid = jnp.broadcast_to(live[:, :, None], (B, M, bs)).reshape(B, M * bs)
    s = jnp.einsum("bsnd,btnd->bsnt", q.astype(jnp.float32), K) * scale
    mask = (valid[:, None, None, :]
            & (t_global[:, None, None, :] <= positions[:, :, None, None]))
    return _partial_from_scores(s, mask, V)


@functools.partial(jax.jit, static_argnames=("scale", "rep"))
def segment_partial_attention(q, k_seg, v_seg, kv_positions, positions,
                              scale=None, k_scale=None, v_scale=None, rep=1):
    """Partial attention over one STREAMED KV segment.

    The segment is a host-tier restore that never enters the pool: KV for
    ``segment_blocks`` spilled blocks, device_put ahead of the walk and
    consumed here as a plain operand.  ``kv_positions`` [B, T] carries each
    slot's global token position (-1 = padding), so segments mask exactly
    like resident blocks and the combined result is position-faithful.

    q [B, S, N, D]; k_seg/v_seg [B, T, KV, D] in the pool's wire dtype;
    k_scale/v_scale [B, T, KV] fp32 when quantized.  Returns fp32
    ``(acc, m, l)`` partials.
    """
    B, S, N, D = q.shape
    if scale is None:
        scale = float(D) ** -0.5
    kp = jnp.asarray(kv_positions, jnp.int32)
    K = k_seg.astype(jnp.float32)
    V = v_seg.astype(jnp.float32)
    if k_scale is not None:
        K = K * k_scale[..., None]
        V = V * v_scale[..., None]
    if rep > 1:
        K = jnp.repeat(K, rep, axis=2)
        V = jnp.repeat(V, rep, axis=2)
    s = jnp.einsum("bsnd,btnd->bsnt", q.astype(jnp.float32), K) * scale
    mask = ((kp >= 0)[:, None, None, :]
            & (kp[:, None, None, :] <= positions[:, :, None, None]))
    return _partial_from_scores(s, mask, V)


def combine_attention_partials(parts, out_dtype=jnp.float32):
    """Merge partial ``(acc, m, l)`` triples into attention output.

    Standard online-softmax combination: rescale every partial by
    ``exp(m_i - max_i m_i)`` and normalize once.  Order-insensitive up to
    fp rounding; empty partials (m = NEG_INF, l = 0) are identities.
    ``parts`` must be non-empty; returns [B, S, N, D] in ``out_dtype``.
    """
    accs, ms, ls = zip(*parts)
    m_tot = functools.reduce(jnp.maximum, ms)
    alphas = [jnp.exp(m - m_tot) for m in ms]
    l_tot = sum(a * l for a, l in zip(alphas, ls))
    acc_tot = sum(a[..., None] * acc for a, acc in zip(alphas, accs))
    denom = jnp.where(l_tot > 0, l_tot, 1.0)
    return (acc_tot / denom[..., None]).astype(out_dtype)

"""In-tree Pallas blocked (flash) attention, forward + backward.

The framework's own MXU attention kernel -- the TPU re-design of the
reference's fused attention/softmax CUDA kernels
(``csrc/transformer/softmax_kernels.cu``, inference ``softmax.cu``): online
softmax over [block_q, block_k] tiles, so no [S, S] score matrix ever
reaches HBM.  FlashAttention-2 style:

* forward saves only O and the per-row logsumexp (LSE);
* backward recomputes P = exp(S - LSE) per tile, seeded by
  ``delta = rowsum(dO * O)``.

At small head dim the kernel is VPU-bound (the fp32 softmax ops on each
[bq, bk] tile outweigh the D-thin matmuls), so the structure minimizes
VPU work per tile (measured on v5e, tools/profile_attn.py):

* q is pre-scaled once outside the kernel (one [B,S,N,D] multiply) instead
  of scaling every [bq, bk] score tile; dq is post-scaled symmetrically;
* interior causal tiles (ki < qi) skip masking entirely -- only diagonal
  tiles pay the iota/compare/select; the padding mask is compiled out
  when S is already a multiple of the block;
* for short k-walks (nk <= _FUSED_DQ_MAX_NK) the backward runs ONE pass:
  the dk/dv grid also emits per-k-tile dq partials (summed outside),
  skipping the second s/exp recompute pass of the classic two-pass bwd.

Arbitrary sequence lengths are handled by padding S up to the 128-lane tile
and masking padded *columns* out of the softmax (padded rows cost dead FLOPs
but keep >=1 valid column, so no NaNs; their dO is zero so they contribute
nothing to dK/dV).  LSE is stored lane-replicated ([BN, S, 128] fp32) --
the upstream TPU kernel's idiom -- so the backward reads it as a
sublane-aligned column with no relayout.

The causal structure skips whole k-tiles above the diagonal in all passes
(the 2x FLOP win dense masking forfeits).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..pallas_utils import LANES, NEG_INF, interpret_mode

# bwd fuses dq into the dk/dv pass (dq partials in HBM) up to this k-walk
# length; beyond it the partials' memory (nk * |dq|) outgrows the saved
# recompute and the classic two-pass bwd takes over
_FUSED_DQ_MAX_NK = 4


def _mask(s, qi, ki, bq, bk, s_valid, causal):
    """Validity mask (pad + causal) for a [bq, bk] score tile; used by the
    sparse-attention kernels which mask every live tile."""
    return _tile_mask(s, qi, ki, bq, bk, s_valid, causal, pad=True)


def _tile_mask(s, qi, ki, bq, bk, s_valid, causal, pad):
    if not causal and not pad:
        return s
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if causal and pad:
        valid = jnp.logical_and(cols < s_valid, cols <= rows)
    elif causal:
        valid = cols <= rows
    else:
        valid = cols < s_valid
    return jnp.where(valid, s, NEG_INF)


# --------------------------------------------------------------------- fwd
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, causal, pad, s_valid, bq, bk):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _tile(masked):
        # q arrives pre-scaled; no per-tile scale multiply
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if masked:
            s = _tile_mask(s, qi, ki, bq, bk, s_valid, causal, pad)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # interior tiles below the diagonal: no mask at all (their columns
        # are all < qi*bq <= s_valid, see module docstring)
        pl.when(ki < qi)(lambda: _tile(False))
        pl.when(ki == qi)(lambda: _tile(True))
    else:
        _tile(True)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l_scr[:])


# ---------------------------------------------------------------------- dq
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, causal, pad, s_valid, bq, bk):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _tile(masked):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if masked:
            s = _tile_mask(s, qi, ki, bq, bk, s_valid, causal, pad)
        p = jnp.exp(s - lse_ref[0][:, :1])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1])
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(ki < qi)(lambda: _tile(False))
        pl.when(ki == qi)(lambda: _tile(True))
    else:
        _tile(True)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


# -------------------------------------------------------------------- dk/dv
def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, causal, pad, s_valid, bq, bk):
    """dk/dv pass of the classic two-pass backward."""
    ki, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _tile(masked):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if masked:
            # transposed tile: rows walk q (dim 0 is q rows here)
            s = _tile_mask(s, qi, ki, bq, bk, s_valid, causal, pad)
        p = jnp.exp(s - lse_ref[0][:, :1])
        # dV += P^T dO   ([bk, bq] @ [bq, D] via contracting the q rows)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0][:, :1])).astype(q.dtype)
        # dK += dS^T Q
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(qi > ki)(lambda: _tile(False))
        pl.when(qi == ki)(lambda: _tile(True))
    else:
        _tile(True)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dkv_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dqp_ref, dk_scr, dv_scr,
                      *, causal, pad, s_valid, bq, bk):
    """One-pass backward: dk/dv accumulation + dq partial per (ki, qi)."""
    ki, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _tile(masked):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if masked:
            s = _tile_mask(s, qi, ki, bq, bk, s_valid, causal, pad)
        p = jnp.exp(s - lse_ref[0][:, :1])
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0][:, :1])).astype(q.dtype)
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dq partial for this k tile: dS @ K  ([bq, bk] @ [bk, D])
        dqp_ref[0] = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dqp_ref.dtype)

    if causal:
        # skipped tiles (qi < ki) must zero their dq partial: the output
        # block is written either way
        pl.when(qi > ki)(lambda: _tile(False))
        pl.when(qi == ki)(lambda: _tile(True))
        pl.when(qi < ki)(
            lambda: dqp_ref.__setitem__(0, jnp.zeros_like(dqp_ref[0])))
    else:
        _tile(True)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# ------------------------------------------------------------------ calls
def _pad_seq(x, block):
    s = x.shape[1]
    sp = -(-s // block) * block
    if sp == s:
        return x
    return jnp.pad(x, ((0, 0), (0, sp - s), (0, 0)))


def _params(grid):
    """Mosaic grid annotations: batch/q-tile dims are embarrassingly
    parallel; only the k/q-walk dim carries the scratch accumulator."""
    from jax.experimental.pallas import tpu as pltpu

    return dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")))


def _fwd_call(q, k, v, causal, s_valid, bq, bk):
    bn, sp, d = q.shape
    nq, nk = sp // bq, sp // bk
    from jax.experimental.pallas import tpu as pltpu

    kernel = functools.partial(_fwd_kernel, causal=causal, pad=s_valid != sp,
                               s_valid=s_valid, bq=bq, bk=bk)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bn, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn, sp, d), q.dtype),
            jax.ShapeDtypeStruct((bn, sp, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret_mode(),
        **_params((bn, nq, nk)),
    )(q, k, v)
    return o, lse


def _bwd_call(q, k, v, do, lse, delta, causal, s_valid, bq, bk):
    """Two-pass backward (dq pass + dk/dv pass); used for long k-walks."""
    bn, sp, d = q.shape
    nq, nk = sp // bq, sp // bk
    from jax.experimental.pallas import tpu as pltpu

    pad = s_valid != sp
    q_spec_i = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    k_spec_j = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0))
    lse_spec_i = pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, pad=pad,
                          s_valid=s_valid, bq=bq, bk=bk),
        grid=(bn, nq, nk),
        in_specs=[q_spec_i, k_spec_j, k_spec_j, q_spec_i, lse_spec_i,
                  lse_spec_i],
        out_specs=q_spec_i,
        out_shape=jax.ShapeDtypeStruct((bn, sp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret_mode(),
        **_params((bn, nq, nk)),
    )(q, k, v, do, lse, delta)

    # dk/dv: grid's 2nd dim walks k tiles, 3rd dim scans q tiles
    q_spec_j = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, j, 0))
    k_spec_i = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, 0))
    lse_spec_j = pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, pad=pad,
                          s_valid=s_valid, bq=bq, bk=bk),
        grid=(bn, nk, nq),
        in_specs=[q_spec_j, k_spec_i, k_spec_i, q_spec_j, lse_spec_j,
                  lse_spec_j],
        out_specs=[k_spec_i, k_spec_i],
        out_shape=[jax.ShapeDtypeStruct((bn, sp, d), q.dtype),
                   jax.ShapeDtypeStruct((bn, sp, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret_mode(),
        **_params((bn, nk, nq)),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _bwd_call_fused(q, k, v, do, lse, delta, causal, s_valid, bq, bk):
    """One-pass backward: dk/dv + dq partials (summed over k tiles here).

    Saves the dq pass's full s/exp recompute (measured ~35-40% of bwd time
    at bench shapes on v5e); costs nk * |dq| of HBM for the partials, so
    it's gated on nk <= _FUSED_DQ_MAX_NK by the caller.
    """
    bn, sp, d = q.shape
    nq, nk = sp // bq, sp // bk
    from jax.experimental.pallas import tpu as pltpu

    pad = s_valid != sp
    q_spec_j = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, j, 0))
    k_spec_i = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, 0))
    lse_spec_j = pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, j, 0))
    # dq partials: [bn * nk, sp, d], block (b * nk + i, j)
    dqp_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b * nk + i, j, 0))

    dk, dv, dqp = pl.pallas_call(
        functools.partial(_dkv_fused_kernel, causal=causal, pad=pad,
                          s_valid=s_valid, bq=bq, bk=bk),
        grid=(bn, nk, nq),
        in_specs=[q_spec_j, k_spec_i, k_spec_i, q_spec_j, lse_spec_j,
                  lse_spec_j],
        out_specs=[k_spec_i, k_spec_i, dqp_spec],
        # dq partials stay fp32: pre-rounding each partial to bf16 before the
        # cross-tile sum would lose cancellation precision vs the two-pass
        # path's fp32 scratch accumulator (numerics must not change at the
        # nk = _FUSED_DQ_MAX_NK boundary); bounded cost, nk <= 4 partials
        out_shape=[jax.ShapeDtypeStruct((bn, sp, d), q.dtype),
                   jax.ShapeDtypeStruct((bn, sp, d), q.dtype),
                   jax.ShapeDtypeStruct((bn * nk, sp, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret_mode(),
        **_params((bn, nk, nq)),
    )(q, k, v, do, lse, delta)
    dq = jnp.sum(dqp.reshape(bn, nk, sp, d), axis=1).astype(q.dtype)
    return dq, dk, dv


# ------------------------------------------------------------- public API
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _mha(q, k, v, causal, scale, block):
    return _mha_fwd(q, k, v, causal, scale, block)[0]


@jax.named_scope("flash_attention")
def _mha_fwd(q, k, v, causal, scale, block):
    s_valid = q.shape[1]
    qp, kp, vp = (_pad_seq(t, block) for t in (q, k, v))
    # pre-scale q once (one [BN, S, D] multiply) instead of scaling every
    # [bq, bk] score tile inside the kernels; dq is post-scaled in _mha_bwd
    qp = qp * jnp.asarray(scale, qp.dtype)
    o, lse = _fwd_call(qp, kp, vp, causal, s_valid, block, block)
    return o[:, :s_valid], (qp, kp, vp, o, lse)


@jax.named_scope("flash_attention")
def _mha_bwd(causal, scale, block, res, do):
    qp, kp, vp, o, lse = res
    s_valid = do.shape[1]
    dop = _pad_seq(do, block)
    delta = jnp.sum(dop.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    delta = jnp.broadcast_to(delta, (*delta.shape[:2], LANES))
    nk = qp.shape[1] // block
    bwd = _bwd_call_fused if nk <= _FUSED_DQ_MAX_NK else _bwd_call
    dq, dk, dv = bwd(qp, kp, vp, dop, lse, delta, causal, s_valid,
                     block, block)
    # s was computed from the pre-scaled q, so d/dq gains the scale factor
    dq = dq * jnp.asarray(scale, dq.dtype)
    return dq[:, :s_valid], dk[:, :s_valid], dv[:, :s_valid]


def _mha_fwd_rule(q, k, v, causal, scale, block):
    o, res = _mha_fwd(q, k, v, causal, scale, block)
    return o, res


_mha.defvjp(_mha_fwd_rule, _mha_bwd)


def mha(q, k, v, causal=True, scale=None, block=None):
    """Blocked multi-head attention: [B, S, N, D] q/k/v -> [B, S, N, D].

    Any S (padded to the 128 tile internally); D should be a multiple of 8.
    Differentiable (custom VJP, FlashAttention-2 backward).
    """
    B, S, N, D = q.shape
    if scale is None:
        scale = float(D) ** -0.5
    if block is None:
        # widest tile that divides the 128-padded length: wide tiles
        # amortize grid/setup overhead and cross-tile softmax bookkeeping
        # without coarsening the padding granularity (S=520 pads to 640,
        # not 1024).  1024 is the VMEM ceiling ([bq, bk] fp32 score tile =
        # 4 MB); measured on v5e it is ~1.2x faster fwd+bwd than 512 at
        # S=1024 standalone (and worth +0.06 end-to-end bench MFU) and
        # keeps nk <= 4 (fused one-pass backward) out to S=4096
        # (BENCH_KERNELS.md)
        s128 = -(-S // LANES) * LANES
        block = next(b for b in (1024, 512, 256, LANES) if s128 % b == 0)

    # the copies on both sides of the kernel, forward and backward, are
    # ``attention_layout`` in a device trace
    @jax.named_scope("attention_layout")
    def fold(t):
        return jnp.swapaxes(t, 1, 2).reshape(B * N, S, D)

    o = _mha(fold(q), fold(k), fold(v), causal, float(scale), block)
    with jax.named_scope("attention_layout"):
        return jnp.swapaxes(o.reshape(B, N, S, D), 1, 2)


# keep the historical name used by ring attention / docs
mha_forward = mha

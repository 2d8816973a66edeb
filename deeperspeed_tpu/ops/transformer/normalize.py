"""Fused LayerNorm / RMSNorm Pallas kernels (fwd + bwd).

Replaces the reference's normalization CUDA kernels
(``csrc/transformer/normalize_kernels.cu``, inference ``layer_norm.cu`` /
``rms_norm.cu``): one VMEM pass per row block computes the statistics and the
normalized output; the backward kernel recomputes the cheap statistics
instead of storing them (saving the HBM round-trip the reference spends on
``means``/``vars`` buffers) and accumulates dgamma/dbeta across row blocks in
a revisited output block (rows are zero-padded to a block multiple -- see
``ops/pallas_utils.py`` -- so padding contributes exact zeros).

Dispatch: Pallas on TPU when the hidden dim tiles onto 128 lanes; jnp
reference math otherwise (identical semantics, used by tests for parity).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...accelerator import get_accelerator
from ...parallel.topology import BATCH_AXES, SP_AXIS
from ..pallas_utils import LANES, rowwise_call, shard_kernel

MAX_BLOCK_ROWS = 256
# Share of the 16 MiB scoped VMEM a kernel may fill with its row blocks and
# their fp32 temporaries; the rest is the compiler's.  Held against the v5e
# compiler at hidden 1024..8192 (tests/unit/ops/test_tpu_compile.py).
_VMEM_BUDGET = 14 * 2 ** 20


def _supported(hidden):
    return hidden % LANES == 0


def _block_rows(hidden, dtype, n_streams, n_temps):
    """Row-block height that fits VMEM at this width and dtype.

    Each of the ``n_streams`` row-blocked operands is double-buffered in its
    own dtype and the kernel body keeps ``n_temps`` fp32 copies of a block
    alive.  A power of two, so the usual batch*seq row counts need no padding
    copy; never below the bf16 sublane tile."""
    per_row = hidden * (2 * n_streams * jnp.dtype(dtype).itemsize + 4 * n_temps)
    fit = max(_VMEM_BUDGET // per_row, 16)
    return min(MAX_BLOCK_ROWS, 1 << (fit.bit_length() - 1))


# --------------------------------------------------------------------- fwd
def _ln_fwd_kernel(x_ref, g_ref, b_ref, y_ref, *, eps, rms):
    x = x_ref[:].astype(jnp.float32)
    mu = 0.0 if rms else jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) * (x - mu), axis=-1, keepdims=True)
    xhat = (x - mu) * jax.lax.rsqrt(var + eps)
    y = xhat * g_ref[:].astype(jnp.float32)
    if b_ref is not None:
        y = y + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)


def _ln_bwd_kernel(g_ref, x_ref, dy_ref, dx_ref, dg_ref, db_ref, *, eps, rms):
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    gamma = g_ref[:].astype(jnp.float32)
    mu = 0.0 if rms else jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) * (x - mu), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mu) * rstd

    dyg = dy * gamma
    m1 = jnp.mean(dyg, axis=-1, keepdims=True)
    m2 = jnp.mean(dyg * xhat, axis=-1, keepdims=True)
    dx = ((dyg - xhat * m2) if rms else (dyg - m1 - xhat * m2)) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)

    # dgamma/dbeta accumulate over row blocks into a revisited [1, H] block;
    # zero-padded rows (pallas_utils contract) contribute exact zeros
    @pl.when(i == 0)
    def _init():
        dg_ref[:] = jnp.zeros_like(dg_ref)
        db_ref[:] = jnp.zeros_like(db_ref)

    dg_ref[:] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[:] += jnp.sum(dy, axis=0, keepdims=True)


@jax.named_scope("fused_norm")
def _ln_fwd_pallas(x2, gamma, beta, eps, rms):
    h = x2.shape[1]
    vec_spec = pl.BlockSpec((1, h), lambda i: (0, 0))
    if rms:
        kernel = functools.partial(
            lambda g_ref, x_ref, y_ref, **kw: _ln_fwd_kernel(
                x_ref, g_ref, None, y_ref, **kw), eps=eps, rms=rms)
        extra = (gamma.reshape(1, h),)
        extra_specs = (vec_spec,)
    else:
        kernel = functools.partial(
            lambda g_ref, b_ref, x_ref, y_ref, **kw: _ln_fwd_kernel(
                x_ref, g_ref, b_ref, y_ref, **kw), eps=eps, rms=rms)
        extra = (gamma.reshape(1, h), beta.reshape(1, h))
        extra_specs = (vec_spec, vec_spec)
    (y,) = rowwise_call(kernel, [("row", x2.dtype)], [x2],
                        _block_rows(h, x2.dtype, n_streams=2, n_temps=2),
                        extra_in_specs=extra_specs, extra_args=extra)
    return y


@jax.named_scope("fused_norm")
def _ln_bwd_pallas(x2, gamma, dy2, eps, rms):
    h = x2.shape[1]
    vec_spec = pl.BlockSpec((1, h), lambda i: (0, 0))
    dx, dg, db = rowwise_call(
        functools.partial(_ln_bwd_kernel, eps=eps, rms=rms),
        [("row", x2.dtype), ("vec", jnp.float32), ("vec", jnp.float32)],
        [x2, dy2], _block_rows(h, x2.dtype, n_streams=3, n_temps=4),
        extra_in_specs=(vec_spec,), extra_args=(gamma.reshape(1, h),))
    return dx, dg, db


# ---------------------------------------------------------------- reference
def _ln_ref(x, gamma, beta, eps, rms):
    x32 = x.astype(jnp.float32)
    mu = 0.0 if rms else jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32)
    if beta is not None:
        y = y + beta.astype(jnp.float32)
    return y.astype(x.dtype)


# ------------------------------------------------------------------ public
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _norm(x, gamma, beta, eps, rms, use_pallas):
    if not use_pallas:
        return _ln_ref(x, gamma, beta, eps, rms)
    h = x.shape[-1]
    rows = x.size // h
    y = _ln_fwd_pallas(x.reshape(rows, h), gamma, beta, eps, rms)
    return y.reshape(x.shape)


def _norm_fwd(x, gamma, beta, eps, rms, use_pallas):
    return _norm(x, gamma, beta, eps, rms, use_pallas), (x, gamma)


def _norm_bwd(eps, rms, use_pallas, res, dy):
    x, gamma = res
    h = x.shape[-1]
    rows = x.size // h
    if use_pallas:
        dx, dg, db = _ln_bwd_pallas(x.reshape(rows, h), gamma,
                                    dy.reshape(rows, h), eps, rms)
        dx = dx.reshape(x.shape)
        dg = dg.reshape(h).astype(gamma.dtype)
        db = db.reshape(h).astype(gamma.dtype)
    else:
        x32, dy32 = x.astype(jnp.float32), dy.astype(jnp.float32)
        g32 = gamma.astype(jnp.float32)
        mu = 0.0 if rms else jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
        rstd = jax.lax.rsqrt(var + eps)
        xhat = (x32 - mu) * rstd
        dyg = dy32 * g32
        m1 = jnp.mean(dyg, axis=-1, keepdims=True)
        m2 = jnp.mean(dyg * xhat, axis=-1, keepdims=True)
        dx = ((dyg - xhat * m2) if rms else (dyg - m1 - xhat * m2)) * rstd
        dx = dx.astype(x.dtype)
        axes = tuple(range(x.ndim - 1))
        dg = jnp.sum(dy32 * xhat, axis=axes).astype(gamma.dtype)
        db = jnp.sum(dy32, axis=axes).astype(gamma.dtype)
    return dx, dg, (None if rms else db)


_norm.defvjp(_norm_fwd, _norm_bwd)


def _dispatch(x, gamma, beta, eps, rms, use_pallas):
    if use_pallas is None:
        use_pallas = (get_accelerator().use_pallas_kernels()
                      and _supported(x.shape[-1]))
    if not use_pallas:
        return _norm(x, gamma, beta, eps, rms, False)
    # activations [batch, seq, ..., hidden]; gamma/beta whole on every shard
    lead = (BATCH_AXES, SP_AXIS)[:x.ndim - 1]
    x_spec = lead + (None,) * (x.ndim - len(lead))
    vecs = (gamma,) if rms else (gamma, beta)
    return shard_kernel(
        lambda x, g, b=None: _norm(x, g, b, eps, rms, True),
        (x, *vecs), (x_spec, *[(None,)] * len(vecs)))


def layer_norm(x, gamma, beta, eps=1e-5, use_pallas=None):
    """Fused LayerNorm over the last dim; fp32 statistics."""
    return _dispatch(x, gamma, beta, eps, False, use_pallas)


def rms_norm(x, gamma, eps=1e-5, use_pallas=None):
    """Fused RMSNorm over the last dim (reference ``rms_norm.cu``)."""
    return _dispatch(x, gamma, None, eps, True, use_pallas)

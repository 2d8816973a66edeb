"""TPU flash attention entry point.

Replaces the reference's fused attention-softmax CUDA kernels
(``csrc/transformer/softmax_kernels.cu``, inference ``softmax.cu``) with
online-softmax blocked attention on the MXU: no [S, S] score matrix ever
reaches HBM.

The kernel is the **in-tree** one (``pallas_flash.mha`` -- fwd + custom-VJP
bwd, causal, any sequence length via tile padding).
"""

import functools

import jax
import jax.numpy as jnp


def flash_attention_supported(q_shape, dtype=None):
    """True when the kernel handles this [B, S, N, D] shape + dtype (fwd AND
    bwd).  Checked *before* dispatch so grad tracing never reaches an
    unsupported kernel."""
    D = q_shape[3]
    if dtype is not None and jnp.dtype(dtype) not in (
            jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    # any S (padded to the 128 tile internally)
    return D % 8 == 0


@functools.partial(jax.jit, static_argnames=("causal", "scale", "window"))
def flash_attention(q, k, v, causal=True, scale=None, window=None):
    """[B, S, N, D] q/k/v -> [B, S, N, D]; bf16/fp32 in, same dtype out.
    ``window``: a causal call's sliding window in rows (``mha``)."""
    from .pallas_flash import mha

    if scale is None:
        scale = float(q.shape[3]) ** -0.5
    return mha(q, k, v, causal=causal, scale=scale, window=window)

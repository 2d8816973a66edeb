"""TPU flash attention entry point.

Replaces the reference's fused attention-softmax CUDA kernels
(``csrc/transformer/softmax_kernels.cu``, inference ``softmax.cu``) with
online-softmax blocked attention on the MXU: no [S, S] score matrix ever
reaches HBM.

The kernels are the **in-tree** ones, fwd + custom-VJP bwd, any sequence
length via tile padding:

* ``pallas_flash.mha``: q ``[B, S, N, D]`` against k and v ``[B, S, N_kv,
  D]`` (N a multiple of N_kv: grouped-query heads), ONE width ``D`` for the
  score and the value, causal or not, with or without a causal window;
* ``pallas_flash_mla.mla``: latent attention's call, a causal score that is
  the sum of a per-head product over ``d_nope`` and one over ``d_rope``
  against a rotary key all heads share (``[B, S, d_rope]``), and values of
  ``d_v``.
"""

import functools

import jax
import jax.numpy as jnp


def flash_attention_supported(q_shape, dtype=None, rope_dim=None, v_dim=None):
    """True when a kernel handles this [B, S, N, D] shape + dtype (fwd AND
    bwd): float32 or bfloat16, and D a multiple of 8.  With ``rope_dim``
    and ``v_dim`` the question is of the latent call (``q_shape`` its
    ``q_nope``'s): heads of whole lane blocks, the rotary part one too or
    half of one with the heads in pairs, a length whose backward keeps a
    head's q side in VMEM (``pallas_flash_mla.supported``).  Checked
    *before* dispatch so grad tracing never reaches an unsupported
    kernel."""
    D = q_shape[3]
    if dtype is not None and jnp.dtype(dtype) not in (
            jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    if rope_dim is not None:
        from .pallas_flash_mla import supported

        return supported(q_shape, rope_dim, v_dim, dtype)
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


@functools.partial(jax.jit, static_argnames=("scale",))
def flash_latent_attention(q_nope, q_rope, k_nope, k_rope, v, scale=None):
    """``pallas_flash_mla.mla``: [B, S, N, d_nope] x2, [B, S, N, d_rope],
    [B, S, d_rope], [B, S, N, d_v] -> [B, S, N, d_v]."""
    from .pallas_flash_mla import mla

    return mla(q_nope, q_rope, k_nope, k_rope, v, scale=scale)

"""Isolate flash-attention kernel timing at bench shape (fwd, bwd, vs XLA).

Timing uses ``tputime.timed_inner`` (loop inside one jit, ended by
``block_until_ready``), so per-dispatch host overhead does not count as
kernel time.

FLOP accounting via ``tputime.attn_flops``: flash fwdbwd = 7 matmul units
(bwd recomputes S/P); the XLA dense path stores P instead of recomputing, so
its fwdbwd executes ~5 units — both are credited with the work they actually
run so TFLOPs are comparable as "achieved rate", not "useful-work rate".
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

from tputime import attn_flops, emit, timed_inner


def main():
    from deeperspeed_tpu.ops.attention.core import _reference_attention
    from deeperspeed_tpu.ops.attention.flash import flash_attention
    from deeperspeed_tpu.ops.attention.pallas_flash import mha

    B, S, N, D = 16, 1024, 12, 64
    q = jax.random.normal(jax.random.PRNGKey(2), (B, S, N, D), jnp.bfloat16)
    fwd = attn_flops(B, S, N, D, mode="fwd")
    fwdbwd = attn_flops(B, S, N, D, mode="fwdbwd")
    dense_fwdbwd = fwd + attn_flops(B, S, N, D, mode="bwd_stored")

    for blk in (256, 512, 1024):
        dt = timed_inner(
            lambda x, b=blk: mha(x, x, x, causal=True, block=b), q, iters=30)
        emit(f"flash_fwd_b{blk}", dt, tflops=round(fwd / dt / 1e12, 1))
        dt = timed_inner(
            lambda x, b=blk: jax.grad(lambda t: mha(
                t, t, t, causal=True, block=b).astype(jnp.float32).sum())(x),
            q, iters=20)
        emit(f"flash_fwdbwd_b{blk}", dt, tflops=round(fwdbwd / dt / 1e12, 1))

    dt = timed_inner(
        lambda x: flash_attention(x, x, x, causal=True, impl="upstream"),
        q, iters=30)
    emit("upstream_fwd", dt, tflops=round(fwd / dt / 1e12, 1))
    dt = timed_inner(
        lambda x: jax.grad(lambda t: flash_attention(
            t, t, t, causal=True, impl="upstream").astype(
                jnp.float32).sum())(x), q, iters=20)
    emit("upstream_fwdbwd", dt, tflops=round(fwdbwd / dt / 1e12, 1))

    dt = timed_inner(
        lambda x: _reference_attention(x, x, x, causal=True).astype(
            jnp.bfloat16), q, iters=20)
    emit("xla_dense_fwd", dt, tflops=round(fwd / dt / 1e12, 1))
    dt = timed_inner(
        lambda x: jax.grad(lambda t: _reference_attention(
            t, t, t, causal=True).astype(jnp.float32).sum())(x).astype(
                jnp.bfloat16), q, iters=20)
    emit("xla_dense_fwdbwd", dt,
         tflops=round(dense_fwdbwd / dt / 1e12, 1))


if __name__ == "__main__":
    main()

"""Time the latent-attention flash kernel on the chip as a model calls it
(``pallas_flash_mla.mla``), beside the plain kernel at the nearest shapes.

    python tools/profile_mla.py [--shape 4x8192x16] [--check]

Lines (JSON, ``tools/tputime.emit``), each forward and forward + backward,
the kernel's device ms apart from what stands around it
(``profile_attn.device_ms``):

* ``mla``: q_nope / k_nope 128, q_rope 64 against ONE rotary key, v 128;
  TFLOP/s credit ``benchmarks/kernel_costs/flash_attention_mla.py``'s count;
* ``mha_128``: ``pallas_flash.mha`` at one width of 128: what the same walk
  costs without the rotary product (a lower bound, not the same work);
* ``mha_256``: ``mha`` on the rotary key copied to every head and q, k, v
  padded to 256 lanes: what the tree could run before this kernel.

``--check``: the kernel beside the plain form (``core.py``), outputs and all
five gradients, on the chip in the timed type.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

import profile_attn
from tputime import attn_flops, emit, timed_inner

D_NOPE, D_ROPE, D_V = 128, 64, 128


def _time(name, call, x, flops, iters):
    n = len(x)
    fwd = jax.jit(lambda t: (call(*t),) + t[1:])
    fwdbwd = jax.jit(lambda t: jax.grad(
        lambda *a: call(*a).astype(jnp.float32).sum(),
        argnums=tuple(range(n)))(*t))
    for mode, fn in (("fwd", fwd), ("fwdbwd", fwdbwd)):
        wall = timed_inner(fn, x, iters=iters)
        dev = profile_attn.device_ms(fn, x)
        emit(f"{name}_{mode}", wall, **dev, tflops=round(
            flops[mode] / (dev["kernel_ms"] * 1e-3) / 1e12, 2))


def main():
    from benchmarks.kernel_costs import flash_attention_mla as costs
    from deeperspeed_tpu.ops.attention import pallas_flash, pallas_flash_mla
    from deeperspeed_tpu.ops.attention.core import \
        _reference_latent_attention

    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="4x8192x16", help="BxSxN")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--only-mla", action="store_true")
    args = ap.parse_args()
    B, S, N = (int(t) for t in args.shape.split("x"))
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    shapes = [(B, S, N, D_NOPE), (B, S, N, D_ROPE), (B, S, N, D_NOPE),
              (B, S, D_ROPE), (B, S, N, D_V)]
    x = tuple(jax.random.normal(k, s, jnp.bfloat16)
              for k, s in zip(keys, shapes))
    if args.check:
        loss = lambda f: lambda *a: jnp.sum(  # noqa: E731
            jnp.square(f(*a).astype(jnp.float32)))
        small = tuple(t[:1, :2048] for t in x)
        wide = tuple(t.astype(jnp.float32) for t in small)
        with jax.default_matmul_precision("highest"):
            want = (_reference_latent_attention(*wide),) + jax.grad(
                loss(_reference_latent_attention), argnums=range(5))(*wide)
        got = (pallas_flash_mla.mla(*small),) + jax.grad(
            loss(pallas_flash_mla.mla), argnums=range(5))(*small)
        for name, a, b in zip(("o", "dq_nope", "dq_rope", "dk_nope",
                               "dk_rope", "dv"), got, want):
            emit(f"check_{name}", rel_err=float(
                jnp.linalg.norm(a.astype(jnp.float32) - b)
                / jnp.linalg.norm(b)))
    shape = dict(B=B, S=S, N=N, d_nope=D_NOPE, d_rope=D_ROPE, d_v=D_V)
    fwd = costs.forward(**shape)["flops"]
    flops = {"fwd": fwd, "fwdbwd": fwd + costs.backward(**shape)["flops"]}
    profile_attn.KERNEL = pallas_flash_mla.KERNEL
    _time("mla", pallas_flash_mla.mla, x, flops, args.iters)
    if args.only_mla:
        return
    profile_attn.KERNEL = "flash_attention"
    for d in (128, 256):
        qkv = tuple(jax.random.normal(k, (B, S, N, d), jnp.bfloat16)
                    for k in keys[:3])
        _time(f"mha_{d}", pallas_flash.mha, qkv,
              {m: attn_flops(B, S, N, d, mode=m) for m in flops}, args.iters)


if __name__ == "__main__":
    main()

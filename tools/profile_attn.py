"""Time the flash-attention kernels alone on the chip, by shape and tile plan.

For each shape: the forward and forward + backward of the folded
``[B*N, S, D]`` call (no layout copies), under the plan ``tile_plan`` picks
and under every ``--plans block:sub:rows`` given, and -- with ``--parent DIR``, a
second checkout of this repo -- under that checkout's kernel for comparison
on the same chip in the same process.

Timing uses ``tputime.timed_inner`` (loop inside one jit, ended by
``block_until_ready``), so per-dispatch host overhead does not count as
kernel time.  TFLOP/s credit the causal half of the square only
(``tputime.attn_flops``: forward 2 matmul units, forward + backward 7).

    python tools/profile_attn.py --shapes 8x2048x16x64 16x1024x12x64 \
        --plans 1024:256:1024 512:128:512 --parent _parent
"""

import argparse
import importlib.util
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

from tputime import attn_flops, emit, timed_inner

# the benchmark cells' shapes, then BENCH_KERNELS.md's milestone shapes
DEFAULT_SHAPES = ["8x2048x16x64", "16x1024x12x64", "4x2048x8x96",
                  "4x2048x8x128", "2x4096x8x128", "2x8192x8x128"]


def _parent_mha(checkout):
    """Another checkout's kernel module, loaded beside this one's (its
    relative imports -- ``pallas_utils`` -- resolve to this checkout)."""
    name = "deeperspeed_tpu.ops.attention._parent_pallas_flash"
    spec = importlib.util.spec_from_file_location(name, os.path.join(
        checkout, "deeperspeed_tpu", "ops", "attention", "pallas_flash.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _time(name, call, x, flops, iters):
    try:
        dt = timed_inner(lambda t: call(t, t, t), x, iters=iters)
        emit(f"{name}_fwd", dt, tflops=round(flops["fwd"] / dt / 1e12, 2))
        dt = timed_inner(
            lambda t: jax.grad(lambda u: call(u, u, u).astype(
                jnp.float32).sum())(t), x, iters=iters)
        emit(f"{name}_fwdbwd", dt,
             tflops=round(flops["fwdbwd"] / dt / 1e12, 2))
    except Exception as e:  # noqa: BLE001 -- a plan the compiler refuses
        emit(f"{name}_error", error=str(e)[:300])


def main():
    from deeperspeed_tpu.ops.attention import pallas_flash as pf

    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=DEFAULT_SHAPES)
    ap.add_argument("--plans", nargs="*", default=[],
                    help="block:sub:rows triples to time beside tile_plan's")
    ap.add_argument("--parent", default=None)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--non-causal", action="store_true")
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()
    dtype, causal = jnp.dtype(args.dtype), not args.non_causal
    parent = _parent_mha(args.parent) if args.parent else None

    for shape in args.shapes:
        B, S, N, D = (int(t) for t in shape.split("x"))
        x = jax.random.normal(jax.random.PRNGKey(2), (B * N, S, D), dtype)
        scale = float(D) ** -0.5
        flops = {m: attn_flops(B, S, N, D, causal, mode=m)
                 for m in ("fwd", "fwdbwd")}
        own = pf.tile_plan(S, D, dtype)
        plans = [("auto", own)]
        for text in args.plans:
            block, sub, rows = (int(t) for t in text.split(":"))
            if S % block == 0:
                plans.append((text, own._replace(block=block, sub=sub,
                                                 rows=rows)))
        for label, plan in plans:
            executed, masked, total = pf.walk_counts(plan, S, causal)
            emit(f"{shape}_{label}_plan", **plan._asdict(),
                 executed_share=round(executed / total, 4),
                 masked_share=round(masked / total, 4))
            _time(f"{shape}_{label}",
                  lambda q, k, v, p=plan: pf._mha(q, k, v, causal, scale, p),
                  x, flops, args.iters)
        if parent is not None:
            s128 = -(-S // 128) * 128
            blk = next(b for b in (1024, 512, 256, 128) if s128 % b == 0)
            _time(f"{shape}_parent_b{blk}",
                  lambda q, k, v: parent._mha(q, k, v, causal, scale, blk),
                  x, flops, args.iters)


if __name__ == "__main__":
    main()

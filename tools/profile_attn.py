"""Time flash attention on the chip as a model calls it: ``mha`` on
``[B, S, N, D]``, the kernel and what stands around it told apart.

For each shape the forward and forward + backward run under a profiler
session, and the device's events are summed by what they are: the kernel
(events named by its scope, ``flash_attention``) and everything else the
program runs around it (``around``: layout copies, the q pre-scale and dq
post-scale, the sum that feeds the gradient).  A program of ``mha`` alone
takes and returns ``[B, S, N, D]`` arrays in the layout the compiler gives a
program's arguments, so ``around`` is an upper bound of what a model pays,
where producers and consumers are fused; the kernel's time is the kernel's.

A shape is ``BxSxNxD``, or ``BxSxN/KVxD`` for grouped-query heads: N query
heads over KV heads of k and v.  Such a shape is timed twice: as the kernel
takes it (``auto``: k and v at their KV heads, a query head reading its KV
head's block where it lies, dk and dv summed over the group inside the
backward kernel) and ``copied`` (k and v repeated out to N heads before the
call and dk, dv summed back after it: the form every grouped-query model
here had until PR 50, and the one ``mha`` still takes for layouts whose heads
are no lane blocks of their own).  The copy and the sum are ``around``.

``--windows 0 1024`` times each shape under those causal windows too (0 is the
full call; a windowed call's kernels are ``flash_attention_window`` events and
its TFLOP/s credit the band's pairs only).  ``--parent DIR`` (a second checkout of this repo) times that checkout's
``mha`` the same way (on the copies, where the heads are grouped), on the
same chip in the same process; ``--plans``
adds ``block:sub:rows`` triples beside the plan ``tile_plan`` picks.

    python tools/profile_attn.py --shapes 8x2048x16x64 16x1024x12x64 \
        --plans 1024:256:1024 --parent _parent

Wall time per call comes from ``tputime.timed_inner`` (loop inside one jit,
ended by ``block_until_ready``).  TFLOP/s credit the causal half of the
square only (``tputime.attn_flops``: forward 2 matmul units, forward +
backward 7) over the kernel's time.
"""

import argparse
import importlib.util
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

from tputime import attn_flops, drain, emit, timed_inner

# the benchmark cells' shapes, then BENCH_KERNELS.md's milestone shapes
DEFAULT_SHAPES = ["8x2048x16x64", "16x1024x12x64", "4x4096x16x128",
                  "4x2048x8x96", "2x8192x8x128"]
KERNEL = "flash_attention"


def _parent_module(checkout):
    """Another checkout's kernel module, loaded beside this one's (its
    relative imports -- ``pallas_utils`` -- resolve to this checkout)."""
    name = "deeperspeed_tpu.ops.attention._parent_pallas_flash"
    spec = importlib.util.spec_from_file_location(name, os.path.join(
        checkout, "deeperspeed_tpu", "ops", "attention", "pallas_flash.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def device_ms(fn, x, calls=4):
    """Device milliseconds a call of ``fn(x)``, by kind of event: the
    kernel's and everything else's, from a profiler session over ``calls``
    calls (compiled before it opens)."""
    from benchmarks import trace_reduce

    drain(fn(x))
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(calls):
                out = fn(x)
            drain(out)
        trace = trace_reduce.read_xplane(trace_reduce.find_xplane(tmp))
    if not trace["devices"]:
        raise RuntimeError("the trace holds no device plane: not a chip run")
    ops = [(trace_reduce.instruction_kind(name), dur)
           for name, _, dur in next(iter(trace["devices"].values()))["ops"]]
    # the kernel's instruction carries its scope: ``flash_attention.3`` in a
    # model's step, ``transpose_jvp_flash_attention__.1`` under a bare grad
    kernel = sum(dur for kind, dur in ops if KERNEL in kind)
    n_kernel = sum(KERNEL in kind for kind, _ in ops)
    total = sum(dur for _, dur in ops)
    around = {}
    for kind, dur in ops:
        if KERNEL not in kind:
            around[kind] = around.get(kind, 0) + dur
    top = sorted(around.items(), key=lambda kv: -kv[1])[:4]
    return {"kernel_ms": round(kernel / calls / 1e6, 4),
            "around_ms": round((total - kernel) / calls / 1e6, 4),
            "kernel_events": n_kernel // calls,
            "around_top": {k: round(v / calls / 1e6, 4) for k, v in top}}


def _time(name, call, qkv, flops, iters):
    """``call(q, k, v)`` forward, and forward + backward to all three."""
    fwd = jax.jit(lambda t: (call(*t),) + t[1:])
    fwdbwd = jax.jit(lambda t: jax.grad(
        lambda *a: call(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2))(*t))
    try:
        for mode, fn in (("fwd", fwd), ("fwdbwd", fwdbwd)):
            wall = timed_inner(fn, qkv, iters=iters)
            dev = device_ms(fn, qkv)
            emit(f"{name}_{mode}", wall, **dev, tflops=round(
                flops[mode] / (dev["kernel_ms"] * 1e-3) / 1e12, 2))
    except Exception as e:  # noqa: BLE001 -- a plan the compiler refuses
        emit(f"{name}_error", error=repr(e)[:300])


def main():
    from deeperspeed_tpu.ops.attention import pallas_flash as pf

    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=DEFAULT_SHAPES)
    ap.add_argument("--plans", nargs="*", default=[],
                    help="block:sub:rows triples to time beside tile_plan's")
    ap.add_argument("--parent", default=None)
    ap.add_argument("--windows", type=int, nargs="*", default=[0],
                    help="causal windows in rows to time; 0 is the full call")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--non-causal", action="store_true")
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()
    dtype, causal = jnp.dtype(args.dtype), not args.non_causal
    parent = _parent_module(args.parent) if args.parent else None
    planned = pf.tile_plan

    def copied(mha, **kw):
        """``mha`` on k and v repeated out to the query heads."""
        return lambda q, k, v: mha(q, *(
            jnp.repeat(t, q.shape[2] // t.shape[2], axis=2) for t in (k, v)),
            causal=causal, **kw)

    for shape in args.shapes:
        B, S, heads, D = shape.split("x")
        B, S, D = int(B), int(S), int(D)
        N, KV = (int(t) for t in (heads.split("/") * 2)[:2])
        keys = jax.random.split(jax.random.PRNGKey(2), 3)
        x = tuple(jax.random.normal(key, (B, S, n, D), dtype)
                  for key, n in zip(keys, (N, KV, KV)))
        flops = {m: attn_flops(B, S, N, D, causal, mode=m)
                 for m in ("fwd", "fwdbwd")}
        own = planned(S, D, dtype, N=N, kv_heads=KV)
        plans = [("auto", own)]
        if KV != N:
            plans.append(("copied", planned(S, D, dtype, N=N)))
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
            for window in args.windows:
                windowed = plan._replace(window=window if window < S else 0)
                share = (pf.band_pairs(S, windowed.window)
                         / pf.band_pairs(S, None)) if causal else 1.0
                # mha asks tile_plan; hand it this plan for the call
                pf.tile_plan = lambda *a, plan=windowed, **kw: plan
                try:
                    _time(f"{shape}_{label}" + (f"_w{window}" if window
                                                else ""),
                          copied(pf.mha) if label == "copied" else
                          lambda q, k, v: pf.mha(q, k, v, causal=causal),
                          x, {m: f * share for m, f in flops.items()},
                          args.iters)
                finally:
                    pf.tile_plan = planned
        if parent is not None:
            for window in args.windows:
                share = (pf.band_pairs(S, window) / pf.band_pairs(S, None)
                         if causal else 1.0)
                _time(f"{shape}_parent" + (f"_w{window}" if window else ""),
                      copied(parent.mha, window=window or None), x,
                      {m: f * share for m, f in flops.items()}, args.iters)


if __name__ == "__main__":
    main()

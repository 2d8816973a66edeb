"""Time EVA's chunk summaries alone on the chip, the kernel pair
(``ops/attention/pallas_eva_pool.py``) beside the plain ``jnp`` form
(``ops/attention/eva.py::chunk_summaries``), at one shape: a forward call,
and forward + backward.  One JSON line a (form, pass): ms a call of the
whole program by the host's clock over ``--calls`` calls beside the time
the bytes a call must move take at the chip's HBM peak (k and v read once
and the summaries written; backward: k, v and the summaries' cotangents
read, dk and dv written), the device's busiest operations from a profiler
session, and each output's largest difference from the plain form's under
``jax.default_matmul_precision("highest")`` over that one's largest entry
(XLA may make the plain form's ``sum(k * mu)`` a matmul, which at the
default precision rounds float32 operands to bfloat16).

    python tools/profile_eva_pool.py            # the EvaByte cell's shape
    python tools/profile_eva_pool.py --seq 8192 --heads 8 --rows 512 1024
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmarks import core
from deeperspeed_tpu.ops.attention import eva, pallas_eva_pool
from tools.profile_moe_walk import busiest


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--top", type=int, default=6)
    ap.add_argument("--forms", nargs="+", default=["pallas", "plain"])
    ap.add_argument("--rows", nargs="+", default=["kept"],
                    help="the kernels' row block at most, to sweep; 'kept' = "
                    "the module's own")
    args = ap.parse_args(argv)

    B, S, N, D, C = (args.batch, args.seq, args.heads, args.head_dim,
                     args.chunk)
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    k, v = (jax.random.normal(keys[i], (B, S, N, D), args.dtype)
            for i in range(2))
    mu, phi = (jnp.clip(jax.random.normal(keys[2 + i], (N, D)), -1, 1)
               * D ** -0.5 for i in range(2))
    gk, gv = (jax.random.normal(keys[4 + i], (B, S // C, N, D), args.dtype)
              for i in range(2))
    rows_bytes, pooled_bytes = k.nbytes + v.nbytes, gk.nbytes + gv.nbytes
    must_move = {"forward": rows_bytes + pooled_bytes}
    must_move["forward_backward"] = (must_move["forward"] + 2 * rows_bytes
                                     + pooled_bytes)
    kind = jax.devices()[0].device_kind
    peak = core.device_peaks(kind)["hbm_bytes_per_s"]
    print(json.dumps({"device": kind, "shapes": dict(
        batch=B, seq=S, heads=N, head_dim=D, chunk=C, dtype=args.dtype),
        "hbm_floor_ms": {name: round(1e3 * n / peak, 3)
                         for name, n in must_move.items()}}), flush=True)

    def programs(use_pallas):
        def fn(*t):
            return eva.chunk_summaries(*t, C, use_pallas=use_pallas)

        def loss(*t):
            kb, vb = fn(*t)
            return (jnp.sum(kb.astype(jnp.float32) * gk.astype(jnp.float32))
                    + jnp.sum(vb.astype(jnp.float32)
                              * gv.astype(jnp.float32)))
        return {"forward": jax.jit(fn),
                "forward_backward": jax.jit(jax.grad(loss,
                                                     argnums=(0, 1, 2, 3)))}

    ops = (k, v, mu, phi)
    with jax.default_matmul_precision("highest"):
        want = {name: program(*ops)
                for name, program in programs(False).items()}
    for form, rows in [(form, rows) for form in args.forms
                       for rows in (args.rows if form == "pallas"
                                    else [None])]:
        if rows not in (None, "kept"):          # read when a call is traced
            pallas_eva_pool.ROWS = int(rows)
            jax.clear_caches()
        for name, program in programs(form == "pallas").items():
            started = time.perf_counter()
            got = jax.block_until_ready(program(*ops))
            compiled_s = time.perf_counter() - started
            start = time.perf_counter()
            for _ in range(args.calls):
                out = program(*ops)
            jax.block_until_ready(out)
            ms = 1e3 * (time.perf_counter() - start) / args.calls
            top, _ = busiest(lambda: program(*ops), 5, args.top)
            off = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                         - b.astype(jnp.float32)))
                         / jnp.max(jnp.abs(b.astype(jnp.float32))))
                   for a, b in zip(got, want[name])]
            print(json.dumps({
                "form": form, "rows": rows and pallas_eva_pool.pool_rows(S, C),
                "pass": name, "ms": round(ms, 3),
                "hbm_floor_pct": round(
                    100 * 1e3 * must_move[name] / peak / ms, 1),
                "first_call_s": round(compiled_s, 1),
                "max_diff_from_highest_over_its_largest": off,
                "busiest": top}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Time Mamba-2's chunked SSD scan alone on the chip, the repo's kernel pair
(``ops/pallas_ssd.py``) beside the plain ``jnp`` form (``ops/ssm.py::_ssd``),
at one shape: a forward call, and forward + backward (whose forward call
also writes the states the backward reads).  One JSON line a (form, pass): ms
a call of the whole program by the host's clock over ``--calls`` calls with
the share of the roofline (``benchmarks/kernel_costs/ssd_scan.py``) that is,
the kernel events' own device ms from a profiler session, and the device's
busiest operations.

    python tools/profile_ssd.py            # the hybrid cell's shapes
    python tools/profile_ssd.py --heads 8 --head-dim 128 --groups 2
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
from deeperspeed_tpu.ops import pallas_ssd, ssm
from tools.profile_moe_walk import busiest


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--groups", type=int, default=2)
    ap.add_argument("--state", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=128)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--forms", nargs="+", default=["pallas", "plain"])
    ap.add_argument("--blocks-a-step", nargs="+", default=["kept"],
                    help="lane blocks a step of the kernels' walk, forward "
                    "and backward alike, to sweep; 'kept' = the module's own")
    args = ap.parse_args(argv)

    B, S, Hd, P, G, N = (args.batch, args.seq, args.heads, args.head_dim,
                         args.groups, args.state)
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(keys[0], (B, S, Hd, P), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (B, S, Hd)) - 4.0)
    a = -jnp.exp(jax.random.uniform(keys[2], (Hd,), minval=0.0, maxval=2.77))
    b = jax.random.normal(keys[3], (B, S, G, N), jnp.bfloat16)
    c = jax.random.normal(keys[4], (B, S, G, N), jnp.bfloat16)
    d = jnp.ones((Hd,), jnp.float32)
    g = jax.random.normal(keys[5], (B, S, Hd, P), jnp.bfloat16)
    plan = pallas_ssd.scan_plan(Hd, P, G, N, args.chunk, (x.dtype,) * 3)

    def scan(form):
        if form == "pallas":
            return lambda *t: ssm._through_the_kernels(*t, plan)
        return lambda x, dt, a, b, c, d: (
            jax.checkpoint(ssm._ssd, static_argnums=5)(x, dt, a, b, c,
                                                       args.chunk)
            + x.astype(jnp.float32) * d[:, None]).astype(x.dtype)

    shapes = (B, S, Hd, G, P, N, args.chunk)
    cost = core.load_kernel_cost("ssd_scan")
    work = {"forward": cost.forward(*shapes)}
    work["forward_backward"] = {
        k: work["forward"][k] + cost.backward(*shapes)[k]
        for k in ("flops", "bytes")}
    kind = jax.devices()[0].device_kind
    peaks = core.device_peaks(kind)
    print(json.dumps({"device": kind, "plan": plan and plan._asdict(),
                      "shapes": dict(zip(
                          "batch seq heads groups head_dim state chunk".split(),
                          shapes)), "work": work}), flush=True)
    ops = (x, dt, a, b, c, d)
    forms = [(form, n) for form in args.forms if form != "pallas"
             or plan is not None
             for n in (args.blocks_a_step if form == "pallas" else [None])]
    for form, blocks_a_step in forms:
        if blocks_a_step not in (None, "kept"):        # read at trace time
            pallas_ssd.FWD_BLOCKS_A_STEP = int(blocks_a_step)
            pallas_ssd.BWD_BLOCKS_A_STEP = int(blocks_a_step)
            jax.clear_caches()
        fn = scan(form)
        programs = {
            "forward": jax.jit(fn),
            "forward_backward": jax.jit(jax.grad(
                lambda *t: jnp.sum(fn(*t).astype(jnp.float32)
                                   * g.astype(jnp.float32)),
                argnums=tuple(range(6)))),
        }
        for name, program in programs.items():
            started = time.perf_counter()
            jax.block_until_ready(program(*ops))
            compiled_s = time.perf_counter() - started
            start = time.perf_counter()
            for _ in range(args.calls):
                out = program(*ops)
            jax.block_until_ready(out)
            ms = 1e3 * (time.perf_counter() - start) / args.calls
            top = busiest(lambda: program(*ops), 5, args.top)
            kernels = [row for row in top if row["op"].startswith("ssd_scan")]
            pct, bound = core.roofline_pct(
                work[name]["flops"], work[name]["bytes"], ms / 1e3,
                peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
            print(json.dumps({
                "form": form, "blocks_a_step": blocks_a_step, "pass": name,
                "ms": round(ms, 3),
                "roofline_pct_of_the_program": round(pct, 2), "bound": bound,
                "first_call_s": round(compiled_s, 1),
                "kernel_events": kernels, "busiest": top}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Time the routed experts' walk alone on the chip, by load:
``moe.dropless.routed_experts`` forward and backward on ``tokens`` rows of
``latent`` floats over ``held`` experts, for a list of loads (slots an
expert) and of chunk sizes.  The loads are data, so one program a chunk size
serves them all; the device's busiest operations of the last load are listed
from a profiler session.

    python tools/profile_moe_walk.py --rows 256 512 --loads 0 40x1 40 704 8000x1+704

The defaults are the hybrid cell's layer (8 experts of a 1024-wide latent,
relu2).  Mellum's cell (``train-mellum2-ep4-8k``: 16 gated experts on rows of
2,304, 1,024-8,192 slots an expert at micro-batch 2-4 and beyond) is

    python tools/profile_moe_walk.py --tokens 32768 --latent 2304 \
        --intermediate 896 --held 16 --gated --rows 256 1024 \
        --loads 1024 2048 4096 8192

and with ``--grouped`` the form that cell's layers take (``dropless.walk_form``:
the sorted slots ``--rows`` at a time through the grouped matmul,
``ops/pallas_gmm.py``; ``--per-token`` is the most slots a token has, ``min(k,
held)``, and ``--tile`` the kernels' rows a tile).  Laguna's cell
(``train-laguna-s-ep32-8k``: 8 gated experts of 1,024 on rows of 3,072, a
collapsed load, the lightest world's, even routing, the heaviest drift and one
expert five times the mean) is

    python tools/profile_moe_walk.py --tokens 16384 --latent 3072 \
        --intermediate 1024 --held 8 --gated --grouped --rows 8192 \
        --per-token 8 --loads 80 400 640 1200 3200x1+300

and without ``--grouped``, ``--rows 256``: the form its layers took until PR
48.  ``walk_form``'s two constants stand on both forms at the three models'
shapes: ``dropless.GROUPED_FROM_SHARE`` on Mellum's command above with
``--rows 1024`` and with ``--grouped --rows 8192`` and the defaults with and
without ``--grouped --rows 8192`` (PERF.md section 6, PR 40), ``dropless.
GROUPED_FROM_TABLE_BYTES`` on Laguna's both ways beside the defaults both
ways at ``--loads 40 350 704`` (BENCH_KERNELS.md, PR 48).  Of the
grouped form each line also gives the kernels alone (``kernel_ms``, the
``grouped_matmul`` events of a profiler session) and their share of the
MXU's peak at the slots' rows (forward, the recomputed forward and the four
transposed products: ``4 * 2 * (L * W_in + F * L)`` FLOPs a slot).

Before a chunk size's loads, one line of what the walk costs a program's
set-up, on the host: the seconds to trace and to lower (not compile) the
forward and backward of one walk and of ``--layers`` walks one after the
other, as a model's layers are.  What grows with the layers is what every
layer pays again; the grouped form's jitted forward, backward and plan are
traced and lowered once (PERF.md section 6, PR 41).

A load ``n`` gives every held expert ``n`` slots, ``nxk`` gives ``k`` experts
``n`` each, and ``+`` joins such parts (experts in order).  One JSON line a
(chunk size, load): ms a call, which is what one expert layer of a step pays
for its routed part (the walk keeps only its inputs, so a recomputed layer
runs it once forward and once backward).
"""

import argparse
import collections
import functools
import glob
import gzip
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from deeperspeed_tpu.moe import dropless
from deeperspeed_tpu.ops import pallas_gmm


def counts_of(load, held):
    """``"8000x1+704"`` -> [8000, 704, 704, ...] for ``held`` experts."""
    counts = []
    for part in load.split("+"):
        n, _, k = part.partition("x")
        counts += [int(n)] * (int(k) if k else held - len(counts))
    return (counts + [0] * held)[:held]


def routing(counts, tokens, per_token, seed=1):
    """Each expert's slots on tokens drawn without order, a token's choices
    past its first ``per_token`` taken back -> (weights, chosen) [tokens,
    held]."""
    rng = np.random.default_rng(seed)
    chosen = np.zeros((tokens, len(counts)), bool)
    for e, n in enumerate(counts):
        chosen[rng.permutation(tokens)[:n], e] = True
    chosen &= np.cumsum(chosen, axis=1) <= per_token
    weights = np.where(chosen, rng.uniform(0.1, 1.0, chosen.shape), 0.0)
    return jnp.asarray(weights, jnp.float32), jnp.asarray(chosen)


PEAK_FLOPS = {"TPU v5 lite": 197e12}


def busiest(run, calls, top):
    """Device ms a call by operation, the ``top`` largest, and the Pallas
    kernels' ms a call, from a profiler session around ``calls`` runs."""
    with tempfile.TemporaryDirectory() as where:
        with jax.profiler.trace(where):
            for _ in range(calls):
                out = run()
            jax.block_until_ready(out)
        path = glob.glob(where + "/plugins/profile/*/*.trace.json.gz")[0]
        events = json.load(gzip.open(path))["traceEvents"]
    thread = {(e["pid"], e["tid"]): e["args"]["name"] for e in events
              if e.get("ph") == "M" and e.get("name") == "thread_name"}
    total, count = collections.Counter(), collections.Counter()
    for e in events:
        if e.get("ph") == "X" and thread.get(
                (e["pid"], e["tid"])) == "XLA Ops":
            total[e["name"]] += e["dur"]
            count[e["name"]] += 1
    kernels = sum(us for name, us in total.items()
                  if name.startswith("grouped_matmul"))
    return [{"op": name, "ms": round(us / calls / 1e3, 3),
             "calls": count[name] // calls}
            for name, us in total.most_common(top)], kernels / calls / 1e3


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--latent", type=int, default=1024)
    ap.add_argument("--intermediate", type=int, default=2688)
    ap.add_argument("--held", type=int, default=8)
    ap.add_argument("--gated", action="store_true",
                    help="gated experts (silu(gate) * up on a fused gate | "
                         "up matrix) in place of relu2")
    ap.add_argument("--grouped", action="store_true",
                    help="walk the sorted slots --rows at a time through the "
                         "grouped matmul (the heavily loaded form) in place "
                         "of an expert's slots")
    ap.add_argument("--per-token", type=int, default=8,
                    help="the most slots a token has: min(k, held)")
    ap.add_argument("--tile", type=int, default=pallas_gmm.TILE_ROWS,
                    help="rows a tile of the grouped matmul")
    ap.add_argument("--rows", type=int, nargs="+",
                    default=[dropless.ROWS_PER_CHUNK])
    ap.add_argument("--loads", nargs="+",
                    default=["0", "40x1", "40", "704", "8000x1+704"])
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--layers", type=int, default=4,
                    help="walks in the program whose trace and lowering "
                         "are timed beside one walk's")
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args(argv)
    pallas_gmm.TILE_ROWS = args.tile

    T, L, F, H = args.tokens, args.latent, args.intermediate, args.held
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (T, L), jnp.bfloat16)
    activation = dropless.gated_silu if args.gated else dropless.relu2
    w_in = (0.02 * jax.random.normal(
        keys[1], (H, L, 2 * F if args.gated else F))).astype(jnp.bfloat16)
    w_out = (0.02 * jax.random.normal(keys[2], (H, F, L))).astype(jnp.bfloat16)
    g = jax.random.normal(keys[3], (T, L), jnp.float32)

    def loss(rows, x, held_w, w_in, w_out, is_chosen):
        out, counted = dropless.routed_experts(x, held_w, is_chosen, w_in,
                                               w_out, activation, rows,
                                               args.grouped, args.per_token)
        return jnp.sum(out * g), counted

    def set_up_seconds(rows, layers, *operands):
        """Host seconds to trace and to lower ``layers`` walks, forward and
        backward, each on the one before's result."""
        def through(x, *rest):
            for _ in range(layers):
                x = jax.grad(lambda x: loss(rows, x, *rest)[0])(x).astype(
                    x.dtype)
            return x

        jax.clear_caches()
        start = time.perf_counter()
        traced = jax.jit(through).trace(*operands)
        middle = time.perf_counter()
        traced.lower()
        return (round(middle - start, 3),
                round(time.perf_counter() - middle, 3))

    print(json.dumps({"device": jax.devices()[0].device_kind, "tokens": T,
                      "latent": L, "intermediate": F, "held": H,
                      "gated": args.gated, "grouped": args.grouped,
                      "per_token": args.per_token, "tile": args.tile}),
          flush=True)
    for rows in args.rows:
        step = jax.jit(jax.value_and_grad(functools.partial(loss, rows),
                                          argnums=(0, 1, 2, 3), has_aux=True))
        held_w, is_chosen = routing(counts_of(args.loads[0], H), T,
                                    args.per_token)
        operands = (x, held_w, w_in, w_out, is_chosen)
        # the first trace of a process pays for its imports: not kept
        _, one, many = [set_up_seconds(rows, n, *operands)
                        for n in (1, 1, args.layers)]
        print(json.dumps({"rows_per_chunk": rows, "trace_s": one[0],
                          "lower_s": one[1], "layers": args.layers,
                          "trace_s_layers": many[0],
                          "lower_s_layers": many[1]}), flush=True)
        for load in args.loads:
            held_w, is_chosen = routing(counts_of(load, H), T,
                                        args.per_token)

            def run():
                return step(x, held_w, w_in, w_out, is_chosen)

            (_, counted), _ = jax.block_until_ready(run())
            start = time.perf_counter()
            for _ in range(args.calls):
                out = run()
            jax.block_until_ready(out)
            line = {
                "rows_per_chunk": rows, "load": load,
                "slots": int(counted["slots"]), "done": int(counted["done"]),
                "ms": round(1e3 * (time.perf_counter() - start) / args.calls,
                            3)}
            if args.grouped or load == args.loads[-1]:
                ops, kernel_ms = busiest(run, 5, args.top)
            if args.grouped:
                flops = 8 * line["slots"] * (L * w_in.shape[-1] + F * L)
                peak = PEAK_FLOPS.get(jax.devices()[0].device_kind)
                line.update(computed=int(counted["computed"]),
                            kernel_ms=round(kernel_ms, 3))
                if peak and kernel_ms:
                    line["kernel_mxu_share"] = round(
                        flops / (kernel_ms * 1e-3) / peak, 4)
            print(json.dumps(line), flush=True)
        print(json.dumps({"rows_per_chunk": rows, "load": load,
                          "busiest": ops}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

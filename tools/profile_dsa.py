"""Time learned sparse attention's parts alone on the chip
(``ops/attention/dsa.py`` over ``pallas_dsa.py``), at one shape: the
selection (scores + each row's best), the attention's three kernels each
alone (forward, dq, dk/dv) and through ``dsa_attention`` forward and
forward + backward, the indexer's loss with its gradients (the kernels
``dsa_head_probs`` + ``dsa_loss_grads``, and beside them the plain form the
CPU runs).  One JSON line a part: ms a call of the whole program by the
host's clock over ``--calls`` calls, the device's busiest operations from a
profiler session and under ``kernels_ms`` each kernel's device ms a call
alone with their sum (the loss's lines: ``dsa_head_probs`` beside
``dsa_loss_grads``), the attention's with the plan they ran
(``pallas_dsa.attend_plan``), the loss's with ``dsa_head_probs``'
(``pallas_dsa.head_probs_plan``); with ``--check`` first, at ``--check-seq``
rows, the kernels' outputs beside the plain forms' (the selection exactly,
the rest by the largest difference over the largest entry).

    python tools/profile_dsa.py                 # the Keye cell's shape
    python tools/profile_dsa.py --seq 8192 --rows 512 1024 --cols 1024
    python tools/profile_dsa.py --loss-rows 128 256 512     # the loss's own
    python tools/profile_dsa.py --probs-heads 1 2 4 8       # dsa_head_probs'
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from deeperspeed_tpu.ops.attention import dsa, pallas_dsa
from tools.profile_moe_walk import busiest

KERNELS = (pallas_dsa.SELECT, pallas_dsa.ATTENTION, pallas_dsa.HEAD_PROBS,
           pallas_dsa.LOSS_GRADS)


def operands(args, seq):
    B, N, KV, D = args.batch, args.heads, args.kv_heads, args.head_dim
    HI, DI = args.indexer_heads, args.indexer_dim
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    dt = args.dtype
    q = jax.random.normal(keys[0], (B, seq, N, D), dt)
    k = jax.random.normal(keys[1], (B, seq, KV, D), dt)
    v = jax.random.normal(keys[2], (B, seq, KV, D), dt)
    qi = jax.random.normal(keys[3], (B, seq, HI, DI), dt)
    ki = jax.random.normal(keys[4], (B, seq, DI), dt)
    w = jax.random.normal(keys[5], (B, seq, HI)) * (HI * DI) ** -0.5
    do = jax.random.normal(keys[6], (B, seq, N, D), dt)
    return q, k, v, qi, ki, w, do


def parts(topk, use):
    def select(qi, ki, w):
        return dsa.dsa_select(qi, ki, w, topk, use_pallas=use)

    def sel_of(words, counts, seq):
        return dsa.Selection(words, counts, pallas_dsa.sel_layout(seq), seq)

    def attend(q, k, v, words, counts):
        return dsa.dsa_attention(q, k, v, sel_of(words, counts, q.shape[1]),
                                 use_pallas=use)

    def attend_grad(q, k, v, words, counts, do):
        def f(q, k, v):
            o, _ = attend(q, k, v, words, counts)
            return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32))
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    def loss(qi, ki, w, q, k, lse, words, counts):
        return jax.value_and_grad(
            lambda qi, ki, w: dsa.dsa_indexer_loss(
                qi, ki, w, q, k, lse, sel_of(words, counts, q.shape[1]),
                use_pallas=use), argnums=(0, 1, 2))(qi, ki, w)

    return select, attend, attend_grad, loss


def timed(name, fn, args, calls, top, **told):
    fn = jax.jit(fn)
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    ms = 1e3 * (time.perf_counter() - t0) / calls
    ops, _ = busiest(lambda: fn(*args), max(2, calls // 4), None)
    # an instruction is named after its innermost scope: ``<kernel>.<n>``
    kernels = {k: round(sum(op["ms"] for op in ops
                            if op["op"].split(".")[0] == k), 3)
               for k in KERNELS}
    print(json.dumps({"part": name, "ms_a_call": round(ms, 3), **told,
                      "first_call_s": round(first, 1),
                      "kernels_ms": {k: v for k, v in kernels.items() if v},
                      "kernels_sum_ms": round(sum(kernels.values()), 3),
                      "busiest": ops[:top]}), flush=True)
    return out


def attention_kernels(args, q, k, v, do, sel, lay, plan, tag):
    """The three ``dsa_attention`` kernels each alone on ``dsa.py``'s
    operands (a call behind ``bwd_call`` whose result is not used is not
    run) -> the rows' log-sum-exp."""
    B, S, N, D = q.shape
    told = dict(plan=plan._asdict())

    def flat(t):
        return dsa._pad_rows(t.reshape(B, S, -1), lay.padded)

    qp, k, v, do = (flat(t) for t in (q * jnp.asarray(D ** -0.5, q.dtype),
                                      k, v, do))
    o, lse = timed(
        f"dsa_attention forward {tag}",
        lambda *a: pallas_dsa.fwd_call(*a, N, lay, plan),
        (qp, k, v, sel.words, sel.counts), args.calls, args.top, **told)
    delta = jnp.swapaxes(jnp.sum(
        (do.astype(jnp.float32) * o.astype(jnp.float32)).reshape(
            B, lay.padded, N, D), axis=-1), 1, 2).reshape(B * N, 1, -1)
    operands = (qp, k, v, do, lse, delta, sel.words, sel.counts)
    for name, part in (("dq", slice(0, 1)), ("dk/dv", slice(1, 3))):
        timed(f"dsa_attention {name} {tag}",
              lambda *a, part=part: pallas_dsa.bwd_call(*a, N, lay, plan)[part],
              operands, args.calls, args.top, **told)
    return lse


def rel(a, b):
    a, b = (jnp.asarray(t, jnp.float32) for t in (a, b))
    return float(jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)),
                                                        1e-30))


def check(args):
    seq, topk = args.check_seq, args.check_topk
    q, k, v, qi, ki, w, do = operands(args, seq)
    got, want = ({}, {})
    for out, use in ((got, True), (want, False)):
        select, attend, attend_grad, loss = (jax.jit(f) for f in parts(
            topk, use))
        sel = select(qi, ki, w)
        out["words"] = sel.words
        # both sides attend over the kernel's selection
        words, counts = got["words"], got.setdefault("counts", sel.counts)
        out["o"], out["lse"] = attend(q, k, v, words, counts)
        out["dq"], out["dk"], out["dv"] = attend_grad(q, k, v, words, counts,
                                                      do)
        out["kl"], (out["dqi"], out["dki"], out["dw"]) = loss(
            qi, ki, w, q, k, got["lse"], words, counts)
    same = bool(jnp.all(got["words"] == want["words"]))
    print(json.dumps({"check": "kernels beside the plain forms", "seq": seq,
                      "topk": topk, "selection_equal": same,
                      "pairs_selected": int(jnp.sum(got["counts"])),
                      **{name: rel(got[name], want[name])
                         for name in ("o", "dq", "dk", "dv", "kl", "dqi",
                                      "dki", "dw")}}), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--indexer-heads", type=int, default=16)
    ap.add_argument("--indexer-dim", type=int, default=64)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--top", type=int, default=6)
    ap.add_argument("--rows", nargs="+", default=["kept"],
                    help="rows of the attention kernels' own block "
                    "(pallas_dsa.attend_plan), to sweep; 'kept' = the "
                    "module's own")
    ap.add_argument("--cols", type=int, default=None,
                    help="columns of a dk/dv program's block in the sweep")
    ap.add_argument("--side-by-side", type=int, default=None,
                    help="query heads of a group side by side in a program")
    ap.add_argument("--span", type=int, default=None,
                    help="rows of k and v resident in the forward and dq")
    ap.add_argument("--loss-rows", nargs="+", default=["kept"],
                    help="rows of the loss kernel's block, to sweep (they "
                    "divide the other kernels' block)")
    ap.add_argument("--probs-heads", nargs="+", type=int, default=[],
                    help="query heads side by side in dsa_head_probs' body "
                    "(pallas_dsa.head_probs_plan), to sweep after the "
                    "module's own")
    ap.add_argument("--no-loss", action="store_true",
                    help="the selection and the attention alone")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--check-seq", type=int, default=2048)
    ap.add_argument("--check-topk", type=int, default=256)
    args = ap.parse_args(argv)
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    if args.check:
        check(args)
    q, k, v, qi, ki, w, do = operands(args, args.seq)
    select, attend, attend_grad, _ = parts(args.topk, True)
    sel = timed("select", select, (qi, ki, w), args.calls, args.top)
    print(json.dumps({"pairs_selected": int(sel.pairs_selected()),
                      "pairs_visited": int(sel.pairs_visited()),
                      "tiles_skipped": int(sel.tiles_skipped())}))
    kept, lay = pallas_dsa.attend_plan, pallas_dsa.sel_layout(args.seq)
    for rows in args.rows:
        # the sweep's plan is this tool's own: the module derives one
        if rows != "kept" and (int(rows) % lay.rows or lay.padded % int(rows)):
            raise SystemExit(f"a block of {rows} rows is no whole groups of "
                             f"{lay.rows} rows that divide {lay.padded}")
        plan = kept(lay, args.head_dim, args.heads // args.kv_heads,
                    args.dtype, None if rows == "kept" else int(rows),
                    args.span, args.cols, args.side_by_side)
        pallas_dsa.attend_plan = lambda *a, plan=plan: plan
        _, attend, attend_grad, _ = parts(args.topk, True)  # traced at a plan
        tag = f"rows={rows}"
        lse = attention_kernels(args, q, k, v, do, sel, lay, plan, tag)
        for name, fn, more in ((f"attend forward {tag}", attend, ()), (
                f"attend forward + backward {tag}", attend_grad, (do,))):
            timed(name, fn, (q, k, v, sel.words, sel.counts) + more,
                  args.calls, args.top, plan=plan._asdict())
    pallas_dsa.attend_plan = kept
    if args.no_loss:
        return
    loss_args = (qi, ki, w, q, k, lse, sel.words, sel.counts)
    own = pallas_dsa.loss_rows
    for block in args.loss_rows:
        # the loss kernel's block is its own
        pallas_dsa.loss_rows = own if block == "kept" else (
            lambda layout, r=int(block): r)
        pallas_dsa.loss_grads_call.clear_cache()        # traced at a block
        timed(f"indexer loss + gradients loss_rows={block}",
              parts(args.topk, True)[3], loss_args,     # traced at a block
              args.calls, args.top)
    pallas_dsa.loss_rows = own
    pallas_dsa.loss_grads_call.clear_cache()
    own_plan = pallas_dsa.head_probs_plan
    for side in args.probs_heads:
        plan = own_plan(lay, args.heads // args.kv_heads, side)
        pallas_dsa.head_probs_plan = lambda *a, plan=plan: plan
        timed(f"indexer loss + gradients probs_heads={side}",
              parts(args.topk, True)[3], loss_args,     # traced at a plan
              args.calls, args.top, probs_plan=plan._asdict())
    pallas_dsa.head_probs_plan = own_plan
    timed("indexer loss + gradients, the plain form",
          parts(args.topk, False)[3], loss_args, max(2, args.calls // 4),
          args.top)


if __name__ == "__main__":
    sys.exit(main())

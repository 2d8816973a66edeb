"""Time the compressed attention's mixing alone on the chip (ZAYA1's CCA,
``ops/attention/cca.py::cca_mix``): the plain form and the kernel pair
(``pallas_cca.py``), each forward alone, backward alone (the gradient's
program: the pair's backward recomputes from the streams and needs no
forward; the plain form's runs what of the forward its backward reads) and
forward + backward (outputs and gradients both returned), with EVERY
operand differentiated (the three streams and the five parameters, as a
training step does).  One JSON line a form: ms a call by the host's clock
over ``--calls`` calls, the bytes the streams need (one read and one write
forward; ``qt``, ``kt`` and the three cotangents read and the three
gradients written, backward) and their share of the HBM roofline.

    python tools/profile_cca_mix.py                 # the ZAYA1-8B cell's shapes
    python tools/profile_cca_mix.py --rows 128 256 512   # the pair's row block
    python tools/profile_cca_mix.py --check         # the pair beside the plain form
    python tools/profile_cca_mix.py --conv-forms    # the per-head convolution's forms
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from deeperspeed_tpu.ops.attention import pallas_cca
from deeperspeed_tpu.ops.attention.cca import cca_mix
from deeperspeed_tpu.ops.ssm import causal_headwise_conv1d

#: bytes/s of a v5e's HBM (Google Cloud, "TPU v5e")
HBM_BYTES_PER_S = 819e9
OPERANDS = ("qt", "kt", "v", "conv_taps", "conv_bias", "head_conv_kernel",
            "head_conv_bias", "k_temperature")


def by_einsum(x, kernel, bias):
    """The stream and each shift against ``[heads, d, d]`` by one
    ``einsum`` over ``[B, S, heads, d]``."""
    width, heads, d, _ = kernel.shape
    batch, seq, channels = x.shape
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0))).reshape(
        batch, seq + width - 1, heads, d)
    y = sum(jnp.einsum("bshd,hde->bshe", padded[:, k:k + seq],
                       kernel[k].astype(x.dtype),
                       preferred_element_type=x.dtype).astype(jnp.float32)
            for k in range(width)).reshape(batch, seq, channels)
    return (y + bias).astype(x.dtype)


def by_grouped_conv(x, kernel, bias):
    """``lax.conv_general_dilated`` with a group a head."""
    width, heads, d, _ = kernel.shape
    # [K, in a group, groups x out a group]
    rhs = jnp.transpose(kernel, (0, 2, 1, 3)).reshape(width, d, heads * d)
    y = jax.lax.conv_general_dilated(
        x, rhs.astype(x.dtype), window_strides=(1,),
        padding=[(width - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=heads)
    return (y.astype(jnp.float32) + bias).astype(x.dtype)


def _weighted(fn, weights):
    """``fn``'s outputs against fixed weights, summed: a scalar whose
    gradient reaches every operand with cotangents that are not all one."""
    def loss(*args):
        outs = jax.tree_util.tree_leaves(fn(*args))
        return sum(jnp.sum(o.astype(jnp.float32) * w)
                   for o, w in zip(outs, weights))
    return loss


def timed(run, args, calls):
    jax.block_until_ready(run(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = run(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / calls


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--rows", type=int, nargs="*", default=[],
                    help="row blocks to time the pair at (default: its own)")
    ap.add_argument("--check", action="store_true",
                    help="the pair's outputs and gradients beside the plain "
                         "form's, the largest difference of each")
    ap.add_argument("--conv-forms", action="store_true",
                    help="the per-head convolution alone in three forms")
    args = ap.parse_args(argv)
    B, S, nq, kv, d = (args.batch, args.seq, args.heads, args.kv_heads,
                       args.head_dim)
    c = (nq + kv) * d
    keys = jax.random.split(jax.random.PRNGKey(0), 9)
    bf16 = jnp.bfloat16
    qt = jax.random.normal(keys[0], (B, S, nq * d), bf16)
    kt = jax.random.normal(keys[1], (B, S, kv * d), bf16)
    v = jax.random.normal(keys[2], (B, S, kv * d), bf16)
    taps = jax.random.normal(keys[3], (2, c)) * 2 ** -0.5
    taps_bias = jax.random.normal(keys[4], (c,)) * 0.02
    kernel = jax.random.normal(keys[5], (2, nq + kv, d, d)) * (2 * d) ** -0.5
    head_bias = jax.random.normal(keys[6], (c,)) * 0.02
    temperature = 1 + 0.1 * jax.random.normal(keys[7], (kv,))
    operands = (qt, kt, v, taps, taps_bias, kernel, head_bias, temperature)
    weights = [jax.random.normal(k, t.shape, bf16)
               for k, t in zip(jax.random.split(keys[8], 3), (qt, kt, v))]
    device = jax.devices()[0]
    said = {"shape": [B, S, nq * d, kv * d, kv * d],
            "device": device.device_kind}
    if args.conv_forms:
        z = jnp.concatenate([qt, kt], axis=-1)
        for name, fn in (("by_head", causal_headwise_conv1d),
                         ("einsum", by_einsum),
                         ("grouped_conv", by_grouped_conv)):
            run = jax.jit(jax.grad(_weighted(fn, [1.0]), argnums=(0, 1)))
            print(json.dumps({"headwise_conv": name, "fwd_bwd_ms": timed(
                run, (z, kernel, head_bias), args.calls), **said}),
                flush=True)
        return 0

    def mix(use_pallas):
        return lambda *a: cca_mix(
            *a, heads=nq, kv_heads=kv, rotary_dim=d // 2, rope_theta=5e6,
            eps=1e-5, use_pallas=use_pallas)

    def grads(fn):
        return jax.jit(jax.grad(_weighted(fn, weights),
                                argnums=tuple(range(len(operands)))))

    def both(fn):
        def run(*a):
            out, pull = jax.vjp(fn, *a)
            return out, pull(tuple(w.astype(o.dtype)
                                   for w, o in zip(weights, out)))
        return jax.jit(run)

    streams = (qt.size + kt.size + v.size) * qt.dtype.itemsize
    backward = 3 * streams - v.size * v.dtype.itemsize
    forms = [("plain", None, mix(False))]
    for rows in args.rows or [None]:
        forms.append(("pallas", rows or pallas_cca.mix_rows(S), mix(True)))
    own = pallas_cca.ROWS
    reference = None
    for name, rows, fn in forms:
        pallas_cca.ROWS = rows or own       # the block ``mix_rows`` gives
        jax.clear_caches()
        fwd_ms = timed(jax.jit(fn), operands, args.calls)
        bwd_ms = timed(grads(fn), operands, args.calls)
        both_ms = timed(both(fn), operands, args.calls)

        def share(needed, ms):
            return 100 * needed / HBM_BYTES_PER_S / (ms / 1e3)

        line = {"cca_mix": name, "rows": rows, "fwd_ms": fwd_ms,
                "bwd_ms": bwd_ms, "fwd_bwd_ms": both_ms,
                "fwd_bytes": 2 * streams, "bwd_bytes": backward,
                "fwd_hbm_roofline_pct": share(2 * streams, fwd_ms),
                "bwd_hbm_roofline_pct": share(backward, bwd_ms),
                "fwd_bwd_hbm_roofline_pct": share(2 * streams + backward,
                                                  both_ms),
                "differentiated": list(OPERANDS), **said}
        if args.check:
            got = (jax.jit(fn)(*operands), grads(fn)(*operands))
            if reference is None:
                reference = got
            else:
                names = ("q", "k", "v_out") + tuple("d_" + n
                                                    for n in OPERANDS)
                line["max_abs_diff_vs_plain"] = {
                    n: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                             - b.astype(jnp.float32))))
                    for n, a, b in zip(names, got[0] + got[1],
                                       reference[0] + reference[1])}
                line["max_abs_plain"] = {
                    n: float(jnp.max(jnp.abs(b.astype(jnp.float32))))
                    for n, b in zip(names, reference[0] + reference[1])}
        print(json.dumps(line), flush=True)
    pallas_cca.ROWS = own
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Time the compressed attention's mixing alone on the chip (ZAYA1's CCA,
``models/zaya.py::cca_mix``): the per-head causal convolution in three forms
-- the module's own (``ops/ssm.py::causal_headwise_conv1d``: a head at a time
on lane-block slices), the stream and its shift against ``[heads, d, d]`` by
one ``einsum`` each, and ``lax.conv_general_dilated`` with
``feature_group_count`` -- and the whole of ``cca_mix``, each forward +
backward.  One JSON line a form: ms a call by the host's clock over
``--calls`` calls.

    python tools/profile_cca_mix.py        # the ZAYA1-8B cell's shapes
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from deeperspeed_tpu.models.zaya import cca_mix
from deeperspeed_tpu.ops.ssm import causal_headwise_conv1d


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


def timed(fn, args, calls):
    run = jax.jit(jax.grad(
        lambda *a: jnp.sum(jnp.concatenate(jax.tree_util.tree_leaves(
            fn(*a)), axis=-1).astype(jnp.float32)), argnums=(0, 1)))
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
    args = ap.parse_args(argv)
    B, S, nq, kv, d = (args.batch, args.seq, args.heads, args.kv_heads,
                       args.head_dim)
    c = (nq + kv) * d
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    z = jax.random.normal(keys[0], (B, S, c), jnp.bfloat16)
    kernel = jax.random.normal(keys[1], (2, nq + kv, d, d)) * (2 * d) ** -0.5
    bias = jnp.zeros((c,), jnp.float32)
    device = jax.devices()[0]
    for name, fn in (("by_head", causal_headwise_conv1d),
                     ("einsum", by_einsum),
                     ("grouped_conv", by_grouped_conv)):
        print(json.dumps({"headwise_conv": name, "fwd_bwd_ms": timed(
            fn, (z, kernel, bias), args.calls), "shape": [B, S, c],
            "device": device.device_kind}), flush=True)
    qt, kt, v = z[..., :nq * d], z[..., nq * d:], z[..., :kv * d]
    taps = jax.random.normal(keys[2], (2, c)) * 2 ** -0.5

    def mix(qt, kernel, kt, v):
        return cca_mix(qt, kt, v, taps, bias, kernel, bias,
                       jnp.ones((kv,)), heads=nq, kv_heads=kv,
                       rotary_dim=d // 2, rope_theta=5e6, eps=1e-5)

    print(json.dumps({"cca_mix": "whole", "fwd_bwd_ms": timed(
        mix, (qt, kernel, kt, v), args.calls),
        "device": device.device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

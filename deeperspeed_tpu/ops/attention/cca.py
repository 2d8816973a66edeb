"""CCA's latent mixing (ZAYA1's compressed convolutional attention,
arXiv:2510.04476): everything between the latent's three projections and
the attention kernel, on ``[B, S, heads x d]`` as the projections leave it
and the kernel reads it (a head is a run of whole lane blocks: nothing is
laid out anew).

* the later half of v's channels are the PREVIOUS token's;
* the packed ``[qt | kt]`` goes through a causal depthwise convolution and
  then a causal convolution whose channels mix inside each head
  (``ops/ssm.py``), both two rows wide;
* the mean of a query head and its KV head from BEFORE the convolutions is
  added back (a KV head takes the mean over its group);
* every head is divided by its RMS, k times a learned temperature a KV
  head, rotary on the first ``rotary_dim`` of a head.

On a TPU, where the shapes are whole tiles, all of it is one kernel pair
(``pallas_cca.py``: a row block goes from the projections' output to q, k,
v in VMEM); elsewhere, and for shapes the kernels do not take, the plain
form: float32 from the convolutions on, about a dozen passes over the
packed width.  The equations with what the published config leaves to
assumption: ``benchmarks/reference/zaya_ref.py``.
"""

import functools

import jax
import jax.numpy as jnp

from ...accelerator import get_accelerator
from ...parallel.topology import BATCH_AXES
from ..pallas_utils import shard_kernel
from ..ssm import causal_depthwise_conv1d, causal_headwise_conv1d
from ..transformer.rope import _rotate_half, rotary_tables
from . import pallas_cca


def _a_step_later(x):
    """``y[:, t] = x[:, t - 1]``, zeros before the sequence."""
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def _plain(qt, kt, v, taps, taps_bias, head_kernel, head_bias, temperature,
           cos, sin, heads, kv_heads, eps):
    """The equations a pass at a time: the depthwise sums float32, rounded
    to the stream's type; the per-head products from operands of that type,
    rounded to it, their sum with the bias float32 and rounded again;
    float32 from there on, q and k rounded once at the end."""
    dtype, f32 = qt.dtype, jnp.float32
    group = heads // kv_heads
    rotary_dim = cos.shape[-1]
    own, previous = jnp.split(v, 2, axis=-1)
    v = jnp.concatenate([own, _a_step_later(previous)], axis=-1)
    z = causal_depthwise_conv1d(jnp.concatenate([qt, kt], axis=-1), taps,
                                taps_bias)
    z = causal_headwise_conv1d(z, head_kernel, head_bias)
    # a head at a time, as slices of the last axis (``ops/ssm.py``'s group
    # norm says why not a reshape)
    z = jnp.split(z.astype(f32), heads + kv_heads, axis=-1)
    q_before = jnp.split(qt.astype(f32), heads, axis=-1)
    k_before = jnp.split(kt.astype(f32), kv_heads, axis=-1)
    m_q = [(q_before[j] + k_before[j // group]) / 2 for j in range(heads)]
    m_k = [sum(m_q[i * group:(i + 1) * group]) / group
           for i in range(kv_heads)]

    def head(x, scale=None):
        x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + eps)
        if scale is not None:
            x = x * scale
        turn, rest = x[..., :rotary_dim], x[..., rotary_dim:]
        return jnp.concatenate([turn * cos + _rotate_half(turn) * sin, rest],
                               axis=-1)

    q = [head(z[j] + m_q[j]) for j in range(heads)]
    k = [head(z[heads + i] + m_k[i], temperature[i].astype(f32))
         for i in range(kv_heads)]
    return (jnp.concatenate(q, axis=-1).astype(dtype),
            jnp.concatenate(k, axis=-1).astype(dtype), v)


def cca_mix(qt, kt, v, taps, taps_bias, head_kernel, head_bias, temperature,
            *, heads, kv_heads, rotary_dim, rope_theta, eps,
            use_pallas=None):
    """``qt`` [B, S, n_q d], ``kt`` and ``v`` [B, S, n_kv d] -> (q, k, v) of
    the same shapes and type: the value shift, the two convolutions on the
    packed ``[qt | kt]`` (``taps`` [2, c] with ``taps_bias`` [c];
    ``head_kernel`` [2, n_q + n_kv, d, d] with ``head_bias`` [c]), the q-k
    mean from before them, unit-RMS heads, k's ``temperature`` [n_kv],
    rotary on the first ``rotary_dim`` of a head.  ``use_pallas`` None: the
    kernel pair on a TPU where the shapes are whole tiles
    (``pallas_cca.compiles_for_tpu``), else the plain form; True asks for
    the kernels (off a TPU in interpret mode)."""
    from ...telemetry.trace import count_kernel_path

    S, d = qt.shape[1], qt.shape[2] // heads
    if use_pallas is None:
        use_pallas = (get_accelerator().use_pallas_kernels()
                      and qt.dtype == kt.dtype == v.dtype
                      and taps.shape[0] == head_kernel.shape[0] == 2
                      and pallas_cca.compiles_for_tpu(S, d, rotary_dim))
    cos, sin = rotary_tables(jnp.arange(S), rotary_dim, rope_theta)
    cos, sin = cos[:, 0], sin[:, 0]                      # [S, rotary_dim]
    if use_pallas:
        # a sequence is whole on a shard (the halo is a block view's), the
        # width too (both means stay inside a KV group)
        streams = (qt, kt, v)
        whole = (taps, taps_bias, head_kernel, head_bias, temperature, cos,
                 sin)
        return shard_kernel(
            functools.partial(pallas_cca.mix, heads=heads, kv_heads=kv_heads,
                              eps=eps), streams + whole,
            ((BATCH_AXES, None, None),) * 3
            + tuple((None,) * t.ndim for t in whole), out_like=(0, 1, 2))
    count_kernel_path(pallas_cca.KERNEL_NAME, "plain")
    return _plain(qt, kt, v, taps, taps_bias, head_kernel, head_bias,
                  temperature, cos, sin, heads, kv_heads, eps)

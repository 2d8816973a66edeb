"""The state-space operators on the CPU: the chunked SSD scan against the
recurrence one step at a time (values and every gradient) at lengths that
are and are not multiples of the chunk, a head range against the whole, the
causal depthwise convolution and the gated group norm against plain numpy."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu.ops.ssm import (causal_depthwise_conv1d,
                                     gated_group_rms_norm, ssd_scan)

B, HD, P, G, N = 2, 4, 8, 2, 16


def ssd_recurrence(x, dt, a, b, c, d=None):
    """The recurrence of ``ops/ssm.py``'s docstring one step at a time, in
    float32.  Shapes as ``ssd_scan``."""
    f32 = jnp.float32
    B, S, Hd, P = x.shape
    G, N = b.shape[2:]
    rep = Hd // G
    x, dt, b, c = (t.astype(f32) for t in (x, dt, b, c))
    b, c = (jnp.repeat(t, rep, axis=2) for t in (b, c))     # [B,S,Hd,N]

    def step(state, op):
        xt, dtt, bt, ct = op
        state = (state * jnp.exp(dtt * a.astype(f32))[..., None, None]
                 + (dtt[..., None] * xt)[..., None] * bt[..., None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct,
                                 precision="highest")

    _, y = jax.lax.scan(step, jnp.zeros((B, Hd, P, N), f32),
                        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    y = jnp.moveaxis(y, 0, 1)
    if d is not None:
        y = y + x * d.astype(f32)[:, None]
    return y


def _operands(seed, seq):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (B, seq, HD, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, seq, HD)) - 1.0)
    a = -jnp.exp(jax.random.normal(ks[2], (HD,)))
    b = jax.random.normal(ks[3], (B, seq, G, N))
    c = jax.random.normal(ks[4], (B, seq, G, N))
    return x, dt, a, b, c, jnp.linspace(0.5, 1.5, HD)


@pytest.mark.parametrize("seq", [1, 7, 16, 33, 50, 64, 100])
def test_chunked_scan_is_the_recurrence(seq):
    ops = _operands(seq, seq)
    got = ssd_scan(*ops, chunk=16)
    want = ssd_recurrence(*ops)
    assert got.shape == (B, seq, HD, P)
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("seq", [24, 48])
def test_chunked_scan_gradients_are_the_recurrences(seq):
    ops = _operands(100 + seq, seq)
    every = tuple(range(6))
    got = jax.grad(lambda *t: jnp.sum(jnp.sin(ssd_scan(*t, chunk=16))),
                   argnums=every)(*ops)
    want = jax.grad(lambda *t: jnp.sum(jnp.sin(ssd_recurrence(*t))),
                    argnums=every)(*ops)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(w).max()))


@pytest.mark.parametrize("chunk", [4, 16, 128])
def test_the_chunk_size_changes_no_number(chunk):
    ops = _operands(3, 40)
    np.testing.assert_allclose(ssd_scan(*ops, chunk=chunk),
                               ssd_scan(*ops, chunk=8), rtol=2e-5, atol=2e-4)


def test_a_range_of_heads_gives_its_part_of_the_whole():
    """Heads are independent: the second group's heads, with that group's B
    and C, read exactly what they read inside the whole layer."""
    x, dt, a, b, c, d = _operands(5, 32)
    whole = ssd_scan(x, dt, a, b, c, d, chunk=16)
    half = ssd_scan(x[:, :, 2:], dt[:, :, 2:], a[2:], b[:, :, 1:],
                    c[:, :, 1:], d[2:], chunk=16)
    np.testing.assert_allclose(half, whole[:, :, 2:], rtol=1e-6, atol=1e-6)


def test_bfloat16_operands_keep_float32_decays():
    """The operands' type is the matmuls'; step sizes and decays stay
    float32, so a long sequence's state neither dies nor blows up."""
    x, dt, a, b, c, d = _operands(7, 96)
    low = ssd_scan(*(t.astype(jnp.bfloat16) for t in (x,)), dt, a,
                   b.astype(jnp.bfloat16), c.astype(jnp.bfloat16), d,
                   chunk=16)
    assert low.dtype == jnp.bfloat16
    want = ssd_recurrence(x, dt, a, b, c, d)
    err = float(jnp.sqrt(jnp.mean(jnp.square(low.astype(jnp.float32)
                                             - want))))
    assert err < 0.03 * float(jnp.sqrt(jnp.mean(jnp.square(want))))


@pytest.mark.parametrize("width", [2, 4])
def test_causal_depthwise_conv_by_hand(width):
    rng = np.random.default_rng(width)
    x = rng.normal(size=(2, 9, 5)).astype(np.float32)
    kernel = rng.normal(size=(width, 5)).astype(np.float32)
    bias = rng.normal(size=(5,)).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(9):
        for k in range(width):
            src = t - (width - 1) + k
            if src >= 0:
                want[:, t] += kernel[k] * x[:, src]
    want += bias
    got = causal_depthwise_conv1d(jnp.asarray(x), jnp.asarray(kernel),
                                  jnp.asarray(bias))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # causal: a later input moves no earlier output
    moved = x.copy()
    moved[:, 6:] += 1.0
    later = causal_depthwise_conv1d(jnp.asarray(moved), jnp.asarray(kernel))
    first = causal_depthwise_conv1d(jnp.asarray(x), jnp.asarray(kernel))
    np.testing.assert_array_equal(np.asarray(later)[:, :6],
                                  np.asarray(first)[:, :6])


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_gated_group_rms_norm_by_hand(groups):
    rng = np.random.default_rng(groups)
    y = rng.normal(size=(3, 8)).astype(np.float32)
    z = rng.normal(size=(3, 8)).astype(np.float32)
    scale = rng.normal(size=(8,)).astype(np.float32)
    gated = (y * z / (1.0 + np.exp(-z))).reshape(3, groups, 8 // groups)
    want = (gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(3, 8) * scale
    got = gated_group_rms_norm(jnp.asarray(y), jnp.asarray(z),
                               jnp.asarray(scale), groups, 1e-5)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

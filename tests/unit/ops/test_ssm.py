"""The state-space operators on the CPU: the chunked SSD scan against the
recurrence one step at a time (values and every gradient) at lengths that
are and are not multiples of the chunk, a head range against the whole, the
causal depthwise convolution and the gated group norm against plain numpy.
The scan's Pallas kernel pair (``ops/pallas_ssd.py``) runs in interpret mode
against the same recurrence and against the plain form; which of the two a
call of ``ssd_scan`` takes is decided from its shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu import telemetry
from deeperspeed_tpu.accelerator import get_accelerator
from deeperspeed_tpu.ops import pallas_ssd
from deeperspeed_tpu.ops.ssm import (causal_depthwise_conv1d,
                                     gated_group_rms_norm, ssd_scan)
from deeperspeed_tpu.parallel import topology

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


def _operands(seed, seq, batch=B, heads=HD, p=P, groups=G, n=N):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (batch, seq, heads, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, seq, heads)) - 1.0)
    a = -jnp.exp(jax.random.normal(ks[2], (heads,)))
    b = jax.random.normal(ks[3], (batch, seq, groups, n))
    c = jax.random.normal(ks[4], (batch, seq, groups, n))
    return x, dt, a, b, c, jnp.linspace(0.5, 1.5, heads)


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


# ------------------------------------------------- the Pallas kernel pair
@pytest.fixture
def kernels(monkeypatch):
    """``ssd_scan`` as on the chip: the accelerator says it has Pallas
    kernels (they run in interpret mode here) and no mesh is installed."""
    monkeypatch.setattr(type(get_accelerator()), "use_pallas_kernels",
                        lambda self: True)
    monkeypatch.setattr(topology, "_GLOBAL_MESH", None)
    monkeypatch.setattr(telemetry.trace, "_KERNEL_PATHS", {})


def _paths():
    return telemetry.kernel_paths().get("ssd_scan", {})


def _low(ops, dtype):
    """``x``, ``b``, ``c`` in ``dtype``; the rest stays float32."""
    x, dt, a, b, c, d = ops
    return x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype), d


def _rms(t):
    return float(jnp.sqrt(jnp.mean(jnp.square(t.astype(jnp.float32)))))


# head width, heads a group, groups, length (a multiple of the chunk of 128
# or not), operands' type
KERNEL_CASES = [
    (64, 2, 2, 256, jnp.float32),
    (64, 2, 1, 200, jnp.float32),
    (64, 16, 1, 128, jnp.float32),
    (128, 1, 2, 256, jnp.float32),
    (128, 2, 1, 130, jnp.float32),
    (64, 2, 2, 200, jnp.bfloat16),
    (128, 1, 2, 256, jnp.bfloat16),
    (128, 16, 1, 128, jnp.bfloat16),
]


@pytest.mark.parametrize("p,per_group,groups,seq,dtype", KERNEL_CASES)
def test_kernel_scan_is_the_recurrence(kernels, p, per_group, groups, seq,
                                       dtype):
    """Outputs and the gradients of all five operands and ``d``, the kernel
    pair against the float32 recurrence on the same (rounded) operands:
    float32 operands to float32's accuracy over sums of 128, bfloat16 ones to
    the rounding of the matmul operands and of ``y`` (the plain form reads
    the same 3 % and 6-9 %)."""
    exact = _operands(seq + p, seq, batch=1, heads=per_group * groups, p=p,
                      groups=groups, n=128)
    ops = _low(exact, dtype)
    every = tuple(range(6))

    def through(scan):
        def loss(*t):
            y = scan(*t)
            return jnp.sum(jnp.sin(y.astype(jnp.float32))), y
        return jax.value_and_grad(loss, argnums=every, has_aux=True)(*ops)

    (_, got), got_grads = through(lambda *t: ssd_scan(*t, chunk=128))
    assert _paths() == {"pallas": 1}
    (_, want), want_grads = through(ssd_recurrence)
    assert got.shape == want.shape and got.dtype == dtype
    for i, (g, w) in enumerate(zip((got,) + got_grads, (want,) + want_grads)):
        assert g.shape == w.shape
        if dtype == jnp.float32:
            np.testing.assert_allclose(g, w, rtol=1e-3,
                                       atol=3e-4 * float(jnp.abs(w).max()))
        else:
            w = w.astype(jnp.float32)
            assert _rms(g.astype(jnp.float32) - w) < (0.12 if i else 0.03
                                                      ) * _rms(w)


@pytest.mark.parametrize("p,per_group,groups,seq,dtype", [
    (64, 4, 2, 130, jnp.float32), (128, 2, 2, 256, jnp.bfloat16)])
def test_kernel_scan_is_the_plain_form(kernels, monkeypatch, p, per_group,
                                       groups, seq, dtype):
    """One algorithm, two implementations: the same inputs through the
    kernels and through the plain form (the same sums in the same types)."""
    ops = _low(_operands(seq, seq, batch=1, heads=per_group * groups, p=p,
                         groups=groups, n=128), dtype)

    def loss(*t):
        y = ssd_scan(*t, chunk=128)
        return jnp.sum(jnp.sin(y.astype(jnp.float32))), y

    def through():
        (_, y), grads = jax.value_and_grad(loss, argnums=tuple(range(6)),
                                           has_aux=True)(*ops)
        return (y,) + grads

    got = through()
    assert _paths() == {"pallas": 1}
    monkeypatch.setattr(type(get_accelerator()), "use_pallas_kernels",
                        lambda self: False)
    want = through()
    assert _paths() == {"pallas": 1, "plain": 1}
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        if dtype == jnp.float32:
            np.testing.assert_allclose(g, w, rtol=1e-3,
                                       atol=3e-4 * float(jnp.abs(w).max()))
        else:
            assert _rms(g - w) < 0.01 * _rms(w)


def test_a_range_of_heads_gives_its_part_of_the_whole_through_the_kernels(
        kernels):
    """The second group's heads, with that group's B and C, through a call
    of their own (one head block of one group) and inside the whole layer
    (a head block of two groups)."""
    x, dt, a, b, c, d = _operands(5, 256, batch=1, heads=4, p=64, groups=2,
                                  n=128)
    whole = ssd_scan(x, dt, a, b, c, d, chunk=128)
    half = ssd_scan(x[:, :, 2:], dt[:, :, 2:], a[2:], b[:, :, 1:],
                    c[:, :, 1:], d[2:], chunk=128)
    assert _paths() == {"pallas": 2}
    np.testing.assert_allclose(half, whole[:, :, 2:], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("why,shape,chunk", [
    ("a chunk of 16 steps", dict(heads=4, p=64, groups=2, n=128), 16),
    ("heads of 8", dict(heads=4, p=8, groups=2, n=128), 128),
    ("a state of 16", dict(heads=4, p=64, groups=2, n=16), 128),
    ("one head of 64 a group", dict(heads=2, p=64, groups=2, n=128), 128),
])
def test_shapes_the_plan_refuses_take_the_plain_form(kernels, why, shape,
                                                     chunk):
    """Chosen from the shapes alone: what is no whole 128-lane block falls
    to the plain form, and the path counter says so."""
    ops = _operands(11, 40, batch=1, **shape)
    assert pallas_ssd.scan_plan(shape["heads"], shape["p"], shape["groups"],
                                shape["n"], chunk, (jnp.float32,) * 3) is None
    got = ssd_scan(*ops, chunk=chunk)
    assert _paths() == {"plain": 1}, why
    np.testing.assert_allclose(got, ssd_recurrence(*ops), rtol=2e-5,
                               atol=2e-5 * float(jnp.abs(got).max()))


def test_without_pallas_kernels_every_shape_takes_the_plain_form(
        kernels, monkeypatch):
    monkeypatch.setattr(type(get_accelerator()), "use_pallas_kernels",
                        lambda self: False)
    ops = _operands(13, 128, batch=1, heads=2, p=64, groups=1, n=128)
    assert pallas_ssd.scan_plan(2, 64, 1, 128, 128,
                                (jnp.float32,) * 3) is not None
    ssd_scan(*ops, chunk=128)
    assert _paths() == {"plain": 1}


def test_the_plan_keeps_whole_groups_in_a_program():
    """The hybrid cell's share (32 heads of 64 in 2 groups) is one head
    block; the whole layer (128 in 8) four of two groups; mixed operand
    types are refused."""
    bf16 = (jnp.bfloat16,) * 3
    assert pallas_ssd.scan_plan(32, 64, 2, 128, 128, bf16) == pallas_ssd.Plan(
        chunk=128, p=64, n=128, r=16, gb=2, heads=2)
    assert pallas_ssd.scan_plan(128, 64, 8, 128, 128, bf16).gb == 2
    assert pallas_ssd.scan_plan(8, 128, 8, 256, 256, bf16) == pallas_ssd.Plan(
        chunk=256, p=128, n=256, r=1, gb=8, heads=1)
    assert pallas_ssd.scan_plan(
        32, 64, 2, 128, 128, (jnp.bfloat16, jnp.float32, jnp.bfloat16)) is None


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

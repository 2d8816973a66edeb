"""CCA's latent mixing (``ops/attention/cca.py``): the kernel pair of
``pallas_cca.py`` in interpret mode beside the plain form, forward and all
eight gradients, across row blocks and sequences."""

import functools

import jax
import jax.numpy as jnp
import pytest

from deeperspeed_tpu.ops.attention import cca, pallas_cca
from deeperspeed_tpu.telemetry import kernel_paths

OPERANDS = ("qt", "kt", "v", "conv_taps", "conv_bias", "head_conv_kernel",
            "head_conv_bias", "k_temperature")
F32, BF16 = jnp.float32, jnp.bfloat16


def _operands(B, S, heads, kv_heads, d, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    c, normal = (heads + kv_heads) * d, jax.random.normal
    return (normal(keys[0], (B, S, heads * d), F32).astype(dtype),
            normal(keys[1], (B, S, kv_heads * d), F32).astype(dtype),
            normal(keys[2], (B, S, kv_heads * d), F32).astype(dtype),
            normal(keys[3], (2, c)) * 2 ** -0.5,
            normal(keys[4], (c,)) * 0.1,
            normal(keys[5], (2, heads + kv_heads, d, d)) * (2 * d) ** -0.5,
            normal(keys[6], (c,)) * 0.1,
            1 + 0.2 * normal(keys[7], (kv_heads,)))


def _weights(operands, seed=1):
    """Cotangents of q, k, v that differ row by row and lane by lane."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k, t.shape, F32)
            for k, t in zip(keys, operands[:3])]


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads",
                                             "use_pallas", "rotary_dim"))
def _value_and_grads(operands, weights, heads, kv_heads, use_pallas,
                     rotary_dim):
    def loss(*args):
        out = cca.cca_mix(
            *args, heads=heads, kv_heads=kv_heads, rotary_dim=rotary_dim,
            rope_theta=1e4, eps=1e-5, use_pallas=use_pallas)
        return sum(jnp.sum(o.astype(F32) * w)
                   for o, w in zip(out, weights)), out

    return jax.value_and_grad(loss, argnums=tuple(range(8)),
                              has_aux=True)(*operands)


def _mixed(operands, weights, heads, kv_heads, use_pallas, rotary_dim=None):
    """(q, k, v) and the gradients of their weighted sum in all eight
    operands."""
    d = operands[0].shape[2] // heads
    (_, out), grads = _value_and_grads(
        tuple(operands), tuple(weights), heads, kv_heads, use_pallas,
        rotary_dim or d // 2)
    return dict(zip(("q", "k", "v_out") + tuple("d_" + n for n in OPERANDS),
                    tuple(out) + tuple(grads)))


def _off(a, b):
    """The largest difference over the larger of 1 and the largest value."""
    a, b = a.astype(F32), b.astype(F32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.maximum(1, jnp.max(jnp.abs(b))))


# float32: the same sums in another order.  bfloat16: the plain form rounds
# each per-head product and their sum to the stream's type and the pair does
# not (``pallas_cca``'s docstring): two roundings of 2^-9 each on the way to
# an output that is itself rounded once, and in a gradient what they leave
# over the backward's dozen terms; a parameter's gradient is a sum over
# every row, where they add up against a sum that cancels
TOLERANCE = {F32: 1e-5, BF16: 2 ** -6}
PARAMETERS = tuple("d_" + n for n in OPERANDS[3:])

CASES = {
    # several row blocks a sequence: the halo before a block and the carry
    # after it; a group of one
    "four_blocks": dict(B=1, S=64, rows=16, heads=2, kv_heads=2, d=32),
    # two sequences of two blocks: a block's halo is its own sequence's;
    # one KV head (v's later half is half a head), a group of four
    "two_sequences": dict(B=2, S=32, rows=16, heads=4, kv_heads=1, d=32),
    # a sequence in one block: zeros before it, nothing after it
    "one_block": dict(B=2, S=32, rows=32, heads=2, kv_heads=1, d=16),
    # a head of whole lanes as on the chip, three blocks
    "whole_lanes": dict(B=1, S=48, rows=16, heads=2, kv_heads=1, d=128),
    # rotary on a whole head, and on a quarter of one
    "rotary_whole": dict(B=1, S=32, rows=16, heads=2, kv_heads=2, d=32,
                         rotary_dim=32),
    "rotary_quarter": dict(B=1, S=32, rows=16, heads=2, kv_heads=1, d=32,
                           rotary_dim=8),
}


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_the_pair_is_the_plain_form(case, dtype, monkeypatch):
    """Forward and all eight gradients, at every row: the first two of a
    sequence (zeros before it), the last two (nothing after it) and those
    either side of a block's edge among them."""
    shape = dict(CASES[case])
    rows, rotary_dim = shape.pop("rows"), shape.pop("rotary_dim", None)
    monkeypatch.setattr(pallas_cca, "ROWS", rows)
    assert pallas_cca.mix_rows(shape["S"]) == rows
    heads, kv_heads = shape["heads"], shape["kv_heads"]
    operands = _operands(dtype=dtype, **shape)
    weights = _weights(operands)
    plain = _mixed(operands, weights, heads, kv_heads, False, rotary_dim)
    pair = _mixed(operands, weights, heads, kv_heads, True, rotary_dim)
    for name, want in plain.items():
        assert pair[name].shape == want.shape, name
        assert pair[name].dtype == want.dtype, name
        loose = 2 if dtype == BF16 and name in PARAMETERS else 1
        assert _off(pair[name], want) <= loose * TOLERANCE[dtype], name
    # the value shift moves bits: exact in the stream's type (a float32
    # cotangent is the test's own product with the loss's 1.0, to an ulp)
    assert _off(pair["v_out"], plain["v_out"]) == 0
    assert _off(pair["d_v"], plain["d_v"]) <= (0 if dtype == BF16 else 1e-6)
    # the edges of a sequence by themselves
    for rows_of in (slice(0, 2), slice(-2, None)):
        for name in ("q", "k", "d_qt", "d_kt"):
            assert _off(pair[name][:, rows_of],
                        plain[name][:, rows_of]) <= TOLERANCE[dtype], name


def test_bfloat16_pair_is_no_farther_from_float32_than_the_plain_form(
        monkeypatch):
    """Never a coarser arithmetic: against the equations in float32 (the
    plain form on float32 copies of the same bfloat16 streams) the pair's
    outputs and stream gradients err no more than the plain form's."""
    monkeypatch.setattr(pallas_cca, "ROWS", 16)
    operands = _operands(1, 64, 2, 2, 32, BF16, seed=2)
    weights = _weights(operands)
    exact = _mixed(tuple(t.astype(F32) for t in operands), weights, 2, 2,
                   False)
    plain = _mixed(operands, weights, 2, 2, False)
    pair = _mixed(operands, weights, 2, 2, True)

    def rms(got, name):
        return float(jnp.sqrt(jnp.mean(jnp.square(
            got[name].astype(F32) - exact[name]))))

    for name in ("q", "k", "d_qt", "d_kt", "d_head_conv_kernel",
                 "d_conv_taps"):
        assert rms(pair, name) <= 1.05 * rms(plain, name), name


def test_no_row_of_a_sequence_reaches_the_next(monkeypatch):
    """Sequence 0's last rows changed: sequence 1's outputs and gradients
    do not move (a first block's halo is zeros, not the rows before it in
    memory).  Sequence 1's first rows' cotangents changed: sequence 0's
    gradients do not move (after a last block the carry is zeros)."""
    monkeypatch.setattr(pallas_cca, "ROWS", 16)
    operands = _operands(2, 32, 2, 2, 32, F32)
    weights = _weights(operands)
    before = _mixed(operands, weights, 2, 2, True)
    bumped = tuple(t.at[0, -2:].add(3.0) for t in operands[:3])
    after = _mixed(bumped + operands[3:], weights, 2, 2, True)
    for name in ("q", "k", "v_out", "d_qt", "d_kt", "d_v"):
        assert _off(after[name][1], before[name][1]) == 0, name
        # (d_v is the cotangents a row earlier, whatever the streams hold)
        assert _off(after[name][0], before[name][0]) > 0 or name == "d_v"
    heavier = [w.at[1, :2].add(3.0) for w in weights]
    after = _mixed(operands, heavier, 2, 2, True)
    for name in ("d_qt", "d_kt", "d_v"):
        assert _off(after[name][0], before[name][0]) == 0, name
        assert _off(after[name][1], before[name][1]) > 0, name


def test_a_shape_of_no_whole_tiles_takes_the_plain_form(monkeypatch):
    """The tiny preset's heads of 16 lanes, left to the dispatch with the
    kernels on offer: the plain form, counted in ``kernel_paths()``; a head
    of whole lanes takes the pair."""
    from deeperspeed_tpu.accelerator import get_accelerator

    monkeypatch.setattr(type(get_accelerator()), "use_pallas_kernels",
                        lambda self: True)

    def traced(d, S):
        was = dict(kernel_paths().get("cca_mix", {}))
        operands = _operands(1, S, 4, 2, d, BF16)
        jax.eval_shape(lambda *a: cca.cca_mix(
            *a, heads=4, kv_heads=2, rotary_dim=d // 2, rope_theta=1e4,
            eps=1e-5), *operands)
        now = kernel_paths()["cca_mix"]
        return {path: n - was.get(path, 0) for path, n in now.items()
                if n != was.get(path, 0)}

    assert traced(16, 96) == {"plain": 1}       # ``ZayaConfig.tiny()``
    assert traced(128, 72) == {"plain": 1}      # rows of no whole tile
    assert traced(128, 96) == {"pallas": 1}


@pytest.mark.parametrize("seq,rows", [(8192, 256), (256, 256), (96, 96),
                                      (4112, 16), (48, 48)])
def test_the_row_block_follows_the_sequence(seq, rows):
    assert pallas_cca.mix_rows(seq) == rows
    assert pallas_cca.compiles_for_tpu(seq, 128, 64)
    assert not pallas_cca.compiles_for_tpu(seq, 64, 32)
    assert not pallas_cca.compiles_for_tpu(seq + 8, 128, 64)

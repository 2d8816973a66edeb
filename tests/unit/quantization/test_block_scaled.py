"""BlockScaledTensor: the one block-scaled pytree type.

Round-trip error bounds per wire dtype, the pytree registration contract
(jit / shard_map / donation), bit-exact memcpy through ``wire_proto`` KV
frames, tamper -> :class:`WireCorruptionError`, and the canonical-dtype /
block-shape helpers the analyzer's DST-G009 rides on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from deeperspeed_tpu.quantization import (BlockScaledTensor, WIRE_DTYPES,
                                          block_shape_error, canonical_dtype,
                                          group_shape, qmax, wire_dtype)

#: per-dtype round-trip bound, as a fraction of the per-group amax:
#: int8 rounds to 1/254 of full scale (+ bf16 scale-snap slack);
#: e4m3 carries a 3-bit mantissa (step 2^-4 of the value), e5m2 a 2-bit
#: one (2^-3) -- bounds are vs amax so denormal-range values stay inside.
RTOL = {"int8": 1.0 / 127, "fp8_e4m3": 0.09, "fp8_e5m2": 0.17}

DTYPES = sorted(WIRE_DTYPES)


def _rand(shape, seed=0, scale=3.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape,
                                     jnp.float32)


# ------------------------------------------------------------- round trip
@pytest.mark.parametrize("dtype", DTYPES)
def test_round_trip_bound_per_group_amax(dtype):
    x = _rand((4, 256), seed=1)
    t = BlockScaledTensor.quantize(x, dtype, group_size=64)
    assert t.values.dtype == WIRE_DTYPES[dtype]
    assert t.scales.dtype == jnp.float32
    y = t.dequantize(jnp.float32)
    err = np.abs(np.asarray(y) - np.asarray(x)).reshape(4, 4, 64)
    amax = np.abs(np.asarray(x)).reshape(4, 4, 64).max(-1, keepdims=True)
    assert (err <= RTOL[dtype] * amax + 1e-6).all(), \
        f"{dtype}: worst {np.max(err / (amax + 1e-12)):.4f} > {RTOL[dtype]}"


@pytest.mark.parametrize("dtype", DTYPES)
def test_fp8_never_overflows_to_nonfinite(dtype):
    # amax maps exactly onto qmax; without the pre-cast clip the fp8 cast
    # of (amax/scale) would overflow to nan/inf on the bf16-snapped scale
    x = jnp.concatenate([_rand((2, 128), seed=2) * 1e4,
                         jnp.full((1, 128), 6e4)])
    t = BlockScaledTensor.quantize(x, dtype, group_size=32)
    y = np.asarray(t.dequantize(jnp.float32))
    assert np.isfinite(y).all()
    assert np.abs(np.asarray(t.values).astype(np.float32)).max() \
        <= qmax(dtype)


def test_cast_requantizes_between_wire_dtypes():
    x = _rand((8, 128), seed=3)
    t8 = BlockScaledTensor.quantize(x, "int8", group_size=64)
    tf = t8.cast("fp8_e4m3")
    assert tf.values.dtype == jnp.float8_e4m3fn
    assert tf.group_size == t8.group_size
    # one extra quantization step of error at most: still within the
    # combined bound vs the original
    err = np.abs(np.asarray(tf.dequantize()) - np.asarray(x))
    amax = np.abs(np.asarray(x)).reshape(8, 2, 64).max(-1)
    assert (err.reshape(8, 2, 64).max(-1)
            <= (RTOL["int8"] + RTOL["fp8_e4m3"]) * amax + 1e-6).all()


# ----------------------------------------------------------- pytree rules
def test_jit_transparent_and_group_size_static():
    t = BlockScaledTensor.quantize(_rand((4, 128)), "fp8", group_size=32)

    @jax.jit
    def deq(t):
        assert t.group_size == 32        # static aux data inside the trace
        return t.dequantize(jnp.float32)

    np.testing.assert_array_equal(np.asarray(deq(t)),
                                  np.asarray(t.dequantize(jnp.float32)))
    out = jax.jit(lambda t: t)(t)
    assert isinstance(out, BlockScaledTensor) and out.group_size == 32


def test_tree_leaves_order_is_values_then_scales():
    t = BlockScaledTensor.quantize(_rand((4, 64)), "int8", group_size=32)
    leaves = jax.tree_util.tree_leaves(t)
    assert len(leaves) == 2
    assert leaves[0] is t.values and leaves[1] is t.scales


def test_shard_map_moves_values_and_scales_together():
    mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("dp",))
    t = BlockScaledTensor.quantize(_rand((4, 128)), "fp8_e5m2",
                                   group_size=64)
    sm = shard_map(lambda t: t.dequantize(jnp.float32), mesh=mesh,
                   in_specs=(P("dp"),), out_specs=P("dp"))
    np.testing.assert_array_equal(np.asarray(sm(t)),
                                  np.asarray(t.dequantize(jnp.float32)))


def test_donation_of_a_block_scaled_arg():
    t = BlockScaledTensor.quantize(_rand((4, 128)), "int8", group_size=64)
    ref = np.asarray(t.dequantize(jnp.float32))
    f = jax.jit(lambda t: BlockScaledTensor(t.values, t.scales * 2.0,
                                            t.group_size),
                donate_argnums=0)
    out = f(t)
    assert isinstance(out, BlockScaledTensor)
    np.testing.assert_allclose(np.asarray(out.dequantize(jnp.float32)),
                               2.0 * ref, rtol=1e-6)


# ------------------------------------------------------------------- wire
def test_wire_roundtrip_is_bitexact_memcpy():
    from deeperspeed_tpu.inference.v2 import wire_proto

    t = BlockScaledTensor.quantize(_rand((2, 8, 128), seed=5), "fp8",
                                   group_size=64)
    payloads = t.wire_payloads()
    assert [p.dtype.name for p in payloads] == ["float8_e4m3fn", "float32"]
    frame = wire_proto.encode_kv_frame("req-1", 3, None, payloads)
    kind, body = wire_proto.decode_frame(frame)
    assert kind == wire_proto.KV
    dec = wire_proto.decode_kv_frame(body)
    back = BlockScaledTensor.from_wire(dec["payloads"], t.group_size)
    # memcpy, not a requantize: byte-identical values AND scales
    assert np.array_equal(np.asarray(back.values).view(np.uint8),
                          np.asarray(t.values).view(np.uint8))
    assert np.array_equal(np.asarray(back.scales), np.asarray(t.scales))
    assert dec["nbytes"] == t.wire_nbytes


def test_tampered_frame_raises_wire_corruption():
    from deeperspeed_tpu.inference.v2 import wire_proto

    t = BlockScaledTensor.quantize(_rand((4, 64), seed=6), "int8",
                                   group_size=32)
    body = wire_proto.encode_kv_body("req-2", 0, None, t.wire_payloads())
    flipped = bytearray(body)
    flipped[-1] ^= 0x40                    # flip a bit inside the payload
    with pytest.raises(wire_proto.WireCorruptionError):
        wire_proto.decode_kv_frame(bytes(flipped))


def test_wire_nbytes_counts_one_byte_values_plus_fp32_scales():
    t = BlockScaledTensor.quantize(_rand((4, 128)), "fp8", group_size=32)
    assert t.wire_nbytes == 4 * 128 + 4 * (4 * 4)


# ---------------------------------------------------------------- helpers
def test_canonical_dtype_aliases():
    assert canonical_dtype("fp8") == "fp8_e4m3"
    assert canonical_dtype("e5m2") == "fp8_e5m2"
    assert canonical_dtype("float8_e4m3fn") == "fp8_e4m3"
    assert canonical_dtype("uint8") == "int8"
    assert canonical_dtype(jnp.int8) == "int8"
    with pytest.raises(ValueError):
        canonical_dtype("fp4")


def test_qmax_and_wire_dtype():
    assert qmax("int8") == 127.0
    assert qmax("fp8") == 448.0
    assert qmax("e5m2") == 57344.0
    assert wire_dtype("fp8") == jnp.float8_e4m3fn


def test_group_shape_falls_back_to_full_dim():
    assert group_shape(256, 64) == 64
    assert group_shape(100, 64) == 100      # non-divisible: one group


def test_block_shape_error_contract():
    assert block_shape_error((4, 128), (4, 2, 1), 64) is None
    msg = block_shape_error((4, 128), (4, 4, 1), 64)
    assert msg is not None and "group_size=64" in msg
    assert block_shape_error((), (1,), 64) is not None


# -------------------------------------------------------------- row layout
def test_row_layout_matches_kv_quantizer():
    from deeperspeed_tpu.ops.quantizer.kv import dequantize_kv, quantize_kv

    x = _rand((16, 4, 64), seed=7)
    for dtype in ("int8", "fp8"):
        q1, s1 = quantize_kv(x, dtype)
        q2, s2 = BlockScaledTensor.quantize_rows(x, dtype)
        assert np.array_equal(np.asarray(q1).view(np.uint8),
                              np.asarray(q2).view(np.uint8))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
        assert s1.shape == (16, 4)          # one fp32 scale per (row, head)
        np.testing.assert_array_equal(
            np.asarray(dequantize_kv(q1, s1, jnp.float32)),
            np.asarray(BlockScaledTensor.dequantize_rows(q2, s2,
                                                         jnp.float32)))


def test_from_rows_builds_a_consistent_pytree():
    x = _rand((8, 2, 32), seed=8)
    q, s = BlockScaledTensor.quantize_rows(x, "fp8")
    t = BlockScaledTensor.from_rows(q, s)
    assert t.group_size == 32
    err = np.abs(np.asarray(t.dequantize(jnp.float32)) - np.asarray(x))
    amax = np.abs(np.asarray(x)).max(-1, keepdims=True)
    assert (err <= RTOL["fp8_e4m3"] * amax + 1e-6).all()

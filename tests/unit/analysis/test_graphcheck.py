"""Analyzer self-tests for the jaxpr/graph rules: each seeded fixture
fires its rule exactly once with the fixture function's file:line, and
the clean variants stay silent."""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from deeperspeed_tpu.analysis import (check_block_scaled, check_bucket_keys,
                                      check_collectives, check_donation,
                                      check_jit_signature,
                                      check_ppermute_perm, check_step_fn,
                                      check_wire_payloads)

_FIX_PATH = pathlib.Path(__file__).parent / "fixtures" / "graph_fixtures.py"
_spec = importlib.util.spec_from_file_location("graph_fixtures", _FIX_PATH)
fx = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(fx)


def _assert_anchor(finding, fn):
    assert finding.path == fn.__code__.co_filename == str(_FIX_PATH)
    assert finding.line == fn.__code__.co_firstlineno


def _mesh():
    return Mesh(np.array(jax.devices("cpu")[:1]), ("dp",))


# ------------------------------------------------------------ G001 / G002
def test_donation_aliasing_fires_once():
    x = jnp.ones((8, 8), jnp.float32)
    findings = check_donation(fx.sum_pair, (x, x), donate_argnums=(0,),
                              min_donation_bytes=1 << 40)
    assert [f.rule for f in findings] == ["DST-G001"]
    _assert_anchor(findings[0], fx.sum_pair)


def test_missing_donation_fires_once():
    big = jnp.ones((512, 1024), jnp.float32)    # 2 MiB each
    findings = check_donation(fx.scale_big, (big, big), donate_argnums=(),
                              min_donation_bytes=1 << 20)
    assert [f.rule for f in findings] == ["DST-G002"]
    _assert_anchor(findings[0], fx.scale_big)


def test_donated_unaliased_step_is_clean():
    a = jnp.ones((512, 1024), jnp.float32)
    b = jnp.ones((512, 1024), jnp.float32)
    assert check_donation(fx.scale_big, (a, b), donate_argnums=(0,)) == []


# ------------------------------------------------------------------ G006
def test_python_scalar_in_signature_fires_once():
    x = jnp.ones((4,), jnp.float32)
    findings = check_jit_signature(fx.add_offset, (x, 3))
    assert [f.rule for f in findings] == ["DST-G006"]
    _assert_anchor(findings[0], fx.add_offset)
    assert "int" in findings[0].message


def test_weak_typed_leaf_fires_and_wrapped_scalar_is_clean():
    x = jnp.ones((4,), jnp.float32)
    weak = check_jit_signature(fx.add_offset, (x, jnp.asarray(3)))
    assert [f.rule for f in weak] == ["DST-G006"]
    assert check_jit_signature(fx.add_offset, (x, jnp.int32(3))) == []


# ------------------------------------------------------------------ G007
def test_non_pow2_bucket_key_fires_once():
    where = (str(_FIX_PATH), 1)
    findings = check_bucket_keys(fx.BAD_BUCKET_KEYS, where=where)
    assert [f.rule for f in findings] == ["DST-G007"]
    assert (findings[0].path, findings[0].line) == where
    assert "6" in findings[0].message
    assert check_bucket_keys(fx.GOOD_BUCKET_KEYS, where=where) == []


# ------------------------------------------------------------------ G005
def test_invalid_ppermute_perm_fires_once():
    where = (str(_FIX_PATH), 2)
    findings = check_ppermute_perm(fx.BAD_PERM, axis_size=2, where=where)
    assert [f.rule for f in findings] == ["DST-G005"]
    assert "duplicate destinations" in findings[0].message
    assert "[3]" in findings[0].message       # out of range for axis_size 2
    assert check_ppermute_perm(fx.GOOD_PERM, axis_size=2, where=where) == []


# ----------------------------------------------------------- G003 / G004
def _traced_psum():
    sm = shard_map(fx.psum_step, mesh=_mesh(), in_specs=P("dp"),
                   out_specs=P())
    return jax.make_jaxpr(sm)(jnp.ones((4,), jnp.float32))


def test_collective_axis_typo_fires_once():
    findings = check_collectives(_traced_psum(), mesh_axes={"tp"},
                                 fn=fx.psum_step)
    assert [f.rule for f in findings] == ["DST-G003"]
    _assert_anchor(findings[0], fx.psum_step)
    assert "'dp'" in findings[0].message


def test_psum_over_unmapped_axis_fires_once():
    findings = check_collectives(_traced_psum(), mesh_axes={"dp", "tp"},
                                 mapped_axes={"tp"}, fn=fx.psum_step)
    assert [f.rule for f in findings] == ["DST-G004"]
    _assert_anchor(findings[0], fx.psum_step)


def test_correctly_mapped_psum_is_clean():
    assert check_collectives(_traced_psum(), mesh_axes={"dp"},
                             fn=fx.psum_step) == []


# ------------------------------------------------------------------ G008
def test_unpaired_int8_collective_fires_once():
    sm = shard_map(fx.gather_int8, mesh=_mesh(), in_specs=P("dp"),
                   out_specs=P(None, "dp"))
    closed = jax.make_jaxpr(sm)(jnp.ones((4,), jnp.int8))
    findings = check_collectives(closed, mesh_axes={"dp"},
                                 fn=fx.gather_int8)
    assert [f.rule for f in findings] == ["DST-G008"]
    _assert_anchor(findings[0], fx.gather_int8)


def test_int8_with_scales_collective_is_clean():
    sm = shard_map(fx.gather_int8_with_scales, mesh=_mesh(),
                   in_specs=(P("dp"), P("dp")),
                   out_specs=(P(None, "dp"), P(None, "dp")))
    closed = jax.make_jaxpr(sm)(jnp.ones((4,), jnp.int8),
                                jnp.ones((4,), jnp.float32))
    assert check_collectives(closed, mesh_axes={"dp"},
                             fn=fx.gather_int8_with_scales) == []


def test_unpaired_int8_wire_payload_fires_once():
    where = (str(_FIX_PATH), 3)
    findings = check_wire_payloads([np.zeros(4, np.int8)], where=where)
    assert [f.rule for f in findings] == ["DST-G008"]
    assert (findings[0].path, findings[0].line) == where
    assert check_wire_payloads(
        [np.zeros(4, np.int8), np.ones(1, np.float32)], where=where) == []


def test_unpaired_fp8_collective_fires_once():
    sm = shard_map(fx.gather_fp8, mesh=_mesh(), in_specs=P("dp"),
                   out_specs=P(None, "dp"))
    closed = jax.make_jaxpr(sm)(jnp.ones((4,), jnp.float8_e4m3fn))
    findings = check_collectives(closed, mesh_axes={"dp"},
                                 fn=fx.gather_fp8)
    assert [f.rule for f in findings] == ["DST-G008"]
    _assert_anchor(findings[0], fx.gather_fp8)
    assert "float8_e4m3" in findings[0].message


def test_unpaired_fp8_wire_payload_fires_once():
    where = (str(_FIX_PATH), 3)
    fp8 = np.asarray(jnp.zeros((4,), jnp.float8_e5m2))
    findings = check_wire_payloads([fp8], where=where)
    assert [f.rule for f in findings] == ["DST-G008"]
    assert "float8_e5m2" in findings[0].message
    assert check_wire_payloads([fp8, np.ones(1, np.float32)],
                               where=where) == []


# ------------------------------------------------------------------ G009
def test_block_shape_mismatch_fires_once():
    where = (str(_FIX_PATH), 4)
    findings = check_block_scaled(*fx.BAD_BLOCK_SHAPES, where=where)
    assert [f.rule for f in findings] == ["DST-G009"]
    assert (findings[0].path, findings[0].line) == where
    assert "group_size=64" in findings[0].message
    assert check_block_scaled(*fx.GOOD_BLOCK_SHAPES, where=where) == []


def test_block_scaled_tensor_roundtrip_is_clean_and_tamper_fires():
    from deeperspeed_tpu.quantization import BlockScaledTensor

    t = BlockScaledTensor.quantize(jnp.ones((4, 128)), "fp8", group_size=64)
    assert check_block_scaled(t) == []
    bad = BlockScaledTensor(t.values, t.scales[:, :1, :], t.group_size)
    findings = check_block_scaled(bad)
    assert [f.rule for f in findings] == ["DST-G009"]


# ------------------------------------------------------- combined entry
def test_check_step_fn_composes_all_rules():
    x = jnp.ones((512, 1024), jnp.float32)
    findings = check_step_fn(fx.add_offset, (x, 7), donate_argnums=(),
                             min_donation_bytes=1 << 20)
    rules = sorted(f.rule for f in findings)
    assert rules == ["DST-G002", "DST-G006"]
    for f in findings:
        _assert_anchor(f, fx.add_offset)

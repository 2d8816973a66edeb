"""EVA attention (``ops/attention/eva.py``, kernels ``pallas_eva.py`` and
``pallas_eva_pool.py``) against the plain reference's attention alone
(``benchmarks/reference/evabyte_ref.py``): the chunk summaries, the mixed
output and all five gradients, with both kernel pairs on (interpret mode
here) and off; the pooling pair alone against the plain form; the mask, read from the outputs (a row of window w sees no summary of
window w and every one of each earlier window; the first window sees none);
what the walk visits beside what the equations need, counted."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import evabyte_ref as ref
from deeperspeed_tpu import telemetry
from deeperspeed_tpu.ops.attention import (eva, pallas_eva,
                                           pallas_eva_pool)

W, C = 64, 8


def _operands(B=2, S=192, N=2, D=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(ks[i], (B, S, N, D)) for i in range(3))
    mu, phi = (jnp.clip(jax.random.normal(ks[3 + i], (N, D)), -1, 1)
               * D ** -0.5 for i in range(2))
    return q, k, v, mu, phi


def _program(use_pallas, window=W, chunk=C):
    def attend(q, k, v, mu, phi):
        kb, vb = eva.chunk_summaries(k, v, mu, phi, chunk,
                                     use_pallas=use_pallas)
        return eva.eva_attention(q, k, v, kb, vb, window, chunk,
                                 use_pallas=use_pallas)
    return attend


def _reference(window=W, chunk=C):
    def one(q, k, v, mu, phi):
        kb, vb = ref.chunk_summaries(k, v, mu, phi, chunk)
        return ref.eva_attention(q, k, v, kb, vb, window, chunk)

    def attend(q, k, v, mu, phi):
        with jax.default_matmul_precision("highest"):
            return jax.vmap(one, in_axes=(0, 0, 0, None, None))(
                q, k, v, mu, phi)
    return attend


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["plain", "kernels"])
def test_the_chunk_summaries_are_the_references(use_pallas):
    q, k, v, mu, phi = _operands()
    got = eva.chunk_summaries(k, v, mu, phi, C, use_pallas=use_pallas)
    want = jax.vmap(lambda a, b: ref.chunk_summaries(a, b, mu, phi, C))(k, v)
    for a, b in zip(got, want):
        assert a.shape == (2, 192 // C, 2, 16)
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    # a direction of zero pools a chunk's plain mean
    flat = eva.chunk_summaries(k, v, 0 * mu, 0 * phi, C,
                               use_pallas=use_pallas)
    np.testing.assert_allclose(
        flat[1], v.reshape(2, -1, C, 2, 16).mean(axis=2), rtol=1e-5,
        atol=1e-6)
    with pytest.raises(ValueError):
        eva.chunk_summaries(k[:, :190], v[:, :190], mu, phi, C,
                            use_pallas=use_pallas)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("S,chunk,rows", [(512, 16, 256), (384, 8, 128),
                                          (512, 16, 2048)],
                         ids=["2-blocks-of-16", "3-blocks-of-8", "1-block"])
def test_the_pooling_kernels_against_the_plain_form(monkeypatch, S, chunk,
                                                    rows, dtype):
    """The pooling pair alone (interpret mode), forward and the gradients in
    k, v, mu and phi, at more than one row block a head (``dmu`` and
    ``dphi`` are summed across grid steps) and at one.  float32 operands:
    1e-5 of the largest entry, against the plain form and the reference.
    bfloat16 operands: what leaves in bfloat16 (the summaries, ``dk``,
    ``dv``) within one rounding of the plain form's, what leaves in float32
    (``dmu``, ``dphi``) to 1e-4 of the plain form's: both compute in
    float32 from the same rounded operands and cotangents (the reference
    gets its cotangents unrounded: one rounding there too)."""
    monkeypatch.setattr(pallas_eva_pool, "ROWS", rows)
    assert pallas_eva_pool.pool_rows(S, chunk) == min(S, rows)
    _, k, v, mu, phi = _operands(S=S, seed=S + chunk)
    k, v = k.astype(dtype), v.astype(dtype)
    shape = (2, S // chunk, 2, 16)
    weigh_k = jnp.cos(jnp.arange(np.prod(shape)).reshape(shape) * 0.1)
    weigh_v = jnp.sin(jnp.arange(np.prod(shape)).reshape(shape) * 0.3)

    def loss(fn):
        def of(*a):
            kb, vb = fn(*a)
            return (jnp.sum(kb.astype(jnp.float32) * weigh_k)
                    + jnp.sum(vb.astype(jnp.float32) * weigh_v))
        return of

    def kernels(*a):
        return eva.chunk_summaries(*a, chunk, use_pallas=True)

    def plain(*a):
        return eva.chunk_summaries(*a, chunk, use_pallas=False)

    def reference(k, v, mu, phi):
        return jax.vmap(lambda a, b: ref.chunk_summaries(
            a.astype(jnp.float32), b.astype(jnp.float32), mu, phi, chunk))(
                k, v)

    args = (k, v, mu, phi)
    got = kernels(*args) + jax.grad(loss(kernels), argnums=(0, 1, 2, 3))(*args)
    names = ("kb", "vb", "dk", "dv", "dmu", "dphi")
    for form in (plain, reference):
        want = form(*args) + jax.grad(loss(form), argnums=(0, 1, 2, 3))(*args)
        for a, b, name in zip(got, want, names):
            assert a.shape == b.shape and a.dtype == (
                jnp.float32 if name in ("dmu", "dphi") else dtype)
            a, b = (np.asarray(t, np.float32) for t in (a, b))
            scale = max(1.0, float(np.max(np.abs(b))))
            exact = dtype == jnp.float32 or (
                form is plain and name in ("dmu", "dphi"))
            tol = (1e-5 if dtype == jnp.float32 else 1e-4) if exact else 2 ** -7
            np.testing.assert_allclose(
                a / scale, b / scale, rtol=tol, atol=tol,
                err_msg=f"{name} against {form.__name__}")


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["plain", "kernels"])
@pytest.mark.parametrize("S,window,chunk", [(192, 64, 8), (128, 128, 16),
                                            (256, 32, 4)])
def test_forward_and_backward_against_the_reference(use_pallas, S, window,
                                                    chunk):
    """Tolerances: float32 on both sides; the kernels' online softmax and
    the reference's whole-row softmax differ by rounding alone (1e-5 of the
    largest gradient)."""
    args = _operands(S=S, seed=S)
    weigh = jnp.cos(jnp.arange(args[0].size).reshape(args[0].shape) * 0.1)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * weigh)

    prog, want = _program(use_pallas, window, chunk), _reference(window, chunk)
    np.testing.assert_allclose(prog(*args), want(*args), rtol=1e-5,
                               atol=1e-5)
    got_g = jax.grad(loss(prog), argnums=(0, 1, 2, 3, 4))(*args)
    want_g = jax.grad(loss(want), argnums=(0, 1, 2, 3, 4))(*args)
    for a, b, name in zip(got_g, want_g, ("q", "k", "v", "mu", "phi")):
        scale = max(1.0, float(jnp.max(jnp.abs(b))))
        np.testing.assert_allclose(a / scale, b / scale, rtol=1e-5,
                                   atol=1e-5, err_msg=f"d{name}")


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["plain", "kernels"])
def test_the_mask_read_from_the_outputs(use_pallas):
    """Changing a key or a value changes exactly the rows that see it."""
    q, k, v, mu, phi = _operands(B=1, S=192)
    attend = _program(use_pallas)
    base = np.asarray(attend(q, k, v, mu, phi))

    def moved_rows(k2, v2):
        out = np.asarray(attend(q, k2, v2, mu, phi))
        return set(np.nonzero(np.abs(out - base).max(axis=(0, 2, 3)) > 0)[0])

    # a value at position 70 (window 1, chunk 8): seen exactly by the rows
    # 70..127 of its own window, and through its chunk's summary by every
    # row of the LATER window 2; by no row of window 0, by no row before it
    v2 = v.at[0, 70].add(1.0)
    assert moved_rows(k, v2) == set(range(70, 128)) | set(range(128, 192))
    # one of the first window: its own rows from it on, then all later rows
    v2 = v.at[0, 5].add(1.0)
    assert moved_rows(k, v2) == set(range(5, 192))
    # one of the last window: no summary of it is seen by anyone
    v2 = v.at[0, 130].add(1.0)
    assert moved_rows(k, v2) == set(range(130, 192))
    # the first window sees no summary: it is windowed attention alone
    kb, vb = eva.chunk_summaries(k, v, mu, phi, C)
    first = eva.eva_attention(q[:, :W], k[:, :W], v[:, :W], kb[:, :W // C],
                              vb[:, :W // C], W, C, use_pallas=use_pallas)
    np.testing.assert_allclose(first, base[:, :W], rtol=1e-6, atol=1e-6)
    # and a row of window 1 weighs all 8 summaries of window 0: the weights
    # of a row add up to one over its keys and those 8 (values of one)
    ones = eva.eva_attention(q, k, jnp.ones_like(v), kb, jnp.ones_like(vb),
                             W, C, use_pallas=use_pallas)
    np.testing.assert_allclose(ones, 1.0, rtol=1e-5, atol=1e-5)
    none_far = eva.eva_attention(q, k, jnp.ones_like(v), kb,
                                 jnp.zeros_like(vb), W, C,
                                 use_pallas=use_pallas)
    far_weight = 1.0 - np.asarray(none_far)[0, :, 0, 0]
    assert np.all(np.abs(far_weight[:W]) < 1e-6)
    assert np.all(far_weight[W:] > 1e-3)


def test_a_float32_number_splits_into_bfloat16_parts_with_no_rounding():
    """The MXU takes the float32 directions (and float32 keys) as bfloat16
    parts: every part is exactly a bfloat16 (the top 16 bits of what is
    left, never a rounding, which a compiler may undo) and their float32
    sum is the number, bit for bit."""
    x = jnp.concatenate([
        jax.random.normal(jax.random.PRNGKey(0), (4096,)) * 3.0,
        jnp.array([0.0, -0.0, 1.0, -1.0, 1e-30, 3.0e38, 2.0 ** -120])])
    parts = pallas_eva_pool._split(x)
    assert len(parts) == pallas_eva_pool.PARTS
    assert all(part.dtype == jnp.bfloat16 for part in parts)
    left = np.asarray(x, np.float32)
    for part in parts:
        part = np.asarray(part.astype(jnp.float32))
        assert not np.any(part.view(np.uint32) & 0xffff)
        # a part is what is left with its low 16 bits cleared: no rounding
        np.testing.assert_array_equal(
            part.view(np.uint32), left.view(np.uint32) & 0xffff0000)
        left = left - part
    assert not np.any(left)


def test_under_a_mesh_each_device_pools_its_own_batch_rows_and_heads(
        monkeypatch):
    """Under a process-global mesh (dp 2 x tp 2) the kernels run in a
    ``shard_map``, a device its own batch row and head: the two summaries
    and the gradients are the plain form's."""
    from deeperspeed_tpu.parallel import topology

    monkeypatch.setattr(topology, "_GLOBAL_MESH", topology.MeshTopology(
        dp=2, tp=2, devices=jax.devices()[:4]))
    _, k, v, mu, phi = _operands(S=128)

    def loss(use_pallas):
        def of(*a):
            kb, vb = eva.chunk_summaries(*a, C, use_pallas=use_pallas)
            return jnp.sum(jnp.sin(kb)) + jnp.sum(jnp.cos(vb))
        return jax.jit(jax.value_and_grad(of, argnums=(0, 1, 2, 3)))

    got, want = loss(True)(k, v, mu, phi), loss(False)(k, v, mu, phi)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_pairs_visited_and_needed_by_hand():
    # 16k bytes, the cell's shapes: a row sees 1024.5 keys of its window
    # and 448 summaries on average
    assert eva.pairs_needed(16384, 2048, 16) == 16384 * 1472.5
    plan = pallas_eva.eva_plan(2048, 16, 128)
    assert plan == pallas_eva.EvaPlan(2048, 128, 512, 128)
    # the walk: row groups of 512 up to their diagonal, whole windows of
    # summaries
    local = 512 * (512 + 1024 + 1536 + 2048)
    assert pallas_eva.pairs_visited(16384, plan) == (
        8 * local + 2048 * 128 * 28)
    assert eva.pairs_visited(16384, 2048, 16, 128, use_pallas=True) \
        == 28_311_552
    # the plain form computes whole blocks
    assert eva.pairs_visited(192, 64, 8, 16, use_pallas=False) == 192 * (
        64 + 24)
    # a short last window
    assert eva.pairs_needed(160, 64, 8) == (2 * 64 * 65 // 2 + 32 * 33 // 2
                                            + 8 * (64 + 32 * 2))
    assert pallas_eva.compiles_for_tpu(16384, 2048, 16, 128)
    assert not pallas_eva.compiles_for_tpu(16384, 2048, 16, 64)
    assert not pallas_eva.compiles_for_tpu(192, 64, 8, 128)
    # the pooling: a head whole lane tiles, rows of 32 bits or pairs of 16,
    # row blocks of whole 16-chunk tiles, the most rows up to ROWS that
    # divide the sequence
    bf16 = jnp.bfloat16
    assert pallas_eva_pool.compiles_for_tpu(16384, 16, 128, bf16)
    assert not pallas_eva_pool.compiles_for_tpu(16384, 16, 64, bf16)
    assert not pallas_eva_pool.compiles_for_tpu(192, 8, 128, bf16)
    assert not pallas_eva_pool.compiles_for_tpu(16384, 16, 128, jnp.float16)
    assert not pallas_eva_pool.compiles_for_tpu(16320, 15, 128, bf16)
    assert pallas_eva_pool.compiles_for_tpu(16320, 15, 128, jnp.float32)
    assert pallas_eva_pool.pool_rows(16384, 16) == 2048
    assert pallas_eva_pool.pool_rows(3 * 1280, 16) == 1280
    assert pallas_eva_pool.pool_rows(192, 8) == 192


def test_the_kernel_call_is_counted_and_refuses_ragged_shapes():
    q, k, v, mu, phi = _operands(B=1, S=128)
    before = {name: dict(telemetry.kernel_paths().get(name, {}))
              for name in ("eva_attention", "eva_pool")}
    jax.clear_caches()
    _program(True)(q, k, v, mu, phi)
    after = telemetry.kernel_paths()
    for name in before:
        assert after[name]["in_place_1"] == before[name].get(
            "in_place_1", 0) + 1
    kb, vb = eva.chunk_summaries(k, v, mu, phi, C)
    assert telemetry.kernel_paths()["eva_pool"]["plain"] == before[
        "eva_pool"].get("plain", 0) + 1
    with pytest.raises(ValueError):
        pallas_eva.eva_mha(q[:, :100], k[:, :100], v[:, :100], kb, vb, W, C)
    with pytest.raises(ValueError):     # 12 chunks: no multiple of eight
        pallas_eva_pool.pool(k[:, :96], v[:, :96], mu, phi, C)
    # the plain form pads a short last window and cuts it off again
    short = eva.eva_attention(q[:, :96], k[:, :96], v[:, :96], kb[:, :12],
                              vb[:, :12], W, C, use_pallas=False)
    whole = eva.eva_attention(q, k, v, kb, vb, W, C, use_pallas=False)
    np.testing.assert_allclose(short, whole[:, :96], rtol=1e-6, atol=1e-6)

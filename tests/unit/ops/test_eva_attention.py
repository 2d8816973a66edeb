"""EVA attention (``ops/attention/eva.py``, kernels ``pallas_eva.py``)
against the plain reference's attention alone
(``benchmarks/reference/evabyte_ref.py``): the chunk summaries, the mixed
output and all five gradients, with the kernels on (interpret mode here) and
off; the mask, read from the outputs (a row of window w sees no summary of
window w and every one of each earlier window; the first window sees none);
what the walk visits beside what the equations need, counted."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import evabyte_ref as ref
from deeperspeed_tpu import telemetry
from deeperspeed_tpu.ops.attention import eva, pallas_eva

W, C = 64, 8


def _operands(B=2, S=192, N=2, D=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(ks[i], (B, S, N, D)) for i in range(3))
    mu, phi = (jnp.clip(jax.random.normal(ks[3 + i], (N, D)), -1, 1)
               * D ** -0.5 for i in range(2))
    return q, k, v, mu, phi


def _program(use_pallas, window=W, chunk=C):
    def attend(q, k, v, mu, phi):
        kb, vb = eva.chunk_summaries(k, v, mu, phi, chunk)
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


def test_the_chunk_summaries_are_the_references():
    q, k, v, mu, phi = _operands()
    got = eva.chunk_summaries(k, v, mu, phi, C)
    want = jax.vmap(lambda a, b: ref.chunk_summaries(a, b, mu, phi, C))(k, v)
    for a, b in zip(got, want):
        assert a.shape == (2, 192 // C, 2, 16)
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    # a direction of zero pools a chunk's plain mean
    flat = eva.chunk_summaries(k, v, 0 * mu, 0 * phi, C)
    np.testing.assert_allclose(
        flat[1], v.reshape(2, -1, C, 2, 16).mean(axis=2), rtol=1e-5,
        atol=1e-6)
    with pytest.raises(ValueError):
        eva.chunk_summaries(k[:, :190], v[:, :190], mu, phi, C)


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


def test_the_kernel_call_is_counted_and_refuses_ragged_shapes():
    q, k, v, mu, phi = _operands(B=1, S=128)
    before = telemetry.kernel_paths().get("eva_attention", {})
    jax.clear_caches()
    _program(True)(q, k, v, mu, phi)
    after = telemetry.kernel_paths()["eva_attention"]
    assert after["in_place_1"] == before.get("in_place_1", 0) + 1
    kb, vb = eva.chunk_summaries(k, v, mu, phi, C)
    with pytest.raises(ValueError):
        pallas_eva.eva_mha(q[:, :100], k[:, :100], v[:, :100], kb, vb, W, C)
    # the plain form pads a short last window and cuts it off again
    short = eva.eva_attention(q[:, :96], k[:, :96], v[:, :96], kb[:, :12],
                              vb[:, :12], W, C, use_pallas=False)
    whole = eva.eva_attention(q, k, v, kb, vb, W, C, use_pallas=False)
    np.testing.assert_allclose(short, whole[:, :96], rtol=1e-6, atol=1e-6)

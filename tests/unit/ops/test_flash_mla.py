"""The latent-attention flash kernel (``pallas_flash_mla.mla``: a score that
is the sum of a per-head product and one against ONE shared rotary key, a
value narrower than the score), in interpret mode, against the plain path
of ``core.py`` and against a form written here: outputs and all five
gradients, at lengths that are no multiple of the tile, over several owner
blocks and spans; which shapes the kernel takes; and that a call with one
width and no second key is planned and named as it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu import telemetry
from deeperspeed_tpu.ops.attention import pallas_flash_mla
from deeperspeed_tpu.ops.attention.core import (_reference_latent_attention,
                                                latent_attention)
from deeperspeed_tpu.ops.attention.flash import flash_attention_supported
from deeperspeed_tpu.ops.attention.pallas_flash import (Plan, kernel_name,
                                                        tile_plan)
from deeperspeed_tpu.ops.attention.pallas_flash_mla import mla, supported

NAMES = ("q_nope", "q_rope", "k_nope", "k_rope", "v")


def _operands(B=1, S=200, N=2, dn=128, dr=64, dv=128, dtype=jnp.float32,
              seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    shapes = [(B, S, N, dn), (B, S, N, dr), (B, S, N, dn), (B, S, dr),
              (B, S, N, dv)]
    return tuple(jax.random.normal(k, s, dtype) for k, s in zip(keys, shapes))


def _expanded(q_nope, q_rope, k_nope, k_rope, v):
    """The expanded form, written here: the rotary key copied to every head
    and one 192-wide product under an explicit mask."""
    S, N = q_nope.shape[1:3]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.repeat(k_rope[:, :, None], N, axis=2)],
                        axis=-1)
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) * q.shape[-1] ** -0.5
    seen = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("bnqk,bknd->bqnd", probs, v)


def _assert_same(fn, ref, operands, tol, what):
    np.testing.assert_allclose(np.asarray(fn(*operands), np.float32),
                               np.asarray(ref(*operands)), rtol=tol, atol=tol,
                               err_msg=f"forward ({what})")

    def loss(f):
        return lambda *a: jnp.sum(jnp.square(f(*a).astype(jnp.float32)))

    got = jax.grad(loss(fn), argnums=range(5))(*operands)
    want = jax.grad(loss(ref), argnums=range(5))(*operands)
    for a, b, name in zip(got, want, NAMES):
        assert a.shape == b.shape, name
        scale = max(1.0, float(jnp.max(jnp.abs(b))))
        np.testing.assert_allclose(np.asarray(a, np.float32) / scale,
                                   np.asarray(b) / scale, rtol=10 * tol,
                                   atol=10 * tol, err_msg=f"d{name} ({what})")


@pytest.mark.parametrize("S,block", [
    (200, None),      # one owner block, S no multiple of the tile
    (300, 128),       # three owner blocks: the walk below the diagonal
    (1000, 256),      # S no multiple of the block
    (384, None),      # three blocks of 128 by the plan itself
])
def test_outputs_and_all_five_gradients_against_the_expanded_form(S, block):
    operands = _operands(S=S)
    _assert_same(lambda *a: mla(*a, block=block), _expanded, operands, 3e-5,
                 f"S={S} block={block}")


@pytest.mark.parametrize("N,dr", [(4, 64), (1, 128), (3, 128)],
                         ids=["two_pairs", "one_head", "rope_a_lane_block"])
def test_by_head_count_and_rotary_width(N, dr):
    """Four heads are two pair blocks of q_rope (dk_rope sums over both);
    a rotary part of 128 is a lane block of its own and needs no pairs."""
    operands = _operands(S=256, N=N, dr=dr, seed=1)
    _assert_same(lambda *a: mla(*a, block=128), _expanded, operands, 3e-5,
                 (N, dr))


def test_forward_over_spans(monkeypatch):
    """A resident span shorter than the head: the forward's grid walks the
    spans and skips those above the diagonal."""
    plan = tile_plan(512, 128, jnp.float32, 128, 2)._replace(span=256)
    monkeypatch.setattr(pallas_flash_mla, "tile_plan", lambda *a, **k: plan)
    _assert_same(mla, _expanded, _operands(S=512, seed=2), 3e-5, "spans")


def test_value_width_and_batch_and_bfloat16():
    operands = _operands(B=2, S=256, dv=256, dtype=jnp.bfloat16, seed=3)
    wide = tuple(t.astype(jnp.float32) for t in operands)
    got = mla(*operands)
    assert got.shape == (2, 256, 2, 256) and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(_expanded(*wide)), atol=0.03)


def test_the_plain_path_is_the_expanded_form_and_takes_any_shape():
    """``core.py``'s plain path (the CPU's, and the kernel's oracle) against
    the form written here, at heads no kernel takes (16 + 8 | 16)."""
    operands = _operands(B=2, S=40, N=3, dn=16, dr=8, dv=16, seed=4)
    assert not supported(operands[0].shape, 8, 16)
    _assert_same(_reference_latent_attention, _expanded, operands, 2e-5,
                 "plain")
    np.testing.assert_allclose(
        np.asarray(latent_attention(*operands, use_pallas=True)),
        np.asarray(_expanded(*operands)), atol=2e-5)


def test_dispatch_takes_the_kernel_and_counts_the_path():
    operands = _operands(S=130, seed=5)
    before = telemetry.kernel_paths().get("flash_attention_mla", {}).get(
        "in_place_2", 0)
    got = latent_attention(*operands, use_pallas=True)
    assert telemetry.kernel_paths()["flash_attention_mla"][
        "in_place_2"] == before + 1
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_expanded(*operands)), atol=3e-5)


def test_scale_is_the_calls_own():
    operands = _operands(S=128, seed=6)
    np.testing.assert_allclose(
        np.asarray(mla(*operands, scale=0.05)),
        np.asarray(_reference_latent_attention(*operands, scale=0.05)),
        atol=3e-5)


@pytest.mark.parametrize("shape,dr,dv,dtype,takes", [
    ((4, 8192, 16, 128), 64, 128, jnp.bfloat16, True),    # the cell's call
    ((4, 8192, 16, 128), 128, 128, jnp.bfloat16, True),
    ((1, 300, 2, 128), 64, 256, jnp.float32, True),
    ((4, 8192, 15, 128), 64, 128, jnp.bfloat16, False),   # odd heads in pairs
    ((4, 8192, 16, 128), 32, 128, jnp.bfloat16, False),   # four to a block
    ((4, 8192, 16, 64), 64, 128, jnp.bfloat16, False),    # nope no lane block
    ((4, 8192, 16, 128), 64, 192, jnp.bfloat16, False),   # v no lane block
    ((1, 32768, 16, 128), 64, 128, jnp.bfloat16, False),  # q side not resident
    ((4, 8192, 16, 128), 64, 128, jnp.float16, False),
])
def test_which_shapes_the_kernel_takes(shape, dr, dv, dtype, takes):
    assert supported(shape, dr, dv, dtype) is takes
    assert flash_attention_supported(shape, dtype, rope_dim=dr,
                                     v_dim=dv) is takes
    if not takes and jnp.dtype(dtype) != jnp.float16 and shape[1] <= 8192:
        B, S, N, dn = 1, 128, shape[2], shape[3]
        operands = _operands(B, S, N, dn, dr, dv)
        with pytest.raises(ValueError, match="flash_attention_mla takes no"):
            mla(*operands)


def test_operands_that_are_no_latent_call_are_refused():
    q_nope, q_rope, k_nope, k_rope, v = _operands(S=128)
    with pytest.raises(ValueError, match="no latent-attention call"):
        mla(q_nope, q_rope, k_nope, jnp.repeat(k_rope[:, :, None], 2, 2), v)


#: the accepted cells' own ``mha`` calls (S, D, N, KV heads, window) -> the
#: plan and the kernel's name at the parent of the PR that added the latent
#: kernel: a call with one width and no second key is what it was
PINNED = {
    "train-410m": ((2048, 64, 16, 16, None),
                   Plan(2048, 256, 512, 2048, True, 2, 128, 0)),
    "train-160m": ((1024, 64, 12, 12, None),
                   Plan(1024, 256, 512, 1024, True, 2, 128, 0)),
    "train-ouro-2.6b-loop4": ((4096, 128, 16, 16, None),
                              Plan(2048, 512, 512, 4096, True, 1, 128, 0)),
    "train-nemotron3-super-ep64-8k": ((8192, 128, 8, 1, None), Plan(
        2048, 512, 512, 8192, True, 1, 128, 0)),
    "train-mellum2-ep4-8k.window": ((8192, 128, 32, 4, 1024), Plan(
        2048, 512, 512, 8192, True, 1, 128, 1024)),
    "train-mellum2-ep4-8k.full": ((8192, 128, 32, 4, None), Plan(
        2048, 512, 512, 8192, True, 1, 128, 0)),
    "train-laguna-s-ep32-8k.window": ((8192, 128, 36, 4, 512), Plan(
        2048, 512, 512, 8192, True, 1, 128, 512)),
    "train-laguna-s-ep32-8k.full": ((8192, 128, 24, 4, None), Plan(
        2048, 512, 512, 8192, True, 1, 128, 0)),
    "train-zaya1-8b-ep2-8k": ((8192, 128, 8, 2, None), Plan(
        2048, 512, 512, 8192, True, 1, 128, 0)),
}


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_an_equal_width_calls_plan_and_name_are_what_they_were(cell):
    (S, D, N, kv, window), plan = PINNED[cell]
    got = tile_plan(S, D, jnp.bfloat16, N=N, window=window, kv_heads=kv)
    assert got == plan
    assert kernel_name(got) == ("flash_attention_window" if window
                                else "flash_attention")


def test_the_latent_call_is_planned_by_the_plain_kernels_plan():
    """The cell's call walks the tiles of a plain call at its ``d_nope``."""
    assert tile_plan(8192, 128, jnp.bfloat16, N=16) == Plan(
        2048, 512, 512, 8192, True, 1, 128, 0)
    assert pallas_flash_mla.KERNEL == "flash_attention_mla"

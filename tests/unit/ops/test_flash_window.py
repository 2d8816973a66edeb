"""The flash kernel's causal window (``pallas_flash.mha(window=...)``), in
interpret mode, against plain attention under the same mask: outputs and all
three gradients, for windows smaller than, equal to and larger than a block,
lengths that are no multiple of the block, the forward over spans and the
two-pass backward; a window that reaches the whole length is the full call;
what the walk visits, counted."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu import telemetry
from deeperspeed_tpu.ops.attention import pallas_flash
from deeperspeed_tpu.ops.attention.core import (_reference_attention,
                                                dot_product_attention)
from deeperspeed_tpu.ops.attention.pallas_flash import (_band, _band_tiles,
                                                        band_pairs, mha,
                                                        tile_plan)


def _qkv(B=1, S=512, N=2, D=16, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, S, N, D), dtype) for k in ks)


def _masked_plain(q, k, v, window):
    """Plain attention under an explicit [S, S] mask, written here."""
    S = q.shape[1]
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) * q.shape[-1] ** -0.5
    probs = jax.nn.softmax(jnp.where((j <= i) & (j > i - window), scores,
                                     -jnp.inf), -1)
    return jnp.einsum("bnqk,bknd->bqnd", probs, v)


def _assert_same(fn, ref, q, k, v, tol, what):
    np.testing.assert_allclose(np.asarray(fn(q, k, v), np.float32),
                               np.asarray(ref(q, k, v)), rtol=tol, atol=tol,
                               err_msg=f"forward ({what})")

    def loss(f):
        return lambda *a: jnp.sum(jnp.square(f(*a).astype(jnp.float32)))

    got = jax.grad(loss(fn), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        scale = max(1.0, float(jnp.max(jnp.abs(b))))
        np.testing.assert_allclose(np.asarray(a, np.float32) / scale,
                                   np.asarray(b) / scale, rtol=10 * tol,
                                   atol=10 * tol, err_msg=f"d{name} ({what})")


@pytest.mark.parametrize("S,block,window", [
    (512, 128, 64),       # smaller than a block: both edges in one chunk
    (512, 128, 128),      # a block: the left edge is the next chunk's diagonal
    (512, 128, 320),      # larger, no multiple: two chunks crossed
    (512, 128, 256),      # two blocks: one chunk seen whole between the edges
    (512, 128, 129), (512, 128, 1), (512, 256, 511),
    (1000, 256, 300),     # S no multiple of the block
    (1000, None, 100),    # the plan's own tiles (one block to the head)
    (384, None, 200),     # three owner blocks of 128
])
def test_window_outputs_and_gradients(S, block, window):
    q, k, v = _qkv(S=S)
    _assert_same(lambda *a: mha(*a, block=block, window=window),
                 lambda *a: _masked_plain(*a, window), q, k, v, 3e-5,
                 f"S={S} block={block} window={window}")


@pytest.mark.parametrize("N,D", [(2, 64), (1, 128), (3, 64)],
                         ids=["two_heads_a_block", "a_head_a_block", "folded"])
def test_window_by_layout(N, D):
    q, k, v = _qkv(S=384, N=N, D=D)
    _assert_same(lambda *a: mha(*a, window=160),
                 lambda *a: _masked_plain(*a, 160), q, k, v, 3e-5, (N, D))


@pytest.mark.parametrize("two_pass", [False, True],
                         ids=["one_kernel_bwd", "two_pass_bwd"])
@pytest.mark.parametrize("heads", [24, 36], ids=["groups_of_6", "groups_of_9"])
def test_window_512_on_gqa_copies_of_four_kv_heads(heads, two_pass):
    """Laguna's calls (``models/laguna.py``): 24 | 36 query heads of 128 on
    GQA's copy of 4 KV heads under a window of 512, a band of two chunks of
    the walk here; outputs and gradients through the copy, against plain
    attention under the mask, with both backward forms."""
    S, D, kv, window = 1024, 128, 4, 512
    plan = tile_plan(S, D, jnp.float32, block=256, N=heads, window=window)
    assert plan.window == window and plan.group == 1
    assert _band(plan.block, window) == (1, [2])
    plan = plan._replace(resident_bwd=not two_pass)

    def copied(fn):
        return lambda q, k, v: fn(q, *(jnp.repeat(t, heads // kv, axis=2)
                                       for t in (k, v)))

    def kernel(q, k, v):
        o = pallas_flash._mha(*(t.reshape(1, S, heads * D) for t in (q, k, v)),
                              True, float(D) ** -0.5, plan)
        return o.reshape(1, S, heads, D)

    q, _, _ = _qkv(S=S, N=heads, D=D)
    _, k, v = _qkv(S=S, N=kv, D=D, seed=1)
    _assert_same(copied(kernel), copied(lambda *a: _masked_plain(*a, window)),
                 q, k, v, 3e-5, f"heads={heads} two_pass={two_pass}")


def _grouped(S, N, kv, D=128, dtype=jnp.float32):
    q, _, _ = _qkv(S=S, N=N, D=D, dtype=dtype)
    _, k, v = _qkv(S=S, N=kv, D=D, dtype=dtype, seed=1)
    return q, k, v


def _on_copies(fn):
    """``fn`` on k and v repeated out to the query heads (GQA's copy)."""
    return lambda q, k, v: fn(q, *(
        jnp.repeat(t, q.shape[2] // t.shape[2], axis=2) for t in (k, v)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("N,kv,window", [
    (32, 4, 1024),      # train-mellum2-ep4-8k's windowed layers
    (36, 4, 512),       # train-laguna-s-ep32-8k's
    (24, 4, 300),       # a window that crosses a block
    (8, 1, 512), (8, 1, 1024), (4, 2, 300), (4, 2, 1024),
])
def test_window_on_kv_heads_addressed_by_the_group(N, kv, window, dtype):
    """A windowed call on k and v at their KV heads (a query head reads its
    KV head's block where it lies; dk and dv leave the backward kernel
    summed over the group): outputs and gradients against
    ``_reference_attention`` on the copies, over five owner blocks of a
    padded length, and counted ``grouped_<N / kv>``."""
    S = 1200
    q, k, v = _grouped(S, N, kv, dtype=dtype)
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    before = dict(telemetry.kernel_paths().get(
        "flash_attention_window_kv_heads", {}))
    _assert_same(
        lambda *a: mha(*a, block=256, window=window),
        _on_copies(lambda *a: _reference_attention(*map(f32, a),
                                                   window=window)),
        q, k, v, 3e-5 if dtype == jnp.float32 else 2e-2,
        f"{N} / {kv} window={window} {jnp.dtype(dtype).name}")
    after = telemetry.kernel_paths()["flash_attention_window_kv_heads"]
    assert {name for name in after if after[name] != before.get(name, 0)} \
        == {f"grouped_{N // kv}"}


@pytest.mark.parametrize("window", [200, 512])
def test_window_on_kv_heads_through_the_two_pass_backward(window):
    S, N, kv, D = 1000, 6, 2, 128
    plan = tile_plan(S, D, jnp.float32, block=256, N=N, window=window,
                     kv_heads=kv)._replace(resident_bwd=False)

    def fn(q, k, v):
        o = pallas_flash._mha(*(t.reshape(1, S, -1) for t in (q, k, v)),
                              True, float(D) ** -0.5, plan)
        return o.reshape(1, S, N, D)

    _assert_same(fn, _on_copies(lambda *a: _masked_plain(*a, window)),
                 *_grouped(S, N, kv), 3e-5, f"two-pass window={window}")


@pytest.mark.parametrize("changes", [
    dict(resident_bwd=False),                       # the two-pass backward
    dict(block=256, sub=128, rows=128, span=256),   # the forward over spans
    dict(block=512, sub=256, rows=256),
], ids=["two_pass_bwd", "spans", "row_groups"])
@pytest.mark.parametrize("window", [100, 256, 600])
def test_window_on_plans_the_cell_does_not_take(changes, window):
    S, N, D = 1000, 2, 64
    plan = tile_plan(S, D, jnp.float32, N=N, window=window)._replace(**changes)
    assert plan.window == window and plan.group == 2

    def fn(q, k, v):
        o = pallas_flash._mha(*(t.reshape(1, S, N * D) for t in (q, k, v)),
                              True, float(D) ** -0.5, plan)
        return o.reshape(1, S, N, D)

    q, k, v = _qkv(S=S, N=N, D=D)
    _assert_same(fn, lambda *a: _masked_plain(*a, window), q, k, v, 3e-5,
                 f"{changes} window={window}")


def test_a_window_that_reaches_the_whole_length_is_the_full_call():
    q, k, v = _qkv(S=256)
    assert tile_plan(256, 16, jnp.float32, window=256).window == 0
    assert tile_plan(256, 16, jnp.float32, window=255).window == 255
    text = [jax.jit(lambda *a, w=w: mha(*a, window=w)).lower(q, k, v).as_text()
            for w in (None, 256, 4096)]
    assert text[0] == text[1] == text[2]
    np.testing.assert_array_equal(np.asarray(mha(q, k, v, window=300)),
                                  np.asarray(mha(q, k, v)))


def test_window_is_a_causal_calls():
    q, k, v = _qkv(S=128)
    with pytest.raises(ValueError):
        mha(q, k, v, causal=False, window=64)
    with pytest.raises(ValueError):
        dot_product_attention(q, k, v, causal=False, window=64)


def test_windowed_calls_count_and_run_under_a_name_of_their_own():
    q, k, v = _qkv(S=256)
    before = telemetry.kernel_paths()
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(mha(*a, window=64))))(q, k, v)
    after = telemetry.kernel_paths()
    assert (sum(after["flash_attention_window"].values())
            - sum(before.get("flash_attention_window", {}).values())) == 1
    assert after.get("flash_attention") == before.get("flash_attention")
    text = jax.jit(jax.grad(lambda *a: jnp.sum(mha(*a, window=64)))).lower(
        q, k, v).as_text(debug_info=True)
    assert "flash_attention_window" in text and "pallas_call" in str(jaxpr)


def test_dispatch_takes_the_window_and_the_plain_path_masks_it():
    q, k, v = _qkv(S=200)
    want = _masked_plain(q, k, v, 48)
    for use_pallas in (True, False):
        got = dot_product_attention(q, k, v, causal=True, window=48,
                                    use_pallas=use_pallas)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(
        np.asarray(_reference_attention(q, k, v, window=48)),
        np.asarray(want), rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("block,sub,window", [
    (2048, 512, 1024), (1024, 512, 1024), (128, 128, 64), (128, 128, 320),
    (256, 128, 129), (512, 256, 1)])
def test_band_tiles_cover_the_band_and_nothing_a_group_cannot_see(
        block, sub, window):
    """Every visible (row, column) pair of a row block lies in exactly one
    piece, a piece's mask hides exactly its hidden pairs, a piece without a
    mask has none, and no piece lies wholly outside the band."""
    full, partial = _band(block, window)
    rows = np.arange(block)[:, None]
    for d in range(0, (partial[-1] if partial else full) + 2):
        cols = np.arange(block)[None, :]
        seen = (cols - d * block <= rows) & (cols - d * block > rows - window)
        covered = np.zeros_like(seen)
        if 1 <= d <= full:
            assert seen.all()
            continue
        tiles = _band_tiles(block, sub, window, d) if (
            d == 0 or d in partial) else []
        if not tiles:
            assert not seen.any()
        for row0, pieces in tiles:
            for c0, nc, mask in pieces:
                assert c0 % 128 == 0 and nc % 128 == 0 and nc > 0
                part = seen[row0:row0 + sub, c0:c0 + nc]
                assert part.any()
                if mask is None:
                    assert part.all()
                else:
                    a = np.arange(sub)[:, None]
                    c = np.arange(nc)[None, :]
                    left, right = mask
                    valid = np.ones_like(part)
                    if left is not None:
                        valid &= c > a + left
                    if right is not None:
                        valid &= c <= a + right
                    np.testing.assert_array_equal(valid, part)
                assert not covered[row0:row0 + sub, c0:c0 + nc].any()
                covered[row0:row0 + sub, c0:c0 + nc] = True
        assert not (seen & ~covered).any()


def test_band_pairs_by_hand():
    # the cell's: 8192 rows, the last 7169 see 1024 columns, the first 1023
    # see 1..1023
    assert band_pairs(8192, 1024) == 7169 * 1024 + 1023 * 1024 // 2 == 7864832
    assert band_pairs(8192, None) == band_pairs(8192, 8192) == 33558528
    assert band_pairs(4, 2) == 7 and band_pairs(4, 1) == 4


def test_executed_pieces_at_the_cells_shapes():
    """At S 8192, window 1024 (block 2048, sub 512) a row group computes
    W / sub + 1 = 3 pieces of 512 columns wherever the band is whole: the
    walk executes 45 of the square's 256 sub x sub squares a head (16 row
    groups of 3, less the first two groups' 3), the full call 136."""
    plan = tile_plan(8192, 128, jnp.bfloat16, N=32, window=1024)
    assert (plan.block, plan.sub, plan.window) == (2048, 512, 1024)
    full, partial = _band(plan.block, plan.window)
    assert (full, partial) == (0, [1])
    squares = 0
    for i in range(8192 // plan.block):
        for d in [0] + [d for d in partial if d <= i]:
            squares += sum(nc for _, pieces in _band_tiles(
                plan.block, plan.sub, plan.window, d)
                for _, nc, _ in pieces) // plan.sub
    assert squares == 45
    assert pallas_flash.walk_counts(plan, 8192)[0] == 136

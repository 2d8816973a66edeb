"""In-tree Pallas flash attention numerics (pattern of
``tests/unit/ops/test_transformer_kernels.py``: kernel vs jnp reference,
fwd + grads, interpret mode off-TPU).

Reference parity target: the fused attention/softmax kernels of
``csrc/transformer/softmax_kernels.cu`` -- here the checklist is exactness
against the naive [S, S] softmax attention, including NON-multiple-of-128
sequence lengths (VERDICT r1 required S=1000), under the tile sizes the
kernel picks for itself (``tile_plan``) and under forced ones."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu.ops.attention import pallas_flash
from deeperspeed_tpu.ops.attention.core import _reference_attention
from deeperspeed_tpu.ops.attention.pallas_flash import (mha, tile_plan,
                                                        walk_counts)


# the benchmark cells' attention calls: train-410m, train-160m,
# train-ouro-2.6b-loop4
CELL_SHAPES = [(8, 2048, 16, 64), (16, 1024, 12, 64), (4, 4096, 16, 128)]


def _qkv(B=2, S=256, N=2, D=16, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, S, N, D)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def _grads(fn, q, k, v):
    return jax.grad(lambda *a: jnp.sum(jnp.square(fn(*a).astype(jnp.float32))),
                    argnums=(0, 1, 2))(q, k, v)


def _assert_fwd_and_grads(fn, ref, q, k, v, tol, what):
    np.testing.assert_allclose(
        np.asarray(fn(q, k, v), np.float32), np.asarray(ref(q, k, v)),
        rtol=tol, atol=tol, err_msg=f"forward mismatch ({what})")
    for a, b, name in zip(_grads(fn, q, k, v), _grads(ref, q, k, v), "qkv"):
        scale = max(1.0, float(jnp.max(jnp.abs(b))))
        np.testing.assert_allclose(
            np.asarray(a, np.float32) / scale, np.asarray(b) / scale,
            rtol=10 * tol, atol=10 * tol,
            err_msg=f"d{name} mismatch ({what})")


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S", [128, 256, 1000, 40])
def test_forward_matches_reference(S, causal):
    q, k, v = _qkv(S=S)
    got = mha(q, k, v, causal=causal)
    want = _reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("S,block", [(256, None), (1000, None), (1024, 128)])
def test_grads_match_reference(S, block):
    # block=128 at S=1024: eight owner blocks, so the walk inside the kernel
    # runs up to seven chunks off the edge before the one on it
    q, k, v = _qkv(S=S, B=1, N=2, D=16)

    def loss_kernel(q, k, v):
        return jnp.sum(jnp.square(mha(q, k, v, causal=True, block=block)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(_reference_attention(q, k, v, causal=True)))

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gk, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name} mismatch (S={S})")


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S", [128, 384, 1000, 1024, 2048])
def test_fwd_and_grads_at_the_plans_own_tiles(S, causal):
    """D = 64 under the tile sizes the code picks: one owner block at
    S <= 2048 (every row group against all its columns at once), several at
    S = 384; S = 1000 pads the chunk the diagonal crosses."""
    q, k, v = _qkv(S=S, B=1, N=1, D=64)
    _assert_fwd_and_grads(
        lambda *a: mha(*a, causal=causal),
        lambda *a: _reference_attention(*a, causal=causal),
        q, k, v, 3e-5, f"S={S} causal={causal}")


@pytest.mark.parametrize("D", [96, 128])
def test_fwd_and_grads_wide_heads(D):
    q, k, v = _qkv(S=512, B=1, N=2, D=D)
    _assert_fwd_and_grads(
        lambda *a: mha(*a, causal=True),
        lambda *a: _reference_attention(*a, causal=True),
        q, k, v, 3e-5, f"D={D}")


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("B,S,N,D,group", [
    (2, 256, 4, 64, 2),      # two heads to a 128-lane block
    (1, 384, 16, 64, 2),     # train-410m's head count, three owner blocks
    (1, 256, 12, 64, 2),     # train-160m's
    (1, 1000, 2, 64, 2),     # a padded length: axis 1 of [B, S, N*D]
    (1, 512, 2, 128, 1),     # a head is a block
    (1, 256, 2, 256, 1),     # a head is two blocks' worth of lanes
    (1, 256, 4, 32, 0),      # folded: heads thinner than half a block
    (1, 256, 2, 96, 0),      # folded: heads are no whole lane blocks
    (2, 256, 3, 64, 0),      # folded: the last block would be half a head
], ids=lambda t: str(t))
def test_fwd_and_grads_by_layout(B, S, N, D, group, causal):
    """Forward and the three gradients against the fp32 reference where the
    kernel takes the projections' own ``[B, S, N*D]`` (heads as column
    groups, ``group`` to a block) and where it folds to ``[B*N, S, D]``."""
    assert tile_plan(S, D, jnp.float32, N=N).group == group
    q, k, v = _qkv(S=S, B=B, N=N, D=D)
    _assert_fwd_and_grads(
        lambda *a: mha(*a, causal=causal),
        lambda *a: _reference_attention(*a, causal=causal),
        q, k, v, 3e-5, f"{(B, S, N, D)} causal={causal}")


@pytest.mark.parametrize("B,S,N,D", [(1, 1000, 4, 64), (2, 256, 2, 128),
                                     (1, 256, 3, 64)])
def test_bf16_fwd_and_grads_by_layout(B, S, N, D):
    """bf16 through the in-place paths (two heads to a block; a head a
    block) and the folded one, against the fp32 reference on the same
    values."""
    q, k, v = _qkv(S=S, B=B, N=N, D=D, dtype=jnp.bfloat16)
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    _assert_fwd_and_grads(
        lambda *a: mha(*a, causal=True),
        lambda *a: _reference_attention(*map(f32, a), causal=True),
        q, k, v, 2e-2, f"bf16 {(B, S, N, D)}")
    assert all(g.dtype == jnp.bfloat16
               for g in _grads(lambda *a: mha(*a, causal=True), q, k, v))


def _lse_residual(q, k, v, causal, **plan_changes):
    """The lse the forward rule keeps for the backward, as ``[B, N, S]``."""
    B, S, N, D = q.shape
    plan = tile_plan(S, D, q.dtype, N=N)._replace(**plan_changes)
    if plan.group:
        operands = [t.reshape(B, S, N * D) for t in (q, k, v)]
    else:
        operands = [jnp.swapaxes(t, 1, 2).reshape(B * N, S, D)
                    for t in (q, k, v)]
    _, (_, _, _, o, lse) = pallas_flash._mha_fwd(
        *operands, causal, float(D) ** -0.5, plan)
    sp = o.shape[1]
    # one float a row, rows on lanes: a row of statistics per head, heads of
    # one lane block together
    heads = max(plan.group, 1)
    assert lse.dtype == jnp.float32
    assert lse.shape == (B * N // heads, heads, sp)
    return lse.reshape(B, N, sp)[:, :, :S]


def _reference_lse(q, k, v, causal):
    S, D = q.shape[1], q.shape[3]
    s = jnp.einsum("bqnd,bknd->bnqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST) * float(D) ** -0.5
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    return jax.nn.logsumexp(s, axis=-1)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("B,S,N,D,changes", [
    # the layouts of test_fwd_and_grads_by_layout
    (2, 256, 4, 64, {}), (1, 384, 16, 64, {}), (1, 256, 12, 64, {}),
    (1, 1000, 2, 64, {}), (1, 512, 2, 128, {}), (1, 256, 2, 256, {}),
    (1, 256, 4, 32, {}), (1, 256, 2, 96, {}), (2, 256, 3, 64, {}),
    # one block that is on the diagonal, padded in rows and columns at once
    (1, 40, 2, 16, {}),
    # running statistics over several spans (the lse leaves in the last
    # one), row groups, one edge tile: folded, two heads to a block, a head
    # a block
    (1, 1000, 2, 16, dict(block=256, sub=128, rows=128, span=256)),
    (1, 1000, 2, 16, dict(block=512, sub=256, rows=256)),
    (1, 1000, 2, 16, dict(block=256, sub=256, rows=256)),
    (2, 1000, 2, 64, dict(block=256, sub=128, rows=128, span=256)),
    (2, 1000, 1, 128, dict(block=256, sub=128, rows=128, span=256)),
], ids=lambda t: str(t))
def test_lse_is_one_float_a_row(B, S, N, D, changes, causal):
    """What the forward kernel writes beside its output: the log-sum-exp of
    each row's scaled, masked scores, one float32 a row (128 lane-replicated
    copies of it are four times the bytes of ``o`` at D = 64)."""
    q, k, v = _qkv(S=S, B=B, N=N, D=D)
    np.testing.assert_allclose(
        np.asarray(_lse_residual(q, k, v, causal, **changes)),
        np.asarray(_reference_lse(q, k, v, causal)), rtol=2e-5, atol=2e-5)


def test_lse_of_bf16_operands_is_float32_and_close():
    q, k, v = _qkv(S=1000, B=1, N=4, D=64, dtype=jnp.bfloat16)
    np.testing.assert_allclose(
        np.asarray(_lse_residual(q, k, v, True)),
        np.asarray(_reference_lse(q, k, v, True)), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("causal", [True, False])
def test_grads_when_the_edge_tile_is_the_padded_one(causal):
    """S = 40: one 128-row block whose single tile is on the diagonal, holds
    the padded columns and the padded rows at once."""
    q, k, v = _qkv(S=40, B=1, N=2, D=16)
    _assert_fwd_and_grads(
        lambda *a: mha(*a, causal=causal),
        lambda *a: _reference_attention(*a, causal=causal),
        q, k, v, 3e-5, f"S=40 causal={causal}")


def _folded(S, D, causal=True, **plan_changes):
    """``_mha`` on folded [B*N, S, D] inputs under a changed plan."""
    plan = tile_plan(S, D, jnp.float32)._replace(**plan_changes)

    def fn(q, k, v):
        B, _, N, _ = q.shape
        fold = lambda t: jnp.swapaxes(t, 1, 2).reshape(B * N, S, D)  # noqa: E731
        o = pallas_flash._mha(fold(q), fold(k), fold(v), causal,
                              float(D) ** -0.5, plan)
        return jnp.swapaxes(o.reshape(B, N, S, D), 1, 2)
    return fn


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("changes", [
    dict(resident_bwd=False),                  # two-pass backward (long S)
    dict(block=256, sub=128, rows=128, span=256),   # forward over 4 spans
    dict(block=512, sub=256, rows=256),        # interior tiles in row groups
    dict(block=256, sub=256, rows=256),        # sub == block: one edge tile
], ids=["two_pass_bwd", "spans", "row_groups", "sub_is_block"])
def test_plans_the_cells_do_not_take(changes, causal):
    """Paths ``tile_plan`` keeps for shapes no test can afford (S beyond
    8k: a forward that holds a span of k/v, the two-pass backward) and tile
    sizes other shapes get, forced at S = 1000."""
    q, k, v = _qkv(S=1000, B=1, N=2, D=16)
    _assert_fwd_and_grads(
        _folded(1000, 16, causal, **changes),
        lambda *a: _reference_attention(*a, causal=causal),
        q, k, v, 3e-5, f"{changes} causal={causal}")


def _in_place(S, N, D, causal=True, **plan_changes):
    """``_mha`` on ``[B, S, N*D]`` inputs (heads as column groups) under a
    changed plan."""
    plan = tile_plan(S, D, jnp.float32, N=N)._replace(**plan_changes)
    assert plan.group

    def fn(q, k, v):
        B = q.shape[0]
        o = pallas_flash._mha(*(t.reshape(B, S, N * D) for t in (q, k, v)),
                              causal, float(D) ** -0.5, plan)
        return o.reshape(B, S, N, D)
    return fn


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("N,D", [(2, 64), (1, 128)], ids=["two_heads", "one"])
@pytest.mark.parametrize("changes", [
    dict(resident_bwd=False),                  # two-pass backward (S >= 16k)
    dict(block=256, sub=128, rows=128, span=256),   # forward over spans
], ids=["two_pass_bwd", "spans"])
def test_long_sequence_plans_in_place(changes, N, D, causal):
    """The paths S of 16k and more take (a forward that holds a span of
    k/v with running statistics per head, the two-pass backward with a row
    of ``delta`` per head), forced at S = 1000 on the in-place layout."""
    q, k, v = _qkv(S=1000, B=2, N=N, D=D)
    _assert_fwd_and_grads(
        _in_place(1000, N, D, causal, **changes),
        lambda *a: _reference_attention(*a, causal=causal),
        q, k, v, 3e-5, f"{changes} N={N} D={D} causal={causal}")


def _equations(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs (a jit, a custom
    VJP's forward) included; a kernel's body is not the program's (the
    lse's 128 x 128 transposes in VMEM are no transpose in HBM)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _primitives(jaxpr):
    """Names of every primitive of a jaxpr (``_equations``)."""
    return (eqn.primitive.name for eqn in _equations(jaxpr))


@pytest.mark.parametrize("shape,group", list(zip(CELL_SHAPES, (2, 2, 1)))
                         + [((2, 2048, 8, 96), 0), ((2, 2048, 3, 64), 0),
                            ((2, 2048, 8, 32), 0), ((2, 2048, 4, 256), 1)],
                         ids=lambda t: str(t))
def test_layout_is_decided_from_heads_and_head_dim(shape, group):
    """At the cells' shapes the program of ``mha`` and of its gradient
    holds no transpose and one kernel call a pass; ``tile_plan`` says so
    from (N, D) alone, whatever S; the fallbacks fold (a transpose each
    way).  A traced call is counted by its path."""
    from deeperspeed_tpu.telemetry import kernel_paths

    B, S, N, D = shape
    assert tile_plan(S, D, jnp.bfloat16, N=N).group == group
    assert {tile_plan(s, D, jnp.float32, N=N).group
            for s in (128, 1000, 32768)} == {group}
    path = f"in_place_{group}" if group else "folded"
    before = kernel_paths().get("flash_attention", {}).get(path, 0)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    fwd = list(_primitives(jax.make_jaxpr(mha)(x, x, x).jaxpr))
    both = list(_primitives(jax.make_jaxpr(jax.grad(
        lambda *a: mha(*a).astype(jnp.float32).sum(), (0, 1, 2)))(x, x, x)
        .jaxpr))
    assert fwd.count("pallas_call") == 1 and both.count("pallas_call") == 2
    assert ("transpose" in fwd) == ("transpose" in both) == (group == 0)
    assert kernel_paths()["flash_attention"][path] == before + 2


def test_bf16_forward_close():
    q, k, v = _qkv(S=256, dtype=jnp.bfloat16)
    got = mha(q, k, v, causal=True)
    want = _reference_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                                v.astype(jnp.float32), causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("S", [1000, 2048])
def test_bf16_fwd_and_grads_close(S):
    """bf16 inputs against the fp32 reference on the same values, with
    ``test_bf16_forward_close``'s tolerances."""
    q, k, v = _qkv(S=S, B=1, N=1, D=64, dtype=jnp.bfloat16)
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    got = mha(q, k, v, causal=True)
    assert got.dtype == jnp.bfloat16
    _assert_fwd_and_grads(
        lambda *a: mha(*a, causal=True),
        lambda *a: _reference_attention(*map(f32, a), causal=True),
        q, k, v, 2e-2, f"bf16 S={S}")
    assert all(g.dtype == jnp.bfloat16
               for g in _grads(lambda *a: mha(*a, causal=True), q, k, v))


def test_scale_override():
    q, k, v = _qkv(S=128)
    got = mha(q, k, v, causal=True, scale=0.5)
    want = _reference_attention(q, k, v, causal=True, scale=0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("block", [128, 256, 512])
def test_block_override_is_honoured(block):
    plan = tile_plan(1024, 64, jnp.bfloat16, block)
    assert plan.block == block and block % plan.sub == 0
    q, k, v = _qkv(S=1024, B=1, N=1, D=16)
    got = mha(q, k, v, causal=True, block=block)
    want = _reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_dispatch_uses_in_tree_kernel_for_odd_seq():
    """core.dot_product_attention routes S=1000 to the in-tree kernel when
    pallas is forced on (round-1 restriction removed)."""
    from deeperspeed_tpu.ops.attention.core import dot_product_attention

    q, k, v = _qkv(S=200, B=1, N=1, D=16)
    got = dot_product_attention(q, k, v, causal=True, use_pallas=True)
    want = _reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_grad_of_padded_rows_is_zero_free():
    """Padded tail (S=40 -> tile 128) must not leak NaNs into grads."""
    q, k, v = _qkv(S=40, B=1, N=1, D=8)
    g = jax.grad(lambda q: jnp.sum(mha(q, k, v, causal=True)))(q)
    assert np.isfinite(np.asarray(g)).all()


# ------------------------------------------------ the tile plan, as counts
@pytest.mark.parametrize("S", [1024, 2048, 1000, 384, 4096, 8192])
@pytest.mark.parametrize("D", [64, 128])
def test_walk_counts_closed_form(S, D):
    """Causal: with n = padded S / sub, the walk computes n(n+1)/2 of the
    n^2 squares and masks the n on the diagonal, whatever the block."""
    plan = tile_plan(S, D, jnp.bfloat16)
    sp = -(-S // plan.block) * plan.block
    n = sp // plan.sub
    assert sp % plan.block == 0 and plan.block % plan.sub == 0
    assert plan.span % plan.block == 0 and sp % plan.span == 0
    assert walk_counts(plan, S, True) == (n * (n + 1) // 2, n, n * n)


@pytest.mark.parametrize("S,was,now", [(2048, 0.75, 0.5625),
                                       (1024, 1.00, 0.625)])
def test_executed_share_at_the_cells_shapes(S, was, now):
    """D = 64: the share of the S x S square the walk computes, against the
    one-level 1024 tiles' (PERF.md section 6, PR 28), and the masked share
    (was 0.50 and 1.00)."""
    executed, masked, total = walk_counts(
        tile_plan(S, 64, jnp.bfloat16), S, True)
    assert executed / total == now < was
    assert masked / total == {2048: 0.125, 1024: 0.25}[S]


@pytest.mark.parametrize("S", [1024, 1000])
def test_walk_counts_non_causal(S):
    """Non-causal: every square computed; only a padded length masks, and
    then only the last column of squares."""
    plan = tile_plan(S, 64, jnp.bfloat16)
    n = 1024 // plan.sub
    assert walk_counts(plan, S, False) == (n * n, n if S == 1000 else 0,
                                           n * n)


def test_plan_splits_what_does_not_fit_vmem():
    """S = 32k at D = 128: the forward holds a span of k/v, not the whole,
    and the backward goes two-pass; the cells' shapes hold everything."""
    long = tile_plan(32768, 128, jnp.bfloat16)
    assert long.span < 32768 and not long.resident_bwd
    for S in (1024, 2048, 8192):
        plan = tile_plan(S, 128, jnp.bfloat16)
        assert plan.span == S and plan.resident_bwd


# ------------------------------------------------- grouped-query heads
# query heads over KV heads: one KV head; the cells' (train-mellum2-ep4-8k
# 32 / 4, train-laguna-s-ep32-8k 36 / 4 windowed and 24 / 4 full); a pair
GROUPED = [(8, 1), (32, 4), (36, 4), (24, 4), (4, 2)]


def _grouped_qkv(S, N, kv, B=1, D=128, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(key, (B, S, n, D), dtype)
                 for key, n in zip(ks, (N, kv, kv)))


def _on_copies(fn):
    """``fn`` on k and v repeated out to the query heads (GQA's copy)."""
    return lambda q, k, v: fn(q, *(
        jnp.repeat(t, q.shape[2] // t.shape[2], axis=2) for t in (k, v)))


def _kv_heads_counted(kernel="flash_attention"):
    from deeperspeed_tpu.telemetry import kernel_paths

    return dict(kernel_paths().get(kernel + "_kv_heads", {}))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("N,kv", GROUPED)
def test_grouped_query_heads_fwd_and_grads(N, kv, dtype):
    """k and v at their KV heads: the output and dq, and dk and dv summed
    over a KV head's query heads inside the backward kernel, against the
    reference on the copies; a padded length of three owner blocks, two
    batch rows where the heads are few."""
    S, B = 300, 2 if N == 4 else 1
    q, k, v = _grouped_qkv(S, N, kv, B=B, dtype=dtype)
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    before = _kv_heads_counted()
    _assert_fwd_and_grads(
        lambda *a: mha(*a, causal=True),
        _on_copies(lambda *a: _reference_attention(*map(f32, a), causal=True)),
        q, k, v, 3e-5 if dtype == jnp.float32 else 2e-2,
        f"{N} / {kv} {jnp.dtype(dtype).name}")
    grads = _grads(lambda *a: mha(*a, causal=True), q, k, v)
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]
    assert all(g.dtype == dtype for g in grads)
    after = _kv_heads_counted()
    label = f"grouped_{N // kv}"
    assert after[label] > before.get(label, 0)
    assert not any(name.startswith("copied") for name in after
                   if after[name] != before.get(name, 0))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("changes", [
    dict(resident_bwd=False),                       # two-pass backward
    dict(block=256, sub=128, rows=128, span=256),   # forward over spans
    dict(block=512, sub=256, rows=256),             # one block to the head
], ids=["two_pass_bwd", "spans", "one_block"])
def test_grouped_query_heads_on_plans_the_cells_do_not_take(changes, causal):
    """Eight query heads over two KV heads through the two-pass backward
    (its dk/dv pass walks a KV head's query heads between the k tile and
    the q tiles), the forward over spans, and a head of one block, causal
    and not, at a padded length."""
    S, N, kv, D = 500, 8, 2, 128
    plan = tile_plan(S, D, jnp.float32, N=N, kv_heads=kv)._replace(**changes)

    def fn(q, k, v):
        o = pallas_flash._mha(*(t.reshape(2, S, -1) for t in (q, k, v)),
                              causal, float(D) ** -0.5, plan)
        return o.reshape(2, S, N, D)

    q, k, v = _grouped_qkv(S, N, kv, B=2)
    _assert_fwd_and_grads(
        fn, _on_copies(lambda *a: _reference_attention(*a, causal=causal)),
        q, k, v, 3e-5, f"{changes} causal={causal}")


# the text ``jax.jit(grad(mha)).lower(q, k, v).as_text()`` of a call whose k
# and v have q's head count, at the commit before grouped-query heads went
# into the kernel (33029de): sha256, by (shape, window).  A change to the
# kernels that every call takes moves these, and re-records them.
_TEXT_BEFORE_GROUPED_HEADS = {
    ((2, 640, 4, 128), None):
        "7b9b229c092b1bef",
    ((2, 1000, 3, 64), None):
        "1416ff64f6025f96",
}


@pytest.mark.parametrize("shape,window", list(_TEXT_BEFORE_GROUPED_HEADS))
def test_a_call_without_grouped_heads_lowers_to_the_text_it_had(shape, window):
    """``rep == 1`` is the call as it was: the program text of forward +
    backward is the recorded one's, and the same whether k and v come as q
    itself or as arrays of their own with q's head count; such a call
    counts nothing under ``*_kv_heads``."""
    import hashlib

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    before = _kv_heads_counted(), _kv_heads_counted("flash_attention_window")
    grad = jax.grad(lambda *a: jnp.sum(
        mha(*a, window=window).astype(jnp.float32)), argnums=(0, 1, 2))
    text = jax.jit(grad).lower(x, x, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        _TEXT_BEFORE_GROUPED_HEADS[shape, window]
    assert before == (_kv_heads_counted(),
                      _kv_heads_counted("flash_attention_window"))
    # and no grouped call's structure: a grid of three axes in the backward
    kernels = [eqn for eqn in _equations(jax.make_jaxpr(grad)(x, x, x).jaxpr)
               if eqn.primitive.name == "pallas_call"]
    assert sorted(len(e.params["grid_mapping"].grid) for e in kernels) == [3, 4]


def test_grouped_heads_are_one_more_grid_axis_and_no_copy():
    """The grouped call's program: k, v, dk and dv at the KV heads' width
    in and out of the two kernels, the backward's grid (batch, KV head,
    query head of the group, k/v block), nothing repeated before the
    kernels and nothing summed after them."""
    S, N, kv, D = 512, 8, 2, 128
    q = jax.ShapeDtypeStruct((1, S, N, D), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, S, kv, D), jnp.bfloat16)
    grad = jax.grad(lambda *a: jnp.sum(mha(*a, block=128).astype(
        jnp.float32)), argnums=(0, 1, 2))
    eqns = list(_equations(jax.make_jaxpr(grad)(q, k, k).jaxpr))
    fwd, bwd = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert fwd.params["grid_mapping"].grid == (1, N, 4, 1)
    assert bwd.params["grid_mapping"].grid == (1, kv, N // kv, 4)
    assert [a.aval.shape for a in fwd.invars] == [
        (1, S, N * D), (1, S, kv * D), (1, S, kv * D)]
    assert [a.aval.shape for a in bwd.outvars] == [
        (1, S, N * D), (1, S, kv * D), (1, S, kv * D)]
    # the one sum is the loss's own; no value is [B, S, KV, group, D]
    assert [e.primitive.name for e in eqns].count("reduce_sum") == 1
    assert not any(getattr(a.aval, "shape", ())[2:] == (kv, N // kv, D)
                   for e in eqns for a in e.outvars)


@pytest.mark.parametrize("N,kv,D,path", [
    (4, 2, 64, "in_place_2"),     # two heads of 64 to a lane block
    (6, 2, 96, "folded"),         # heads that are no lane blocks
    (3, 1, 64, "folded"),         # an odd head count at 64
])
@pytest.mark.parametrize("window", [None, 100])
def test_layouts_that_keep_the_copy_of_k_and_v(N, kv, D, path, window):
    """Heads packed two to a lane block, and the folded layout, cannot
    address a KV head by the query head's group: ``mha`` copies k and v out
    to the query heads itself, says so, and gives the reference's
    numbers."""
    kernel = "flash_attention_window" if window else "flash_attention"
    q, k, v = _grouped_qkv(384, N, kv, D=D)
    before = _kv_heads_counted(kernel)
    _assert_fwd_and_grads(
        lambda *a: mha(*a, window=window),
        _on_copies(lambda *a: _reference_attention(*a, window=window)),
        q, k, v, 3e-5, f"{N} / {kv} at {D}")
    after = _kv_heads_counted(kernel)
    label = f"copied_{N // kv}"
    assert after[label] > before.get(label, 0)
    assert after.get(f"grouped_{N // kv}", 0) == before.get(
        f"grouped_{N // kv}", 0)
    from deeperspeed_tpu.telemetry import kernel_paths
    assert path in kernel_paths()[kernel]


@pytest.mark.parametrize("tp,label", [(4, "copied_4"), (2, "grouped_4")])
def test_kv_heads_under_a_sharded_head_axis(reset_mesh, tp, label):
    """Eight query heads over two KV heads, the head axis laid out over
    ``tp`` devices: over two each device holds a KV head and its four query
    heads and the kernel addresses the group; over four the KV heads do not
    divide, and ``dot_product_attention`` hands each device its query
    heads' copies."""
    from deeperspeed_tpu.ops.attention.core import dot_product_attention

    reset_mesh.set_mesh(reset_mesh.MeshTopology(dp=8 // tp, tp=tp))
    q, k, v = _grouped_qkv(256, 8, 2, B=8 // tp, seed=3)
    before = _kv_heads_counted()
    jax.clear_caches()
    _assert_fwd_and_grads(
        jax.jit(lambda *a: dot_product_attention(*a, use_pallas=True)),
        _on_copies(lambda *a: _reference_attention(*a, causal=True)),
        q, k, v, 3e-5, f"tp={tp}")
    after = _kv_heads_counted()
    assert {name for name in after
            if after[name] != before.get(name, 0)} == {label}


def test_k_and_v_that_are_no_kv_heads_are_refused():
    q, k, v = _grouped_qkv(128, 6, 4)
    with pytest.raises(ValueError):
        mha(q, k, v)
    with pytest.raises(ValueError):
        mha(q, k[:, :64], v[:, :64])


@pytest.mark.parametrize("shape,kv,window", [
    ((4, 8192, 32, 128), 4, 1024), ((4, 8192, 32, 128), 4, None),
    ((2, 8192, 36, 128), 4, 512), ((2, 8192, 24, 128), 4, None),
], ids=["mellum_window", "mellum_full", "laguna_window", "laguna_full"])
def test_grouped_backward_fits_vmem_at_the_cells_shapes(shape, kv, window):
    """The one-kernel backward still holds a head's q side at the cells'
    lengths with a KV head's fp32 dk and dv sums beside it (``tile_plan``
    counts them), inside the budget and under the limit a call may state."""
    B, S, N, D = shape
    plan = tile_plan(S, D, jnp.bfloat16, N=N, window=window, kv_heads=kv)
    assert plan.resident_bwd and plan.group == 1
    assert plan == tile_plan(S, D, jnp.bfloat16, N=N, window=window)
    held = pallas_flash._bwd_resident_bytes(S, D, 2, grouped=True)
    assert held - pallas_flash._bwd_resident_bytes(S, D, 2) == 2 * S * D * 4
    assert held <= pallas_flash._VMEM_BUDGET
    # what ``_bwd_call`` states: the resident side, the k/v blocks in and
    # out, the block's accumulators and the tallest tile's temporaries
    need = (held + 8 * plan.block * D * 2 + 2 * plan.block * D * 4
            + 5 * max(plan.rows, plan.sub) * plan.block * 4)
    assert pallas_flash._vmem_limit(need) <= pallas_flash._VMEM_LIMIT
    assert need * 5 // 4 <= pallas_flash._VMEM_LIMIT
    # a length at which the sums are what no longer fits goes two-pass
    assert tile_plan(12288, D, jnp.bfloat16, N=N).resident_bwd
    assert not tile_plan(12288, D, jnp.bfloat16, N=N,
                         kv_heads=kv).resident_bwd

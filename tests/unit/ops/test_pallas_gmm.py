"""The grouped matmul's kernels (``ops/pallas_gmm.py``) in interpret mode
against a loop over the groups: groups that straddle tiles, empty groups,
rows past the last group, one group for all the rows; blocks wider than the
chunk of columns a kernel multiplies at a time; and the walk's plan of
visits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu.ops import pallas_gmm

M, TILE, K, N = 64, 16, 256, 128
SIZES = [
    [5, 0, 20, 3, 0, 30],       # straddled tiles, empty groups, rows left over
    [0, 0, 0, 64, 0, 0],        # one group has every row
    [16, 16, 16, 16, 0, 0],     # whole tiles only
    [1, 1, 1, 1, 1, 1],         # every group in one tile
    [0, 0, 0, 0, 0, 7],         # the last group alone, in part of a tile
]


def _operands(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (M, K)),
            jax.random.normal(ks[1], (len(SIZES[0]), K, N)),
            jax.random.normal(ks[2], (M, N)),
            jax.random.normal(ks[3], (len(SIZES[0]), K, N)))


def _groups(sizes):
    ends = np.cumsum(sizes)
    return list(zip(ends - sizes, ends))


@pytest.mark.parametrize("sizes", SIZES)
@pytest.mark.parametrize("transpose_rhs", [False, True])
def test_each_groups_rows_by_its_own_matrix(sizes, transpose_rhs):
    lhs, rhs, _, _ = _operands()
    visits = pallas_gmm.visit_plan(jnp.asarray(sizes, jnp.int32), M, TILE)
    out = pallas_gmm.grouped_matmul(
        lhs, jnp.swapaxes(rhs, 1, 2) if transpose_rhs else rhs, visits,
        transpose_rhs=transpose_rhs, tile_rows=TILE)
    for e, (lo, hi) in enumerate(_groups(np.asarray(sizes))):
        np.testing.assert_allclose(out[lo:hi], lhs[lo:hi] @ rhs[e],
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("sizes", SIZES)
def test_outer_products_add_to_the_groups_with_rows_only(sizes):
    lhs, _, rhs, into = _operands(1)
    visits = pallas_gmm.visit_plan(jnp.asarray(sizes, jnp.int32), M, TILE)
    got = pallas_gmm.grouped_outer(lhs, rhs, visits, into, tile_rows=TILE)
    for e, (lo, hi) in enumerate(_groups(np.asarray(sizes))):
        if hi == lo:
            # a group with no rows is not visited: its slab is the old one
            np.testing.assert_array_equal(got[e], into[e])
        else:
            np.testing.assert_allclose(
                got[e], into[e] + lhs[lo:hi].T @ rhs[lo:hi], rtol=1e-4,
                atol=1e-4)


@pytest.mark.parametrize("width", [384, 512])   # 3 chunks of 128, 2 of 256
@pytest.mark.parametrize("form", ["matmul", "transposed", "outer"])
def test_a_blocks_columns_are_multiplied_a_chunk_at_a_time(width, form):
    """A block wider than ``_COLUMNS`` is the same product, straddled tiles
    and all: the matmul kernels loop over chunks of whole lane blocks that
    divide the block's width (the outer product takes its block whole)."""
    sizes = np.asarray(SIZES[0])
    ks = jax.random.split(jax.random.PRNGKey(width), 3)
    lhs = jax.random.normal(ks[0], (M, K))
    visits = pallas_gmm.visit_plan(jnp.asarray(sizes, jnp.int32), M, TILE)
    if form == "outer":
        rhs = jax.random.normal(ks[1], (M, width))
        into = jax.random.normal(ks[2], (len(sizes), K, width))
        got = pallas_gmm.grouped_outer(lhs, rhs, visits, into, tile_rows=TILE)
        for e, (lo, hi) in enumerate(_groups(sizes)):
            np.testing.assert_allclose(
                got[e], into[e] + lhs[lo:hi].T @ rhs[lo:hi], rtol=1e-4,
                atol=1e-4)
        return
    rhs = jax.random.normal(ks[1], (len(sizes), K, width))
    out = pallas_gmm.grouped_matmul(
        lhs, jnp.swapaxes(rhs, 1, 2) if form == "transposed" else rhs, visits,
        transpose_rhs=form == "transposed", tile_rows=TILE)
    for e, (lo, hi) in enumerate(_groups(sizes)):
        np.testing.assert_allclose(out[lo:hi], lhs[lo:hi] @ rhs[e],
                                   rtol=1e-4, atol=1e-4)


def test_rows_of_no_group_never_reach_a_product():
    """What lies past the last group, or in a straddled tile beside a
    group's own rows, may hold anything: the outer product zeroes it."""
    sizes = jnp.asarray([5, 0, 20, 3, 0, 0], jnp.int32)
    lhs, _, rhs, into = _operands(2)
    visits = pallas_gmm.visit_plan(sizes, M, TILE)
    clean = pallas_gmm.grouped_outer(lhs, rhs, visits, into, tile_rows=TILE)
    dirty = pallas_gmm.grouped_outer(
        lhs.at[28:].set(jnp.nan), rhs.at[28:].set(jnp.inf), visits, into,
        tile_rows=TILE)
    np.testing.assert_array_equal(clean, dirty)


def test_the_visits_follow_the_rows():
    visits = pallas_gmm.visit_plan(jnp.asarray(SIZES[0], jnp.int32), M, TILE)
    # group 0: tile 0; group 2 (rows 5-24): tiles 0, 1; group 3 (25-27): 1;
    # group 5 (28-57): tiles 1, 2, 3
    assert int(visits.count) == 7
    np.testing.assert_array_equal(visits.group[:7], [0, 2, 2, 3, 5, 5, 5])
    np.testing.assert_array_equal(visits.tile[:7], [0, 0, 1, 1, 1, 2, 3])
    np.testing.assert_array_equal(visits.offsets, [0, 5, 5, 25, 28, 28, 58])
    assert visits.group.shape == (M // TILE + len(SIZES[0]) - 1,)
    nobody = pallas_gmm.visit_plan(jnp.zeros(6, jnp.int32), M, TILE)
    assert int(nobody.count) == 0


def test_the_widths_the_kernels_take():
    assert pallas_gmm.takes(2304, 1792, 896)
    assert pallas_gmm.takes()
    assert not pallas_gmm.takes(2304, 96, 48)
    assert pallas_gmm._widths(2304) == [2304, 1152, 768, 384, 256, 128]
    assert pallas_gmm._widths(896) == [896, 128]


def test_a_buffer_nobody_has_written_is_only_a_shape():
    after = jnp.ones((4, 128))
    got = pallas_gmm.unwritten((32, 256), jnp.bfloat16, after)
    assert got.shape == (32, 256) and got.dtype == jnp.bfloat16

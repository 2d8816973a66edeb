"""In-tree Pallas kernels of the grouped matmul: rows sorted by group, each
group's rows against that group's matrix.

``lhs`` [m, k] holds the rows of ``g`` groups one after the other, ``sizes``
[g] of them each (``sum(sizes) <= m``; what lies past the last group is
nobody's).  Two kernels, in the manner of megablox (``jax.experimental.
pallas.ops.tpu.megablox``), whose grid they share:

``grouped_matmul``: ``out[rows of group e] = lhs[rows of group e] @ rhs[e]``
(or ``@ rhs[e].T``), ``rhs`` [g, k, n].  ``grouped_outer``: ``into[e] +=
lhs[rows of e].T @ rhs[rows of e]``, the transposed product that a weight's
gradient is, summed in float32 into an accumulator that is updated in place.

Both walk *visits*: a visit is one tile of ``tile_rows`` rows against one
group.  A tile that lies inside one group is visited once; a tile that
straddles groups is visited once for each group that has rows in it, and
only that group's rows are stored (``grouped_matmul``) or multiplied
(``grouped_outer``: the others are zeroed before the product).  No group is
padded to a capacity.  The visits are counted from ``sizes`` on the device
(``visit_plan``) and the grid's visit axis is as long as that count, so a
call costs what the rows given to it cost: tiles past the last group are not
visited, their rows of ``out`` are not written, and a group with no rows is
not visited and its slab of ``into`` not touched.

Beside them ``unwritten``: a buffer handed over as it was allocated, for
results that are written tile by tile before anyone reads them.

The same sums in the same types as ``jnp.dot(..., preferred_element_type=
...)`` on each group: operands as given, float32 accumulation over the whole
contracted width (``k`` is not tiled, so a group's matrix is fetched once
for all the consecutive tiles of its rows), the result rounded once.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .attention.pallas_flash import _NN, _NT, _TN, _params, _vmem_limit
from .pallas_utils import LANES, interpret_mode

_F32 = jnp.float32
#: Rows a tile.  A group's matrix is fetched once a run of its tiles, so the
#: tile's height only sets how much of a straddled tile is computed twice
#: (at most ``groups`` tiles a call) and what the MXU is fed at a time.
TILE_ROWS = 512
# what a call's blocks may take of VMEM (v5e: 128 MiB), double buffers and
# the float32 accumulator included; widths are split until they fit
_VMEM_BUDGET = 40 << 20


class Visits(NamedTuple):
    """The walk of one call (``visit_plan``)."""
    offsets: jax.Array      # [g + 1] the row each group starts at
    group: jax.Array        # [most visits] the group of a visit
    tile: jax.Array         # [most visits] the row tile of a visit
    count: jax.Array        # [] visits to make


def visit_plan(sizes, m, tile_rows):
    """``sizes`` [g] rows a group, laid out from row 0 of ``m`` (a multiple
    of ``tile_rows``) -> ``Visits``: group by group, each group's tiles in
    row order, ``count`` of them; the places past ``count`` are never run."""
    g = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tile_rows
    tiles = jnp.where(sizes > 0, (ends - 1) // tile_rows - first + 1, 0)
    upto = jnp.cumsum(tiles)
    v = jnp.arange(m // tile_rows + g - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(v[:, None] >= upto[None, :], axis=1), g - 1)
    tile = first[group] + v - (upto[group] - tiles[group])
    return Visits(jnp.concatenate([jnp.zeros(1, jnp.int32), ends]),
                  group.astype(jnp.int32),
                  jnp.clip(tile, 0, m // tile_rows - 1).astype(jnp.int32),
                  upto[-1])


def _widths(n):
    """The widths a dimension of ``n`` lanes can be cut to: whole blocks of
    128 lanes that divide it, widest first."""
    blocks = n // LANES
    return [LANES * d for d in range(blocks, 0, -1) if blocks % d == 0]


def takes(*widths):
    """Whether the kernels take operands of these minor widths: whole blocks
    of 128 lanes (the operands are one type, float32 or bfloat16)."""
    return all(w > 0 and w % LANES == 0 for w in widths)


def _owned(offsets_ref, group_ref, tile_ref, v, shape):
    """Of visit ``v``'s tile -> (whether all of it is the visit's group's,
    which of its rows are, as a mask of ``shape``)."""
    g = group_ref[v]
    lo, hi = offsets_ref[g], offsets_ref[g + 1]
    first = tile_ref[v] * shape[0]
    rows = first + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (jnp.logical_and(lo <= first, first + shape[0] <= hi),
            jnp.logical_and(rows >= lo, rows < hi))


# ------------------------------------------------------------------ kernels
# A matmul's block is multiplied ``_COLUMNS`` output columns at a time, in a
# loop inside the kernel: Mosaic unrolls a dot over its whole block, and a
# block of [512, 2304] x [2304, 1792] unrolled is 1.9 MB of the executable
# for each of a step's 24 calls, where the step program's load from the
# compile cache follows its size in every process (PERF.md section 6, PR
# 41: 0.4-0.5 MB a call so, for 3.6 % of the kernels' time; the outer
# product's loop cost 7 % and is not made).  The same sums: a column's
# contraction is whole either way.
_COLUMNS = 256


def _column_chunks(n, body):
    """``body(columns)`` for every chunk of a block's ``n`` columns, a
    ``pl.ds`` of ``_COLUMNS`` or fewer in whole lane blocks."""
    width = next(w for w in _widths(n) if w <= _COLUMNS)

    def chunk(j, carry):
        body(pl.ds(pl.multiple_of(j * width, width), width))
        return carry

    jax.lax.fori_loop(0, n // width, chunk, 0)


def _matmul_kernel(offsets_ref, group_ref, tile_ref, lhs_ref, rhs_ref,
                   out_ref, *, dims):
    whole, mine = _owned(offsets_ref, group_ref, tile_ref, pl.program_id(1),
                         (out_ref.shape[0], 1))

    def columns(cols):
        rhs = rhs_ref[:, cols] if dims == _NN else rhs_ref[cols, :]
        acc = jax.lax.dot_general(lhs_ref[...], rhs, dims,
                                  preferred_element_type=_F32)

        @pl.when(whole)
        def _():
            out_ref[:, cols] = acc.astype(out_ref.dtype)

        @pl.when(jnp.logical_not(whole))
        def _():
            # a straddled tile stays in VMEM from one group's visit to the
            # next
            out_ref[:, cols] = jnp.where(
                mine, acc, out_ref[:, cols].astype(_F32)).astype(
                    out_ref.dtype)

    _column_chunks(out_ref.shape[1], columns)


def _outer_kernel(offsets_ref, group_ref, tile_ref, lhs_ref, rhs_ref, old_ref,
                  out_ref, acc_ref):
    v, last = pl.program_id(2), pl.num_programs(2) - 1
    g = group_ref[v]

    @pl.when(jnp.logical_or(v == 0, group_ref[jnp.maximum(v - 1, 0)] != g))
    def _():
        acc_ref[...] = old_ref[...]

    whole, _ = _owned(offsets_ref, group_ref, tile_ref, v, (lhs_ref.shape[0],
                                                             1))

    @pl.when(whole)
    def _():
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], _TN, preferred_element_type=_F32)

    @pl.when(jnp.logical_not(whole))
    def _():
        # the rows of other groups, and of nobody, leave both operands: what
        # they hold may be anything
        def own(ref):
            _, mine = _owned(offsets_ref, group_ref, tile_ref, v, ref.shape)
            return jnp.where(mine, ref[...].astype(_F32), 0.0).astype(
                ref.dtype)

        acc_ref[...] += jax.lax.dot_general(
            own(lhs_ref), own(rhs_ref), _TN, preferred_element_type=_F32)

    @pl.when(jnp.logical_or(v == last,
                            group_ref[jnp.minimum(v + 1, last)] != g))
    def _():
        out_ref[...] = acc_ref[...]


# -------------------------------------------------------------------- calls
def _matmul_call(visits, lhs, rhs, transpose_rhs, tile_rows):
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    size = lhs.dtype.itemsize

    def need(tn):
        return 2 * size * (tile_rows * k + k * tn + tile_rows * tn) + (
            4 * tile_rows * tn)

    tn = next((w for w in _widths(n) if need(w) <= _VMEM_BUDGET), LANES)
    if transpose_rhs:
        rhs_spec = pl.BlockSpec((None, tn, k),
                                lambda j, v, off, grp, tile: (grp[v], j, 0))
    else:
        rhs_spec = pl.BlockSpec((None, k, tn),
                                lambda j, v, off, grp, tile: (grp[v], 0, j))
    with jax.named_scope("grouped_matmul"):
        return pl.pallas_call(
            functools.partial(_matmul_kernel,
                              dims=_NT if transpose_rhs else _NN),
            out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                in_specs=[pl.BlockSpec(
                    (tile_rows, k),
                    lambda j, v, off, grp, tile: (tile[v], 0)), rhs_spec],
                out_specs=pl.BlockSpec(
                    (tile_rows, tn),
                    lambda j, v, off, grp, tile: (tile[v], j)),
                grid=(n // tn, visits.count)),
            cost_estimate=pl.CostEstimate(
                flops=2 * m * k * n, transcendentals=0,
                bytes_accessed=size * (m * k * (n // tn) + m * n
                                       + rhs.shape[0] * k * n)),
            interpret=interpret_mode(),
            **_params("parallel", "arbitrary", vmem=_vmem_limit(need(tn))),
        )(visits.offsets, visits.group, visits.tile, lhs, rhs)


def _outer_call(visits, lhs, rhs, into, tile_rows):
    from jax.experimental.pallas import tpu as pltpu

    (m, k), n = lhs.shape, rhs.shape[1]
    size = lhs.dtype.itemsize

    def need(tk, tn):
        # the slab comes in and goes out double-buffered beside the sums
        return 2 * size * tile_rows * (tk + tn) + 5 * 4 * tk * tn

    tk, tn = max(((a, b) for a in _widths(k) for b in _widths(n)
                  if need(a, b) <= _VMEM_BUDGET),
                 key=lambda ab: (ab[0] * ab[1], ab[1]),
                 default=(LANES, LANES))
    slab = pl.BlockSpec((None, tk, tn),
                        lambda j, i, v, off, grp, tile: (grp[v], i, j))
    with jax.named_scope("grouped_matmul"):
        return pl.pallas_call(
            _outer_kernel,
            out_shape=jax.ShapeDtypeStruct(into.shape, _F32),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                in_specs=[
                    pl.BlockSpec((tile_rows, tk),
                                 lambda j, i, v, off, grp, tile: (tile[v], i)),
                    pl.BlockSpec((tile_rows, tn),
                                 lambda j, i, v, off, grp, tile: (tile[v], j)),
                    slab],
                out_specs=slab,
                grid=(n // tn, k // tk, visits.count),
                scratch_shapes=[pltpu.VMEM((tk, tn), _F32)]),
            # the accumulator is the output: a slab no visit names stays
            input_output_aliases={5: 0},
            cost_estimate=pl.CostEstimate(
                flops=2 * m * k * n, transcendentals=0,
                bytes_accessed=size * m * (k * (n // tn) + n * (k // tk))
                + 8 * into.size),
            interpret=interpret_mode(),
            **_params("parallel", "arbitrary", "arbitrary",
                      vmem=_vmem_limit(need(tk, tn))),
        )(visits.offsets, visits.group, visits.tile, lhs, rhs, into)


def _unwritten_call(shape, dtype, after):
    with jax.named_scope("unwritten"):
        return pl.pallas_call(
            lambda after_ref, out_ref: None,
            out_shape=jax.ShapeDtypeStruct(shape, dtype),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            interpret=interpret_mode())(after)


# --------------------------------------------------------------- public API
def unwritten(shape, dtype, after):
    """A buffer of ``shape`` that nobody has written, for results that will
    be written before they are read (the sorted slots' rows): what XLA has
    no name for, so its nearest, zeros, costs a pass over the buffer (1.5 ms
    for Mellum's 1.2 GB, twice a layer) that no reader needs.  A kernel that
    writes nothing hands the allocation over as it is.  ``after`` is any
    array the buffer is made after: it ties each call to its own place in
    the program, so that two buffers of one shape are two buffers.  Whoever
    reads a row nobody wrote reads anything."""
    return _unwritten_call(shape, jnp.dtype(dtype), after)


@functools.partial(jax.jit, static_argnames=("transpose_rhs", "tile_rows"))
def grouped_matmul(lhs, rhs, visits, transpose_rhs=False,
                   tile_rows=TILE_ROWS):
    """``lhs`` [m, k] rows sorted by group, ``rhs`` [g, k, n] (``[g, n, k]``
    with ``transpose_rhs``), ``visits`` from ``visit_plan(sizes, m,
    tile_rows)`` -> [m, n] in ``lhs``'s type: each group's rows by its
    matrix, float32 sums.  Rows of no group are not written: whoever reads
    them reads anything.  Jitted, so that a model's layers of one shape
    share one trace and one lowering."""
    return _matmul_call(visits, lhs, rhs, transpose_rhs, tile_rows)


@functools.partial(jax.jit, static_argnames=("tile_rows",))
def grouped_outer(lhs, rhs, visits, into, tile_rows=TILE_ROWS):
    """``into`` [g, k, n] float32 ``+= lhs[rows of e].T @ rhs[rows of e]``
    for every group ``e`` with rows (``lhs`` [m, k], ``rhs`` [m, n], one
    type), float32 sums, in place."""
    return _outer_call(visits, lhs, rhs, into, tile_rows)

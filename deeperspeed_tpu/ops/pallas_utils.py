"""Shared Pallas tiling scaffolding for elementwise/rowwise kernels.

One place for the TPU tile geometry (128 lanes, 8 sublanes), the
flatten/pad/unpad dance, and the interpret-mode switch -- every fused op
(adam, lion, gelu, softmax, layernorm) tiles through these helpers so
block-divisibility invariants live in one spot.

Padding contract: arrays are padded **to a multiple of the block row count**
with explicit zeros, so every grid block lies fully inside the array.
Kernels that accumulate across rows (e.g. layernorm dgamma/dbeta) rely on
this -- out-of-bounds partial blocks have unspecified contents on real TPU
(only interpret mode zero-fills them).
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8
# masking sentinel for softmax kernels (finite: -inf breaks exp/max algebra)
NEG_INF = -1e30


def interpret_mode():
    """Pallas interpret fallback off-TPU (tests execute real kernel code)."""
    return jax.default_backend() != "tpu"


def _kernel_mesh():
    """Where ``shard_kernel`` wraps a call: ``(mesh to map over, the
    process-global mesh, its axes not yet manual)``; None where the call is
    direct (no mesh installed, one device, or every axis already manual)."""
    from ..parallel import topology as topo

    mesh = topo._GLOBAL_MESH
    if mesh is None or mesh.mesh.size == 1:
        return None
    use_mesh, manual = mesh.mesh, frozenset()
    am = jax.sharding.get_abstract_mesh()
    if not am.empty:
        use_mesh, manual = am, frozenset(am.manual_axes)
    auto = frozenset(mesh.mesh.axis_names) - manual
    return (use_mesh, mesh, auto) if auto else None


def kernel_spec(spec, shape):
    """The ``PartitionSpec`` ``shard_kernel`` lays an operand of ``shape``
    out by, given the mesh axes ``spec`` names per dim: of those, an axis is
    used only if it is larger than 1, is not already manual in an enclosing
    ``shard_map`` and divides the dim.  None where the call is direct."""
    from jax.sharding import PartitionSpec

    where = _kernel_mesh()
    if where is None:
        return None
    _, mesh, auto = where
    dims = []
    for entry, size in zip(spec, shape):
        axes = () if entry is None else (
            (entry,) if isinstance(entry, str) else tuple(entry))
        kept, n = [], 1
        for a in axes:
            if (a in auto and mesh.sizes[a] > 1
                    and size % (n * mesh.sizes[a]) == 0):
                kept.append(a)
                n *= mesh.sizes[a]
        dims.append(tuple(kept) if kept else None)
    return PartitionSpec(*dims)


def shard_kernel(fn, args, specs, out_like=0):
    """Call ``fn(*args)`` -- a function made of Pallas kernels -- under the
    process-global mesh; its one output is laid out like ``args[out_like]``
    (a tuple of outputs like the operands a tuple ``out_like`` names).

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"): under a mesh of more than one device the
    call must sit in a ``shard_map`` that is manual over every mesh axis,
    where each device runs the kernel on its own shard.  ``specs`` gives,
    per operand and per dim, the mesh axes that dim is laid out over by the
    model code's conventions (the ``topology.constrain`` call sites); which
    of them an operand takes is ``kernel_spec``'s answer, and over the other
    axes the operands are replicated.  With no mesh installed, one device,
    or every axis already manual, the call is direct.
    """
    where = _kernel_mesh()
    if where is None:
        return fn(*args)
    use_mesh, _, auto = where
    in_specs = tuple(kernel_spec(s, a.shape) for s, a in zip(specs, args))
    out_specs = (tuple(in_specs[i] for i in out_like)
                 if isinstance(out_like, tuple) else in_specs[out_like])
    return jax.shard_map(
        fn, mesh=use_mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=auto, check_vma=False)(*args)


def pad_rows(x2, block_rows):
    """Zero-pad [rows, h] so rows is a multiple of ``block_rows``."""
    rows = x2.shape[0]
    rp = -(-rows // block_rows) * block_rows
    if rp == rows:
        return x2
    return jnp.pad(x2, ((0, rp - rows), (0, 0)))


def row_block_size(rows, max_block_rows):
    """Block height: full array when small, else the configured block."""
    return min(max_block_rows, -(-rows // SUBLANES) * SUBLANES)


def rowwise_call(kernel, out_shapes, arrays, block_rows, extra_in_specs=(),
                 extra_args=()):
    """Run ``kernel`` over row blocks of 2-D ``arrays`` (all same shape).

    ``out_shapes``: list of (kind, dtype) with kind 'row' (per-row-block
    output) or 'vec' (a [1, h] block revisited by every grid step, for
    cross-row accumulation).  Arrays are padded to a block multiple first.
    """
    rows, h = arrays[0].shape
    br = row_block_size(rows, block_rows)
    padded = [pad_rows(a, br) for a in arrays]
    rp = padded[0].shape[0]
    grid = (rp // br,)
    row_spec = pl.BlockSpec((br, h), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, h), lambda i: (0, 0))
    out_specs = [row_spec if kind == "row" else vec_spec
                 for kind, _ in out_shapes]
    out_shape = [jax.ShapeDtypeStruct((rp, h) if kind == "row" else (1, h), dt)
                 for kind, dt in out_shapes]
    single = len(out_shape) == 1
    result = pl.pallas_call(
        kernel, grid=grid,
        in_specs=list(extra_in_specs) + [row_spec] * len(padded),
        out_specs=out_specs[0] if single else out_specs,
        out_shape=out_shape[0] if single else out_shape,
        interpret=interpret_mode(),
    )(*extra_args, *padded)
    outs = [result] if single else list(result)
    return [o[:rows] if kind == "row" else o
            for o, (kind, _) in zip(outs, out_shapes)]


def elementwise_call(kernel, out_dtypes, arrays, block_rows):
    """Run an elementwise ``kernel`` over flattened (rows, 128) tiles of
    same-shape ``arrays``; returns outputs reshaped to the input shape."""
    shape = arrays[0].shape
    n = arrays[0].size
    rows = -(-n // LANES)

    def to2d(x):
        flat = jnp.ravel(x)
        return jnp.pad(flat, (0, rows * LANES - n)).reshape(rows, LANES)

    outs = rowwise_call(kernel, [("row", dt) for dt in out_dtypes],
                        [to2d(a) for a in arrays], block_rows)
    return [o.reshape(-1)[:n].reshape(shape) for o in outs]

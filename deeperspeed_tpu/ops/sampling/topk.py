"""Sorted top-k over logit rows (Pallas), for on-device sampling filters.

Decode-time sampling only needs the k largest logits of each row sorted in
descending order (the top-k filter threshold is the k-th value).  k is tiny
(<= 64) next to the vocab axis, so a full ``jnp.sort`` wastes ~V log V work
per row; this kernel does k iterative max-extractions per row entirely in
VMEM -- each pass is one VPU max-reduce plus a masked overwrite, O(k * V)
with k unrolled at trace time.

Off-TPU the public wrapper falls back to ``jax.lax.top_k`` (already sorted
descending); kernel-vs-fallback parity is pinned by
``tests/unit/ops/test_sampling.py`` with ``force_kernel=True`` running the
kernel in interpret mode.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..pallas_utils import NEG_INF, SUBLANES, interpret_mode, pad_rows


def _topk_kernel(x_ref, vals_ref, idx_ref, *, k):
    work = x_ref[...].astype(jnp.float32)               # [SUBLANES, V]
    V = work.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, work.shape, 1)
    vals, idxs = [], []
    for _ in range(k):
        m = jnp.max(work, axis=1, keepdims=True)        # [SUBLANES, 1]
        # ties resolve to the lowest index, matching lax.top_k
        first = jnp.min(jnp.where(work == m, cols, V), axis=1, keepdims=True)
        vals.append(m)
        idxs.append(first)
        work = jnp.where(cols == first, NEG_INF, work)
    vals_ref[...] = jnp.concatenate(vals, axis=1).astype(vals_ref.dtype)
    idx_ref[...] = jnp.concatenate(idxs, axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "force_kernel"))
@jax.named_scope("sorted_topk")
def sorted_topk(x, k, force_kernel=False):
    """Top-k values (descending) + their indices per row.

    x [rows, V] -> (vals [rows, k] f32, idx [rows, k] i32)
    """
    rows, V = x.shape
    k = int(k)
    if k < 1 or k > V:
        raise ValueError(f"k={k} out of range for vocab {V}")
    if interpret_mode() and not force_kernel:
        vals, idx = jax.lax.top_k(x.astype(jnp.float32), k)
        return vals, idx.astype(jnp.int32)
    # one sublane tile of rows per grid step: the TPU lowering takes a row
    # block only in multiples of 8, and a [1, V] block fills the same vregs
    # as an [8, V] one.  Rows are independent, so the padding is sliced off.
    xp = pad_rows(x, SUBLANES)
    rp = xp.shape[0]
    vals, idx = pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        grid=(rp // SUBLANES,),
        in_specs=[pl.BlockSpec((SUBLANES, V), lambda r: (r, 0))],
        out_specs=[pl.BlockSpec((SUBLANES, k), lambda r: (r, 0)),
                   pl.BlockSpec((SUBLANES, k), lambda r: (r, 0))],
        out_shape=[jax.ShapeDtypeStruct((rp, k), jnp.float32),
                   jax.ShapeDtypeStruct((rp, k), jnp.int32)],
        interpret=interpret_mode(),
    )(xp)
    return vals[:rows], idx[:rows]

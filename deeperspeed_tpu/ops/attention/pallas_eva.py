"""Pallas kernels of EVA attention (EvaByte): one softmax a row over two
sets, the exact keys of the row's own window up to the row (causal) and the
summaries of every chunk of every EARLIER window, forward + backward.

The equations are ``ops/attention/eva.py``'s.  Here ``W`` is the window in
rows, ``P`` the summaries a window leaves (``W / chunk``), ``nW = S / W``.
The kernels take q, k, v ``[B, S, N*D]`` as the projections hold them and
the summaries ``[B, S / chunk, N*D]`` (as ``pallas_eva_pool``'s kernels
write them, under a scope of their own), heads addressed as column groups of
``D`` lanes (``pallas_flash``'s in-place layout at one head a lane block),
and are built from ``pallas_flash``'s tiles:

* a grid program owns one window of one head: its q rows, its k/v rows, and
  the head's summaries whole (``S / chunk`` rows, 256 KB a side at 16k bytes
  and D = 128) resident in VMEM;
* the window's causal block is ``pallas_flash``'s one-block form: a group
  of ``sub`` rows against the columns up to its own diagonal at once, only
  the last ``sub`` columns masked (``_edge_tiles``, ``_pieces``);
* the summaries are walked a WINDOW's at a time, ``P`` columns a step, by a
  loop as long as the window's number: the windows a row does not see are
  not visited, and nothing of a visited one is masked (a row sees all the
  summaries of an earlier window or none).  The running statistics of a row
  group live in VMEM scratch between the steps;
* forward saves ``o`` and one float a row, the log-sum-exp over BOTH sets,
  named for a remat policy as ``pallas_flash``'s are (``SAVED_BY_REMAT``);
* backward is one call.  A program makes its window's dq (both sets), its
  own keys' dk and dv whole (no other window sees them), and adds what its
  rows give the summaries into fp32 accumulators that stay in VMEM while the
  grid steps through the head's windows (that axis is sequential).

``pairs_visited`` counts the (row, key) pairs this walk computes, beside the
pairs the equations need (``eva.pairs_needed``): a count, never a time.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ..pallas_utils import LANES, interpret_mode
from .pallas_flash import (_NN, _NT, _TN, SAVED_BY_REMAT, _edge_tiles, _masked,
                           _params, _pieces, _rows_off_lanes, _rows_onto_lanes,
                           _vmem_limit, _walk)

#: the scope the kernels run under: names their events in a device trace and
#: their counts in ``telemetry.kernel_paths()`` / ``kernel_passes()``
KERNEL_NAME = "eva_attention"


class EvaPlan(NamedTuple):
    """Tile sizes of one call (rows)."""
    window: int     # W: rows a grid program owns
    per_window: int  # P: summaries a window leaves
    sub: int        # row group of the causal block (``pallas_flash``'s)
    width: int      # D: lanes of a head


def eva_plan(window, chunk, head_dim):
    return EvaPlan(int(window), int(window) // int(chunk),
                   min(int(window), 512), int(head_dim))


def compiles_for_tpu(seq, window, chunk, head_dim):
    """Whether the TPU compiler takes these shapes: whole windows, heads and
    a window's summaries whole 128-lane tiles.  (In interpret mode any whole
    number of windows of whole chunks runs.)"""
    return (seq % window == 0 and window % chunk == 0
            and head_dim % LANES == 0 and window % LANES == 0
            and (window // chunk) % LANES == 0)


def pairs_visited(seq, plan):
    """(row, key) pairs one head's walk computes in a length ``seq``: a row
    group against the columns up to its diagonal, and every row of a window
    against every summary of the windows before it."""
    n = seq // plan.window
    local = sum(plan.sub * ncols
                for _, ncols in _edge_tiles(plan.window, plan.sub, True))
    return n * local + plan.window * plan.per_window * n * (n - 1) // 2


# --------------------------------------------------------------------- fwd
def _fwd_kernel(q_ref, k_ref, v_ref, kb_ref, vb_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, window, per_window, sub):
    w = pl.program_id(2)
    for row0, ncols in _edge_tiles(window, sub, True):
        rows = pl.ds(row0, sub)
        q = q_ref[0, rows, :]           # pre-scaled
        # the window's causal block: a row group's columns at once
        pieces = [(pl.ds(c0, nc), mask)
                  for c0, nc, mask in _pieces(ncols, sub, True)]
        ss = [_masked(jax.lax.dot_general(
            q, k_ref[0, cols, :], _NT, preferred_element_type=jnp.float32),
            mask, True, 0, 0) for cols, mask in pieces]
        m = functools.reduce(
            jnp.maximum, [jnp.max(s, axis=1, keepdims=True) for s in ss])
        ps = [jnp.exp(s - m) for s in ss]
        m_scr[...] = jnp.broadcast_to(m, (sub, LANES))
        l_scr[...] = jnp.broadcast_to(
            sum(jnp.sum(p, axis=1, keepdims=True) for p in ps), (sub, LANES))
        acc_scr[...] = sum(
            jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0, cols, :], _NN,
                                preferred_element_type=jnp.float32)
            for p, (cols, _) in zip(ps, pieces))

        def earlier(j, q=q):
            """The summaries of window ``j`` < w: all seen, none masked."""
            cols = pl.ds(pl.multiple_of(j * per_window, per_window),
                         per_window)
            s = jax.lax.dot_general(q, kb_ref[0, cols, :], _NT,
                                    preferred_element_type=jnp.float32)
            m_prev = m_scr[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            m_scr[...] = jnp.broadcast_to(m_new, (sub, LANES))
            l_scr[...] = jnp.broadcast_to(
                l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
                (sub, LANES))
            acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
                p.astype(vb_ref.dtype), vb_ref[0, cols, :], _NN,
                preferred_element_type=jnp.float32)

        _walk(0, w, earlier)
        l = l_scr[:, :1]
        o_ref[0, rows, :] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, :, rows] = _rows_onto_lanes(m_scr[:, :1] + jnp.log(l))


def _need_fwd(plan, itemsize, summaries):
    W, D = plan.window, plan.width
    return (8 * W * D * itemsize                # q, k, v, o double-buffered
            + 4 * summaries * D * itemsize      # both summaries, the same
            + 2 * 8 * W * 4                     # lse out, a sublane tile
            + 2 * plan.sub * LANES * 4 + plan.sub * D * 4
            + 3 * plan.sub * W * 4)             # a score tile, its exp, slack


def _fwd_call(q, k, v, kb, vb, heads, plan):
    from jax.experimental.pallas import tpu as pltpu

    b, s, _ = q.shape
    W, D, M = plan.window, plan.width, kb.shape[1]
    owned = pl.BlockSpec((1, W, D), lambda b, g, w: (b, w, g))
    whole = pl.BlockSpec((1, M, D), lambda b, g, w: (b, 0, g))
    pairs = b * heads * pairs_visited(s, plan)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, window=W, per_window=plan.per_window,
                          sub=plan.sub),
        grid=(b, heads, s // W),
        in_specs=[owned, owned, owned, whole, whole],
        out_specs=[owned, pl.BlockSpec(
            (1, 1, W), lambda b, g, w: (b * heads + g, 0, w))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b * heads, 1, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((plan.sub, LANES), jnp.float32),
                        pltpu.VMEM((plan.sub, LANES), jnp.float32),
                        pltpu.VMEM((plan.sub, D), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=4 * pairs * D, transcendentals=pairs,
            bytes_accessed=(4 * q.size + 2 * kb.size) * q.dtype.itemsize
            + 4 * b * heads * s),
        interpret=interpret_mode(),
        **_params("parallel", "parallel", "arbitrary",
                  vmem=_vmem_limit(_need_fwd(plan, q.dtype.itemsize, M))),
    )(q, k, v, kb, vb)


# ---------------------------------------------------------------------- bwd
def _bwd_kernel(q_ref, k_ref, v_ref, kb_ref, vb_ref, do_ref, o_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, dkb_ref, dvb_ref,
                lse_scr, dq_scr, dk_scr, dv_scr, dkb_scr, dvb_scr,
                *, window, per_window, sub):
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init_head():
        dkb_scr[...] = jnp.zeros_like(dkb_scr)
        dvb_scr[...] = jnp.zeros_like(dvb_scr)

    # the window's lse as the tiles read it: rows off the lanes, once
    for r in range(0, window, sub):
        lse_scr[pl.ds(r, sub), :] = _rows_off_lanes(
            lse_ref[0, :, pl.ds(r, sub)])
    dk_scr[...] = jnp.zeros_like(dk_scr)
    dv_scr[...] = jnp.zeros_like(dv_scr)

    for row0, ncols in _edge_tiles(window, sub, True):
        rows = pl.ds(row0, sub)
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        lse = lse_scr[rows, :1]
        delta = jnp.sum(do.astype(jnp.float32)
                        * o_ref[0, rows, :].astype(jnp.float32),
                        axis=1, keepdims=True)

        def against(k, v, mask, q=q, do=do, lse=lse, delta=delta):
            """The row group against some keys -> (dv's, dk's, dq's share)."""
            s = jax.lax.dot_general(q, k, _NT,
                                    preferred_element_type=jnp.float32)
            p = jnp.exp(_masked(s, mask, True, 0, 0) - lse)
            dv = jax.lax.dot_general(p.astype(do.dtype), do, _TN,
                                     preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, _NT,
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(q.dtype)
            dk = jax.lax.dot_general(ds, q, _TN,
                                     preferred_element_type=jnp.float32)
            return dv, dk, jax.lax.dot_general(
                ds, k, _NN, preferred_element_type=jnp.float32)

        dq = None
        for c0, nc, mask in _pieces(ncols, sub, True):
            cols = pl.ds(c0, nc)
            dv, dk, part = against(k_ref[0, cols, :], v_ref[0, cols, :], mask)
            dv_scr[cols, :] += dv
            dk_scr[cols, :] += dk
            dq = part if dq is None else dq + part
        dq_scr[...] = dq

        def earlier(j, against=against):
            cols = pl.ds(pl.multiple_of(j * per_window, per_window),
                         per_window)
            dv, dk, part = against(kb_ref[0, cols, :], vb_ref[0, cols, :],
                                   False)
            dvb_scr[cols, :] += dv
            dkb_scr[cols, :] += dk
            dq_scr[...] += part

        _walk(0, w, earlier)
        dq_ref[0, rows, :] = dq_scr[...].astype(dq_ref.dtype)

    dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
    dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(w == pl.num_programs(2) - 1)
    def _finalize_head():
        dkb_ref[0] = dkb_scr[...].astype(dkb_ref.dtype)
        dvb_ref[0] = dvb_scr[...].astype(dvb_ref.dtype)


def _need_bwd(plan, itemsize, summaries):
    W, D = plan.window, plan.width
    return (16 * W * D * itemsize               # q k v do o dq dk dv, twice
            + 8 * summaries * D * itemsize      # summaries and theirs, twice
            + 2 * 8 * W * 4 + W * LANES * 4     # the lse, both layouts
            + (plan.sub + 2 * W + 2 * summaries) * D * 4    # accumulators
            + 5 * plan.sub * W * 4)             # s, p, dp, ds and a cast


def _bwd_call(q, k, v, kb, vb, do, o, lse, heads, plan):
    from jax.experimental.pallas import tpu as pltpu

    b, s, _ = q.shape
    W, D, M = plan.window, plan.width, kb.shape[1]
    owned = pl.BlockSpec((1, W, D), lambda b, g, w: (b, w, g))
    whole = pl.BlockSpec((1, M, D), lambda b, g, w: (b, 0, g))
    stat = pl.BlockSpec((1, 1, W), lambda b, g, w: (b * heads + g, 0, w))
    rows = jax.ShapeDtypeStruct(q.shape, q.dtype)
    pooled = jax.ShapeDtypeStruct(kb.shape, kb.dtype)
    pairs = b * heads * pairs_visited(s, plan)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, window=W, per_window=plan.per_window,
                          sub=plan.sub),
        grid=(b, heads, s // W),
        in_specs=[owned, owned, owned, whole, whole, owned, owned, stat],
        out_specs=[owned, owned, owned, whole, whole],
        out_shape=[rows, rows, rows, pooled, pooled],
        scratch_shapes=[pltpu.VMEM((W, LANES), jnp.float32),
                        pltpu.VMEM((plan.sub, D), jnp.float32),
                        pltpu.VMEM((W, D), jnp.float32),
                        pltpu.VMEM((W, D), jnp.float32),
                        pltpu.VMEM((M, D), jnp.float32),
                        pltpu.VMEM((M, D), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=10 * pairs * D, transcendentals=pairs,
            bytes_accessed=(8 * q.size + 4 * kb.size) * q.dtype.itemsize
            + 4 * b * heads * s),
        interpret=interpret_mode(),
        **_params("parallel", "parallel", "arbitrary",
                  vmem=_vmem_limit(_need_bwd(plan, q.dtype.itemsize, M))),
    )(q, k, v, kb, vb, do, o, lse)


# ------------------------------------------------------------- public API
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _eva(q, k, v, kb, vb, heads, scale, plan):
    return _eva_fwd(q, k, v, kb, vb, heads, scale, plan)[0]


def _eva_fwd(q, k, v, kb, vb, heads, scale, plan):
    with jax.named_scope(KERNEL_NAME):
        # pre-scaled once, as ``pallas_flash`` does; dq is post-scaled
        qp = q * jnp.asarray(scale, q.dtype)
        o, lse = _fwd_call(qp, k, v, kb, vb, heads, plan)
        # what a remat policy keeps (``SAVED_BY_REMAT``), so that a
        # recomputed layer does not run the forward kernel again
        o, lse = (checkpoint_name(t, name) for t, name in
                  zip((o, lse), SAVED_BY_REMAT))
        return o, (qp, k, v, kb, vb, o, lse)


def _eva_bwd(heads, scale, plan, res, do):
    qp, k, v, kb, vb, o, lse = res
    with jax.named_scope(KERNEL_NAME):
        dq, dk, dv, dkb, dvb = _bwd_call(qp, k, v, kb, vb, do, o, lse, heads,
                                         plan)
        return dq * jnp.asarray(scale, dq.dtype), dk, dv, dkb, dvb


_eva.defvjp(_eva_fwd, _eva_bwd)


def eva_mha(q, k, v, kb, vb, window, chunk, scale=None):
    """``[B, S, N, D]`` q, k, v (rotated) and the chunk summaries
    ``[B, S / chunk, N, D]`` -> ``[B, S, N, D]``: row t's softmax over the
    keys ``j <= t`` of its own window and the summaries of every earlier
    window.  ``S`` a whole number of windows of whole chunks.
    Differentiable (custom VJP) in all five operands."""
    from ...telemetry.trace import count_kernel_path

    B, S, N, D = q.shape
    if S % window or window % chunk or kb.shape[1] * chunk != S:
        raise ValueError(f"{S} rows are not whole windows of {window} in "
                         f"chunks of {chunk}")
    plan = eva_plan(window, chunk, D)
    if window % plan.sub:
        raise ValueError(f"a window of {window} rows is not whole row "
                         f"groups of {plan.sub}")
    count_kernel_path(KERNEL_NAME, "in_place_1")
    with jax.named_scope("attention_layout"):
        q, k, v = (t.reshape(B, S, N * D) for t in (q, k, v))
        kb, vb = (t.reshape(B, S // chunk, N * D) for t in (kb, vb))
    o = _eva(q, k, v, kb, vb, N, float(D) ** -0.5 if scale is None
             else float(scale), plan)
    with jax.named_scope("attention_layout"):
        return o.reshape(B, S, N, D)

"""In-tree Pallas kernels of Mamba-2's chunked SSD scan, forward + backward.

The same sums in the same types as the plain form (``ops/ssm.py::_ssd``):
matmul operands in the operands' type with float32 sums; ``dt A``, its
running sums, every ``exp`` and the carried state float32.  What differs is
where the temporaries live: everything of size ``[Q, Q]`` (a chunk's scores,
decays and their product) or ``[P, N]`` (a head's state) stays in VMEM, and
only the operands and the results cross HBM.

Layout: the kernels read ``x`` (and ``dy``) and write ``y`` (and ``dx``) as
``[B, S, heads * P]`` and read ``b``, ``c`` as ``[B, S, G * N]``, the layouts
a Mamba mixer's splits produce, with heads addressed as *lane blocks* of 128:
one head of P = 128, or two heads of 64 side by side (the pattern of
``attention/pallas_flash.py``; a 4-D operand at a minor width of 64 would
cost a transposing copy each, PERF.md section 6, PR 30).  The step sizes are
tiny beside them (a float a head and step) and are handed over with a
program's heads on lanes, ``[B, J, S, 128]`` (zeros past its ``hb`` heads:
float32 rows of 32 take a 128-lane tile in HBM either way): a chunk's running
log-decay ``cs`` is then one float32 matmul by a triangle of ones, with
``cs_i`` down the rows of a ``[Q, Q]`` tile; ``cs_j`` along its columns is the
same array transposed once a program (the very same floats, so the decay on
the diagonal is exactly 1).

Grid ``(batch, head block, chunk)``: a program owns the heads of ``gb`` whole
groups (``hb = gb * R`` heads, at most 2048 lanes of ``x``) for one chunk of
``Q`` steps and walks each group's lane blocks with a loop in the kernel
body, so the scores ``C B^T`` are computed once a group and a step of the
walk costs no grid step.  The chunk axis is sequential: the state
``[heads * P, N]`` float32 of a lane block is carried in scratch from chunk
to chunk.

Backward: one kernel that walks the chunks in reverse with the state's
cotangent in scratch and recomputes a chunk's ``[Q, Q]`` pieces from the
operands.  The state *entering* each chunk is what the forward call of a
differentiated scan writes beside ``y`` (in the operands' type: it is a
matmul operand): ``[B, chunks, heads * P, N]``, 67 MB a layer at the hybrid
cell's shapes, alive only from a layer's recomputation to its backward call.
A forward call that is not differentiated (a remat wrap's first pass) writes
none.  Gradients of the step sizes leave as two small float32 arrays (the
part through ``dt x`` and the part through the decays) that the rule adds up
outside.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .attention.pallas_flash import (_NN, _NT, _TN, _each_its_lanes,
                                     _own_lanes, _params, _vmem_limit)
from .pallas_utils import LANES, NEG_INF, interpret_mode

_F32 = jnp.float32
# the most lanes of ``x`` a program owns: [128, 2048] blocks of x, y (and
# dy, dx, the saved states) double-buffered stay inside the default
# scoped-VMEM limit; one group wider than twice that takes the plain form
_LANES_A_PROGRAM = 2048


class Plan(NamedTuple):
    """Block sizes of one scan call, from its shapes (``scan_plan``)."""
    chunk: int      # Q: steps a program owns
    p: int          # head width
    n: int          # state width
    r: int          # heads a group
    gb: int         # groups a program owns
    heads: int      # heads to a lane block of 128: 1 or 2


def scan_plan(heads, p, groups, n, chunk, dtypes):
    """The kernels' block plan, or None where they do not take the shapes:
    they want whole 128-lane blocks (a chunk that is a multiple of 128
    steps, heads of 64 or 128, a state width that is a multiple of 128, an
    even number of heads a group at P = 64), one operand type (float32 or
    bfloat16) and a group no wider than they can hold."""
    if len(set(dtypes)) != 1 or jnp.dtype(dtypes[0]) not in (
            jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return None
    if chunk % LANES or chunk > 2 * LANES or n % LANES or p not in (64, 128):
        return None
    if heads % groups:
        return None
    r = heads // groups
    if (r * p) % LANES or r * p > 2 * _LANES_A_PROGRAM:
        return None
    gb = max([g for g in range(1, groups + 1)
              if groups % g == 0 and g * r * p <= _LANES_A_PROGRAM],
             default=1)
    return Plan(chunk, p, n, r, gb, LANES // p)


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _sum_f32(a, b):
    """A float32 running sum as a matmul by zeros and ones: every pass of
    the MXU, so the sum is float32's."""
    return jax.lax.dot_general(a, b, _NN, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _triangles(q):
    """The lower and the upper triangle of ones (float32 [q, q], with the
    diagonal), and the lower as a mask."""
    i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return (i >= j).astype(_F32), (i <= j).astype(_F32), i >= j


def _each_its_rows(parts, shape):
    """``shape`` filled, in each head's rows, with that head's ``[1, N]``."""
    if len(parts) == 1:
        return jnp.broadcast_to(parts[0], shape)
    d = shape[0] // len(parts)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    out = parts[-1]
    for h in range(len(parts) - 2, -1, -1):
        out = jnp.where(row < (h + 1) * d, parts[h], out)
    return out


def _decays(dt_ref, a_ref, q):
    """A chunk's running log-decay for every head of the program, steps on
    sublanes ``cs_col`` [Q, 128] and the same floats with steps on lanes
    ``cs_row`` [128, Q], and what follows from it: ``since`` = exp(cs) (what
    the entering state has decayed to by each step) and ``to_end`` =
    exp(cs_last - cs) (what a step's push decays to by the chunk's end), both
    [Q, 128]; the entering state keeps exp(cs_last).  Also the upper triangle
    of ones and the lower triangle as a mask."""
    lower, upper, tril = _triangles(q)
    cs_col = _sum_f32(lower, dt_ref[0, 0] * a_ref[0])
    return (cs_col, cs_col.T, jnp.exp(cs_col),
            jnp.exp(cs_col[q - 1:q, :] - cs_col), upper, tril)


# A program walks its lane blocks with a loop in the kernel body (a body
# unrolled over 16 lane blocks took the tracer seconds a call, in every
# process and before any compile cache is asked: PERF.md section 6, PR 35),
# so which heads a lane block holds is a traced number.  A lane block's
# slice of x and its rows of the state are dynamic slices at multiples of
# 128.  What the walk needs of a head's column of the [Q, 128] arrays of
# per-head values (``_decays``) is laid out for it before it starts, by
# short static loops: each value on its head's lanes of a [Q, lanes of x]
# scratch, and each head's cs lane-replicated in a [Q, 128] tile of its own.
# (A rotation of the lanes by a traced amount does the same with no scratch;
# it made the forward kernel 2.8 times slower.)
def _block(k):
    """Lane block ``k``'s lanes of the program's x block, which are also its
    rows of the program's state (a lane of x is a row of the state)."""
    return pl.ds(pl.multiple_of(k * LANES, LANES), LANES)


def _lay_out(plan, cs_col, cs_scr, columns):
    """``cs_scr[h]`` = head ``h``'s column of ``cs_col`` on every lane; and
    for each ``(scratch, cols)`` of ``columns``, ``scratch`` [Q, lanes of x]
    = in every head's lanes that head's column of ``cols`` [Q, 128]."""
    hb = plan.gb * plan.r
    for h in range(hb):
        cs_scr[h] = jnp.broadcast_to(cs_col[:, h:h + 1], cs_scr.shape[1:])
    for k in range(hb // plan.heads):
        heads = range(k * plan.heads, (k + 1) * plan.heads)
        for scratch, cols in columns:
            scratch[:, k * LANES:(k + 1) * LANES] = _each_its_lanes(
                [cols[:, h:h + 1] for h in heads], LANES)


def _kept(cs_scr, k, plan, shape):
    """What lane block ``k``'s entering state ``shape`` = [128, N] keeps by
    the chunk's end, exp(cs_last) in each head's rows.  The ``exp`` is taken
    of a row of N lanes: Mosaic broadcasts a scalar one way at a time."""
    q = cs_scr.shape[1]
    return _each_its_rows(
        [jnp.exp(jnp.broadcast_to(cs_scr[k * plan.heads + t, q - 1:q, :1],
                                  (1, shape[1])))
         for t in range(plan.heads)], shape)


def _masked_decay(cs_scr, cs_row_scr, h, tril):
    """exp(cs_i - cs_j) of head ``h`` under the triangle, 0 above it:
    [Q, Q] float32."""
    lag = cs_scr[h][:, :1] - cs_row_scr[pl.ds(h, 1), :]
    return jnp.exp(jnp.where(tril, lag, NEG_INF))


def _groups(plan):
    """The static walk of a program's groups: ``(group, its lane blocks'
    numbers in the program: first, past the last)``."""
    per_group = plan.r * plan.p // LANES
    return [(g, g * per_group, (g + 1) * per_group) for g in range(plan.gb)]


# Lane blocks a step of the walk.  Mosaic schedules a step of the loop as one
# piece, so more blocks a step hide more of a block's latencies (the matmuls'
# fill and drain, the exps) behind the next block's; all of a group's blocks
# a step is the loop unrolled where Mosaic lowers it (the body is still
# traced once), fewer are traced that many times.  Kernel alone at the hybrid
# cell's shapes (8 blocks a group), ms forward / backward at 1, 2, 4, 8 blocks
# a step: 1.24 / 2.31, 1.12 / 1.98, 0.98 / 2.18, 0.77 / 2.03 (BENCH_KERNELS.md,
# PR 35): the forward's short body is bound by those latencies and takes the
# group whole; the backward's long one gains nothing past 2, and every block
# more is lowered for Mosaic again in every process.
FWD_BLOCKS_A_STEP = 8
BWD_BLOCKS_A_STEP = 2


def _walk(lo, hi, block, blocks_a_step, carry=0):
    """Run ``carry = block(k, carry)`` for the lane blocks k in [lo, hi),
    ``blocks_a_step`` of them (or all, if that many do not divide them) a
    step of the loop."""
    if blocks_a_step >= hi - lo or (hi - lo) % blocks_a_step:
        return jax.lax.fori_loop(lo, hi, block, carry, unroll=True)

    def step(i, carry):
        for j in range(blocks_a_step):
            carry = block(lo + i * blocks_a_step + j, carry)
        return carry

    return jax.lax.fori_loop(0, (hi - lo) // blocks_a_step, step, carry)


# --------------------------------------------------------------------- fwd
def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, *rest, plan,
                keep_states):
    """One chunk of the heads of a program: y, and the state carried on."""
    if keep_states:
        s_ref, state, cs_row_scr, cs_scr, dt_scr, since_scr, end_scr = rest
    else:
        state, cs_row_scr, cs_scr, dt_scr, since_scr, end_scr = rest
    q, n, dtype = plan.chunk, plan.n, x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[:] = jnp.zeros_like(state)

    cs_col, cs_row_scr[:], since, to_end, _, tril = _decays(dt_ref, a_ref, q)
    _lay_out(plan, cs_col, cs_scr, ((dt_scr, dt_ref[0, 0]), (since_scr, since),
                                    (end_scr, to_end)))
    for g, lo, hi in _groups(plan):
        bm = b_ref[0, :, g * n:(g + 1) * n]
        cm = c_ref[0, :, g * n:(g + 1) * n]
        scores = _dot(cm, bm, _NT)                          # [Q, Q], a group's

        def block(k, carry):
            lanes = _block(k)
            xf = x_ref[0, :, lanes].astype(_F32)
            xdt = (xf * dt_scr[:, lanes]).astype(dtype)
            st = state[lanes, :]
            if keep_states:
                s_ref[0, 0, lanes, :] = st.astype(dtype)
            # what the entering state gives each step, then the chunk's own
            y = _dot(cm, st.astype(dtype), _NT) * since_scr[:, lanes]
            for t in range(plan.heads):
                mixed = scores * _masked_decay(
                    cs_scr, cs_row_scr, k * plan.heads + t, tril)
                y += _dot(mixed.astype(dtype), _own_lanes(xdt, t, plan.heads))
            y_ref[0, :, lanes] = (y + xf * d_ref[:, lanes]).astype(dtype)
            # what the chunk adds to the state by its end
            pushed = _dot((xdt.astype(_F32)
                           * end_scr[:, lanes]).astype(dtype), bm,
                          _TN)                              # [128, N]
            state[lanes, :] = st * _kept(cs_scr, k, plan, st.shape) + pushed
            return carry

        _walk(lo, hi, block, FWD_BLOCKS_A_STEP)


# --------------------------------------------------------------------- bwd
def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, s_ref, dy_ref,
                dx_ref, ddtx_ref, ddta_ref, db_ref, dc_ref, dd_ref, dstate,
                cs_row_scr, cs_scr, dt_scr, since_scr, end_scr, dcol_scr,
                drow_scr, dscores_scr, db_scr, dc_scr, *, plan):
    """One chunk, walked from the last to the first: the cotangents of the
    chunk's operands, and the state's cotangent carried back.  The chunk's
    ``[Q, Q]`` pieces are recomputed; the state that entered it is read.
    The cotangents of the log-decays gather a head's column (``dcol_scr``,
    [Q, 128]) or row (``drow_scr``, [128, Q]) at a time, that of dt through
    ``dt x`` in ``ddtx_ref``'s block itself."""
    q, n, p, dtype = plan.chunk, plan.n, plan.p, x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate[:] = jnp.zeros_like(dstate)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    cs_col, cs_row_scr[:], since, to_end, upper, tril = _decays(
        dt_ref, a_ref, q)
    _lay_out(plan, cs_col, cs_scr, ((dt_scr, dt_ref[0, 0]), (since_scr, since),
                                    (end_scr, to_end)))
    dcol_scr[:] = jnp.zeros_like(dcol_scr)
    drow_scr[:] = jnp.zeros_like(drow_scr)
    ddtx_ref[0, 0] = jnp.zeros((q, LANES), _F32)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (q, LANES), 1)
    head_row = jax.lax.broadcasted_iota(jnp.int32, (LANES, q), 0)

    def head_sums(values):
        """[Q, 128] -> each head's sum over its own lanes, [Q, 1] apiece."""
        return [jnp.sum(_own_lanes(values, t, plan.heads), axis=1,
                        keepdims=True) for t in range(plan.heads)]

    dlast = jnp.zeros((1, LANES), _F32)         # of cs_last, a lane a head
    for g, lo, hi in _groups(plan):
        bm = b_ref[0, :, g * n:(g + 1) * n]
        cm = c_ref[0, :, g * n:(g + 1) * n]
        scores = _dot(cm, bm, _NT)
        dscores_scr[:] = jnp.zeros_like(dscores_scr)
        db_scr[:] = jnp.zeros_like(db_scr)
        dc_scr[:] = jnp.zeros_like(dc_scr)

        def block(k, dlast):
            lanes = _block(k)
            xf = x_ref[0, :, lanes].astype(_F32)
            dy = dy_ref[0, :, lanes]
            dyf = dy.astype(_F32)
            w_dt, w_since, w_end = (scratch[:, lanes] for scratch in
                                    (dt_scr, since_scr, end_scr))
            xdt = (xf * w_dt).astype(dtype)
            xdtf = xdt.astype(_F32)
            st = s_ref[0, 0, lanes, :]                      # entered, [128, N]
            dst = dstate[lanes, :]                          # of the state left
            dst_lo = dst.astype(dtype)
            # state' = state * keep + (xdt * to_end)^T B
            dpush = _dot(bm, dst_lo, _NT)                   # [Q, 128]
            db_scr[:] += _dot((xdtf * w_end).astype(dtype), dst_lo)
            kept = dst * st.astype(_F32)
            # y += (C state^T) * since
            z = _dot(cm, st, _NT)
            dz = (dyf * w_since).astype(dtype)
            dc_scr[:] += _dot(dz, st)
            dstate[lanes, :] = dst * _kept(cs_scr, k, plan, dst.shape) + _dot(
                dz, cm, _TN)
            dxdt = dpush * w_end
            by_since = head_sums(dyf * z * w_since)
            by_end = head_sums(dpush * xdtf * w_end)
            for t in range(plan.heads):
                # y += (scores * decay) xdt, the head's own lanes
                h = k * plan.heads + t
                decay = _masked_decay(cs_scr, cs_row_scr, h, tril)
                mixed = scores * decay
                dy_h = _own_lanes(dy, t, plan.heads)
                dxdt += _dot(mixed.astype(dtype), dy_h, _TN)
                dmixed = _dot(dy_h, xdt, _NT)               # [Q, Q]
                dscores_scr[:] += dmixed * decay
                dlag = dmixed * mixed
                dcol_scr[:] += jnp.where(
                    head_lane == h,
                    jnp.sum(dlag, axis=1, keepdims=True) + by_since[t]
                    - by_end[t], 0.0)
                drow_scr[:] -= jnp.where(
                    head_row == h, jnp.sum(dlag, axis=0, keepdims=True), 0.0)
                dkeep = jnp.sum(jnp.sum(kept[t * p:(t + 1) * p, :], axis=0,
                                        keepdims=True), axis=1, keepdims=True)
                dlast += jnp.where(
                    head_lane[:1] == h,
                    jnp.sum(by_end[t], axis=0, keepdims=True)
                    + dkeep * jnp.exp(cs_scr[h, q - 1:q, :1]), 0.0)
            for t, through_x in enumerate(head_sums(dxdt * xf)):
                ddtx_ref[0, 0] += jnp.where(
                    head_lane == k * plan.heads + t, through_x, 0.0)
            dx_ref[0, :, lanes] = (dxdt * w_dt
                                   + dyf * d_ref[:, lanes]).astype(dtype)
            dd_ref[0, :, lanes] += jnp.sum(dyf * xf, axis=0, keepdims=True)
            return dlast

        dlast = _walk(lo, hi, block, BWD_BLOCKS_A_STEP, dlast)
        # scores = C B^T
        dscores = dscores_scr[:].astype(dtype)
        dc_ref[0, :, g * n:(g + 1) * n] = (
            dc_scr[:] + _dot(dscores, bm)).astype(dtype)
        db_ref[0, :, g * n:(g + 1) * n] = (
            db_scr[:] + _dot(dscores, cm, _TN)).astype(dtype)

    # cs_last is the chunk's last running sum; a running sum's cotangent is
    # the reversed running sum of its own
    last_row = jax.lax.broadcasted_iota(jnp.int32, (q, LANES), 0) == q - 1
    ddta_ref[0, 0] = _sum_f32(
        upper, dcol_scr[:] + drow_scr[:].T + jnp.where(last_row, dlast, 0.0))


# ------------------------------------------------------------------ calls
def _specs(plan, chunks, reverse):
    """BlockSpecs shared by the two calls, by operand; ``reverse`` walks the
    chunk axis from the last chunk to the first."""
    q, n = plan.chunk, plan.n
    hb = plan.gb * plan.r

    def at(i):
        return chunks - 1 - i if reverse else i

    wide = pl.BlockSpec((1, q, hb * plan.p), lambda b, j, i: (b, at(i), j))
    group = pl.BlockSpec((1, q, plan.gb * n), lambda b, j, i: (b, at(i), j))
    return dict(
        wide=wide, group=group,
        steps=pl.BlockSpec((1, 1, q, LANES), lambda b, j, i: (b, j, at(i), 0)),
        a=pl.BlockSpec((1, 1, LANES), lambda b, j, i: (j, 0, 0)),
        d=pl.BlockSpec((1, hb * plan.p), lambda b, j, i: (0, j)),
        states=pl.BlockSpec((1, 1, hb * plan.p, n),
                            lambda b, j, i: (b, at(i), j, 0)),
        dd=pl.BlockSpec((1, 1, hb * plan.p), lambda b, j, i: (b, 0, j)))


def _cost(x, b, plan, matmuls, wide_tensors, states):
    """What a call costs, for XLA's scheduler (``pallas_flash._cost``):
    ``matmuls`` of ``2 Q 128 128`` FLOPs a lane block and chunk."""
    B, S, lanes = x.shape
    blocks = B * (S // plan.chunk) * (lanes // LANES)
    return pl.CostEstimate(
        flops=2 * matmuls * blocks * plan.chunk * LANES * LANES,
        transcendentals=blocks * plan.heads * plan.chunk * plan.chunk,
        bytes_accessed=(wide_tensors * x.size + 2 * wide_tensors * b.size
                        + states * x.size * plan.n // plan.chunk)
        * x.dtype.itemsize)


def _walk_scratch(plan):
    """What ``_lay_out`` fills for the walk: cs with the steps on lanes
    [128, Q]; each head's cs on every lane [hb, Q, 128]; dt, exp(cs) and
    exp(cs_last - cs) on their heads' lanes, [Q, lanes of x] each."""
    from jax.experimental.pallas import tpu as pltpu

    q, hb = plan.chunk, plan.gb * plan.r
    return ([pltpu.VMEM((LANES, q), _F32), pltpu.VMEM((hb, q, LANES), _F32)]
            + [pltpu.VMEM((q, hb * plan.p), _F32)] * 3)


def _vmem_need(plan, itemsize, wide_tensors):
    """VMEM a program holds: its wide blocks (and the states') double-
    buffered, the carried state, the walk's scratch and a head's [Q, Q]
    float32 temporaries."""
    hb = plan.gb * plan.r
    lanes = hb * plan.p
    return (2 * wide_tensors * plan.chunk * lanes * itemsize
            + 2 * lanes * plan.n * itemsize + lanes * plan.n * 4
            + (hb * LANES + 3 * lanes) * plan.chunk * 4
            + 8 * plan.chunk * plan.chunk * 4 + 6 * plan.chunk * LANES * 4)


def _fwd_call(x, dt, a, b, c, d, plan, keep_states):
    from jax.experimental.pallas import tpu as pltpu

    B, S, lanes = x.shape
    q, n = plan.chunk, plan.n
    hbp = plan.gb * plan.r * plan.p
    sp = _specs(plan, S // q, reverse=False)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    out_specs = [sp["wide"]]
    if keep_states:
        out_shape.append(jax.ShapeDtypeStruct((B, S // q, lanes, n), x.dtype))
        out_specs.append(sp["states"])
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan, keep_states=keep_states),
        grid=(B, lanes // hbp, S // q),
        in_specs=[sp["wide"], sp["steps"], sp["a"], sp["group"], sp["group"],
                  sp["d"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hbp, n), _F32)] + _walk_scratch(plan),
        cost_estimate=_cost(x, b, plan, matmuls=3 + plan.heads,
                            wide_tensors=2, states=int(keep_states)),
        interpret=interpret_mode(),
        **_params("parallel", "parallel", "arbitrary", vmem=_vmem_limit(
            _vmem_need(plan, x.dtype.itemsize, 2))),
    )(x, dt, a, b, c, d)
    return out if keep_states else (out[0], None)


def _bwd_call(x, dt, a, b, c, d, states, dy, plan):
    from jax.experimental.pallas import tpu as pltpu

    B, S, lanes = x.shape
    q, n = plan.chunk, plan.n
    hbp = plan.gb * plan.r * plan.p
    sp = _specs(plan, S // q, reverse=True)
    like = jax.ShapeDtypeStruct
    return pl.pallas_call(
        functools.partial(_bwd_kernel, plan=plan),
        grid=(B, lanes // hbp, S // q),
        in_specs=[sp["wide"], sp["steps"], sp["a"], sp["group"], sp["group"],
                  sp["d"], sp["states"], sp["wide"]],
        out_specs=[sp["wide"], sp["steps"], sp["steps"], sp["group"],
                   sp["group"], sp["dd"]],
        out_shape=[like(x.shape, x.dtype), like(dt.shape, _F32),
                   like(dt.shape, _F32), like(b.shape, b.dtype),
                   like(c.shape, c.dtype), like((B, 1, lanes), _F32)],
        scratch_shapes=[pltpu.VMEM((hbp, n), _F32)]      # the state's
        + _walk_scratch(plan) + [
                        pltpu.VMEM((q, LANES), _F32),    # of cs, by column
                        pltpu.VMEM((LANES, q), _F32),    # of cs, by row
                        pltpu.VMEM((q, q), _F32),        # of a group's scores
                        pltpu.VMEM((q, n), _F32),        # of a group's B
                        pltpu.VMEM((q, n), _F32)],       # of a group's C
        cost_estimate=_cost(x, b, plan, matmuls=7 + 2 * plan.heads,
                            wide_tensors=3, states=1),
        interpret=interpret_mode(),
        **_params("parallel", "parallel", "arbitrary", vmem=_vmem_limit(
            _vmem_need(plan, x.dtype.itemsize, 3))),
    )(x, dt, a, b, c, d, states, dy)


# ------------------------------------------------------------- public API
def _operands(x, dt, a, b, c, d, plan):
    """The calls' operands: the step sizes [B, S, heads] and the decay rates
    [heads] with a program's ``hb`` heads on (zero-padded) lanes, [B, J, S,
    128] and [J, 1, 128]; the skip a float a lane of ``x``."""
    B, S, heads = dt.shape
    hb = plan.gb * plan.r
    pad = ((0, 0),) * 3 + ((0, LANES - hb),)
    dt = jnp.pad(jnp.swapaxes(dt.reshape(B, S, heads // hb, hb), 1, 2), pad)
    a = jnp.pad(a.reshape(-1, 1, hb), pad[1:])
    return x, dt, a, b, c, jnp.repeat(d, plan.p)[None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a, b, c, d, plan):
    with jax.named_scope("ssd_scan"):
        return _fwd_call(*_operands(x, dt, a, b, c, d, plan), plan,
                         keep_states=False)[0]


def _scan_fwd(x, dt, a, b, c, d, plan):
    with jax.named_scope("ssd_scan"):
        y, states = _fwd_call(*_operands(x, dt, a, b, c, d, plan), plan,
                              keep_states=True)
    return y, (x, dt, a, b, c, d, states)


def _scan_bwd(plan, res, dy):
    x, dt, a, b, c, d, states = res
    B, S, heads = dt.shape
    with jax.named_scope("ssd_scan"):
        dx, ddtx, ddta, db, dc, dd = _bwd_call(
            *_operands(x, dt, a, b, c, d, plan), states, dy, plan)

    def by_step(t):                         # [B, J, S, 128] -> [B, S, heads]
        return jnp.swapaxes(t[..., :plan.gb * plan.r], 1, 2).reshape(
            B, S, heads)

    ddtx, ddta = by_step(ddtx), by_step(ddta)
    return (dx, ddtx + ddta * a, jnp.sum(ddta * dt, axis=(0, 1)),
            db, dc, jnp.sum(dd.reshape(B, heads, plan.p), axis=(0, 2)))


_scan.defvjp(_scan_fwd, _scan_bwd)


@functools.partial(jax.jit, static_argnames=("plan",))
def ssd_scan_kernel(x, dt, a, b, c, d, plan):
    """The scan on whole chunks through the kernels: ``x`` [B, S, heads * P],
    ``dt`` [B, S, heads] float32, ``a`` and ``d`` [heads] float32, ``b`` and
    ``c`` [B, S, G * N], S a multiple of ``plan.chunk`` -> y [B, S,
    heads * P] in ``x``'s type, the ``D x`` term included.  Differentiable
    in all six.  Jitted, so that a model's layers of one shape share one
    trace and one lowering of each kernel."""
    return _scan(x, dt, a, b, c, d, plan)

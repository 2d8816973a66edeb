"""Pallas kernels of CCA's latent mixing (``cca.cca_mix``): everything
between the compressed attention's three projections and the flash kernel,
forward + backward, a row block of a sequence at a time.

With ``x = [qt | kt]`` (``n_q + n_kv`` heads of ``d`` lanes), a head ``h``
of KV group ``g`` and ``t`` a row of one sequence (rows before it are zero):

    z1[t]  = a0 * x[t-1] + a1 * x[t] + b1                 (a channel's own)
    z2_h[t] = z1_h[t-1] @ W0_h + z1_h[t] @ W1_h + b2_h    (a head's own)
    m_qj = (x_qj + x_kg) / 2,   m_kg = mean_{j in g} m_qj
    y = z2 + m;   n = y * rsqrt(mean(y^2) + eps)  (* tau_g for k)
    out = n * cos + swap_halves(n) * (-+ sin)     on the first ``rot`` lanes
    v_out[t] = v[t] on the first half of v's lanes, v[t-1] on the later half

The kernels take ``qt`` [B, S, n_q d] and ``kt``, ``v`` [B, S, n_kv d] as
the projections leave them and write q, k, v where ``pallas_flash.mha``
reads them: a head is a block of ``d`` lanes and nothing is laid out anew.

* a grid program owns ``mix_rows`` rows of one sequence at the whole width
  and walks its heads one after another; the parameters stay in VMEM;
* causality reaches two rows back: a second view of each input gives the
  ``HALO`` rows before the block (zeros before a sequence starts, never the
  previous sequence's rows), and a row shift is a sublane rotation of the
  block with its halo on top;
* the arithmetic: float32 everywhere but the per-head products' operands,
  which are of the stream's type (``z1`` rounded once, as the plain form
  rounds it; the products' sums float32).  The plain form's two other
  intermediate roundings (each product, then ``z2``, to the stream's type)
  are left out, so on bfloat16 the pair reads within those two roundings of
  the plain form and no farther from float32: q, k and the streams'
  gradients within 2^-6 of the largest value (an ulp or two of it), the
  parameters' gradients, sums over every row, within 2^-5; on float32 the
  two agree to 1e-5;
* backward is ONE call whose residuals are ``qt``, ``kt`` and the
  parameters: it recomputes a block's forward up to the unit-RMS heads,
  rounds ``d z2`` to the stream's type before the products' transposes (as
  the plain form's cotangent is), and sums the five parameters' gradients in
  float32 blocks that stay in VMEM while the grid walks (both axes
  sequential).  The transposed convolutions reach two rows FORWARD, so the
  grid walks a sequence's row blocks from the last to the first and a block
  leaves the next one its first row's ``d z1`` and ``d z2 @ W0^T`` in
  scratch (zeros after a sequence ends).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..pallas_utils import LANES, SUBLANES, interpret_mode
from .pallas_flash import _NN, _NT, _TN, _params, _vmem_limit

#: the scope the kernels run under (never one that starts with
#: ``flash_attention``, whose events ``flash_attention_cca_roofline`` sums)
KERNEL_NAME = "cca_mix"
#: rows of a sequence a grid program owns, at most.  At the ZAYA cell's
#: call the forward reads the same from 256 rows to 1024 and a fifth slower
#: at 128; the backward gains a tenth from 256 to 1024, 0.1 ms a call, for
#: five times the compiler's time (BENCH_KERNELS.md, PR 57)
ROWS = 256
#: rows of the view before a block: a whole sublane tile of either dtype
HALO = 2 * SUBLANES


def compiles_for_tpu(seq, head_dim, rotary_dim):
    """Whether the TPU compiler takes these shapes: a head whole 128-lane
    tiles, row blocks of whole halo tiles, a rotation of two equal halves
    inside a head.  (In interpret mode any head and any even rotation
    run.)"""
    return (head_dim % LANES == 0 and seq % HALO == 0
            and rotary_dim % 2 == 0 and 0 < rotary_dim <= head_dim)


def mix_rows(seq):
    """The row block: the most rows up to ``ROWS`` that divide ``seq`` into
    blocks of whole halo tiles (a short sequence is one block)."""
    for rows in range(min(ROWS, seq) // HALO * HALO, 0, -HALO):
        if seq % rows == 0:
            return rows
    return seq


def rotation_tables(cos, sin, head_dim):
    """``cos, sin`` [S, rot] -> float32 [S, d] each: ``cos`` then ones, and
    ``sin`` with its first half negated then zeros, so that the rotation of
    a head is ``n * cos + swap_halves(n) * sin`` on all its lanes."""
    rot, f32 = cos.shape[-1], jnp.float32
    pad = ((0, 0), (0, head_dim - rot))
    sign = jnp.where(jnp.arange(rot) < rot // 2, -1.0, 1.0)
    return (jnp.pad(cos.astype(f32), pad, constant_values=1.0),
            jnp.pad(sin.astype(f32) * sign, pad))


def _roll(x, shift, axis):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(x, shift % x.shape[axis], axis)


def _a_row_later(x, halo):
    """``y[t] = x[t - 1]`` over a block ``x`` [R, d] whose earlier rows are
    ``halo`` [HALO, d]."""
    return _roll(jnp.concatenate([halo, x], axis=0), 1, 0)[HALO:]


def _a_row_earlier(x, after):
    """``y[t] = x[t + 1]`` over a block ``x`` [R, d] whose next row is
    ``after`` [1, d]."""
    tail = jnp.broadcast_to(after, (SUBLANES, x.shape[1]))
    return _roll(jnp.concatenate([x, tail], axis=0), -1, 0)[:x.shape[0]]


def _swap_halves(n, rot, beyond=None):
    """Lanes ``[0, rot/2)`` and ``[rot/2, rot)`` of a head exchanged; what
    lies beyond them is ``beyond`` (None: anything, where the caller
    multiplies it by zero)."""
    half = rot // 2
    lane = jax.lax.broadcasted_iota(jnp.int32, n.shape, 1)
    swapped = jnp.where(lane < half, _roll(n, -half, 1), _roll(n, half, 1))
    if beyond is None or rot == n.shape[1]:
        return swapped
    return jnp.where(lane < rot, swapped, beyond)


def _sum8(x):
    """Eight partial sums over the rows: added up by the caller."""
    return x.reshape(-1, SUBLANES, x.shape[1]).sum(axis=0)


def _rows(ref, lanes, halo_ref, first):
    """A head's rows of a block and of its halo, float32: ([R, d], [HALO,
    d]), the halo zeros where the block is a sequence's first."""
    halo = halo_ref[0, :, lanes].astype(jnp.float32)
    return (ref[0, :, lanes].astype(jnp.float32),
            jnp.where(first, 0.0, halo))


def _depthwise(x, x_before, halo, taps_ref, b1_ref, cols, first):
    """``z1`` over the block (``x_before`` its rows a row later) and its
    halo -> (z1 [R, d], z1's halo): the halo's last row is ``z1[r0 - 1]``,
    zeros before a sequence."""
    a0, a1 = taps_ref[0:1, cols], taps_ref[1:2, cols]
    b1 = b1_ref[:, cols]
    z1 = a0 * x_before + a1 * x + b1
    z1_halo = jnp.where(first, 0.0, a0 * _roll(halo, 1, 0) + a1 * halo + b1)
    return z1, z1_halo


def _v_blocks(width):
    """v's lanes a lane block at a time: (lanes, whether the block holds
    lanes of the later half, which are a row later; of its lanes which are
    NOT, or None where all are)."""
    half, step = width // 2, min(LANES, width)
    for at in range(0, width, step):
        lane = at + jax.lax.broadcasted_iota(jnp.int32, (1, step), 1)
        yield (slice(at, at + step), at + step > half,
               lane < half if at < half < at + step else None)


def _heads_of(g, heads, kv_heads, d):
    """Of KV group ``g``: (its k head's lanes in ``kt``, its parameters'
    columns, its index among all heads), then the same of its query heads
    in ``qt``."""
    group = heads // kv_heads
    k = (slice(g * d, (g + 1) * d),
         slice((heads + g) * d, (heads + g + 1) * d), heads + g)
    qs = [(slice(j * d, (j + 1) * d), slice(j * d, (j + 1) * d), j)
          for j in range(g * group, (g + 1) * group)]
    return k, qs


# --------------------------------------------------------------------- fwd
def _fwd_kernel(qt_ref, kt_ref, v_ref, qh_ref, kh_ref, vh_ref, taps_ref,
                b1_ref, w_ref, b2_ref, tau_ref, cos_ref, sin_ref,
                q_ref, k_ref, vo_ref, *, heads, kv_heads, rot, eps):
    d = qt_ref.shape[2] // heads
    group = heads // kv_heads
    first = pl.program_id(1) == 0
    cos, sin = cos_ref[...], sin_ref[...]

    def mixed(x, halo, cols, h):
        z1, z1_halo = _depthwise(x, _a_row_later(x, halo), halo, taps_ref,
                                 b1_ref, cols, first)
        z1, z1_halo = z1.astype(w_ref.dtype), z1_halo.astype(w_ref.dtype)
        # a row later after the product: one rounding of z1 serves both
        earlier = _a_row_later(
            jax.lax.dot_general(z1, w_ref[0, h], _NN,
                                preferred_element_type=jnp.float32),
            jax.lax.dot_general(z1_halo, w_ref[0, h], _NN,
                                preferred_element_type=jnp.float32))
        return earlier + jax.lax.dot_general(
            z1, w_ref[1, h], _NN,
            preferred_element_type=jnp.float32) + b2_ref[:, cols]

    def finish(y, scale=None):
        n = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        if scale is not None:
            n = n * scale
        return n * cos + _swap_halves(n, rot) * sin

    for g in range(kv_heads):
        (k_lanes, k_cols, k_head), qs = _heads_of(g, heads, kv_heads, d)
        xk, xk_halo = _rows(kt_ref, k_lanes, kh_ref, first)
        m_k = 0.0
        for lanes, cols, h in qs:
            x, halo = _rows(qt_ref, lanes, qh_ref, first)
            m_q = (x + xk) / 2
            m_k = m_k + m_q
            q_ref[0, :, lanes] = finish(
                mixed(x, halo, cols, h) + m_q).astype(q_ref.dtype)
        k_ref[0, :, k_lanes] = finish(
            mixed(xk, xk_halo, k_cols, k_head) + m_k / group,
            tau_ref[:, k_lanes]).astype(k_ref.dtype)
    for lanes, shifted, stays in _v_blocks(v_ref.shape[2]):
        if not shifted:
            vo_ref[0, :, lanes] = v_ref[0, :, lanes]
            continue
        v, halo = _rows(v_ref, lanes, vh_ref, first)
        later = _a_row_later(v, halo)
        if stays is not None:
            later = jnp.where(stays, v, later)
        vo_ref[0, :, lanes] = later.astype(vo_ref.dtype)


def _blocks(rows, n, forward):
    """Block specs of a grid ``(b, n row blocks)``: rows of a width, the
    halo before them, a whole parameter, rotary's rows.  Backward the row
    blocks are walked from a sequence's last to its first."""
    def at(r):
        return r if forward else n - 1 - r

    def owned(width):
        return pl.BlockSpec((1, rows, width), lambda b, r: (b, at(r), 0))

    def halo(width):
        return pl.BlockSpec(
            (1, HALO, width), lambda b, r: (
                b, jnp.maximum(at(r) * (rows // HALO) - 1, 0), 0))

    def whole(t):
        return pl.BlockSpec(t.shape, lambda b, r: (0,) * t.ndim)

    def table(d):
        return pl.BlockSpec((rows, d), lambda b, r: (at(r), 0))

    return owned, halo, whole, table


def _need(rows, c, d, itemsize, backward):
    """VMEM bytes of a call: its row blocks of ``c`` channels in all with
    their halos (double-buffered; backward reads the cotangents too), the
    per-head matrices (backward their float32 sums beside them) and some
    thirty float32 terms of a head."""
    streams = 3 if backward else 2
    return (2 * streams * (rows + HALO) * c * itemsize
            + 4 * c * d * (itemsize + (4 if backward else 0))
            + 32 * (rows + HALO) * d * 4)


# Each call is behind a ``jax.jit`` of its own, as ``pallas_eva_pool``'s: a
# model's layers and passes are then one trace and one lowered body.
_STATIC = ("heads", "kv_heads", "rot", "eps", "rows")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd_call(qt, kt, v, taps, b1, w, b2, tau, cos, sin, heads, kv_heads,
              rot, eps, rows):
    b, s, cq = qt.shape
    ck, d = kt.shape[2], qt.shape[2] // heads
    n = s // rows
    owned, halo, whole, table = _blocks(rows, n, True)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, kv_heads=kv_heads,
                          rot=rot, eps=eps),
        grid=(b, n),
        in_specs=[owned(cq), owned(ck), owned(ck),
                  halo(cq), halo(ck), halo(ck)]
        + [whole(t) for t in (taps, b1, w, b2, tau)] + [table(d), table(d)],
        out_specs=[owned(cq), owned(ck), owned(ck)],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype)
                   for t in (qt, kt, v)],
        cost_estimate=pl.CostEstimate(
            flops=(4 * d + 40) * (qt.size + kt.size),
            transcendentals=b * s * (heads + kv_heads),
            bytes_accessed=2 * (qt.size + kt.size + v.size)
            * qt.dtype.itemsize),
        interpret=interpret_mode(),
        **_params("parallel", "parallel", vmem=_vmem_limit(
            _need(rows, cq + 2 * ck, d, qt.dtype.itemsize, False))))
    with jax.named_scope(KERNEL_NAME):      # the kernels' name in a trace
        return call(qt, kt, v, qt, kt, v, taps, b1, w, b2, tau, cos, sin)


# ---------------------------------------------------------------------- bwd
def _bwd_kernel(qt_ref, kt_ref, qh_ref, kh_ref, dq_ref, dk_ref, dvo_ref,
                taps_ref, b1_ref, w_ref, b2_ref, tau_ref, cos_ref, sin_ref,
                dqt_ref, dkt_ref, dv_ref, dtaps_ref, db1_ref, dw_ref,
                db2_ref, dtau_ref, carry, v_carry,
                *, heads, kv_heads, rot, eps):
    d = qt_ref.shape[2] // heads
    group = heads // kv_heads
    walked = pl.program_id(1)
    first = walked == pl.num_programs(1) - 1    # a sequence's first rows
    cos, sin = cos_ref[...], sin_ref[...]
    f32 = jnp.float32

    @pl.when((pl.program_id(0) == 0) & (walked == 0))
    def _init_sums():
        for ref in (dtaps_ref, db1_ref, dw_ref, db2_ref, dtau_ref):
            ref[...] = jnp.zeros_like(ref)

    @pl.when(walked == 0)       # nothing after a sequence's last rows
    def _init_carry():
        carry[...] = jnp.zeros_like(carry)
        v_carry[...] = jnp.zeros_like(v_carry)

    def head(x, halo, cols, h, m, do, tau_lanes=None):
        """A head's forward again and its backward: (``d x`` through the
        convolutions, ``d y``: what the mean's terms take)."""
        w0, w1 = w_ref[0, h], w_ref[1, h]
        x_before = _a_row_later(x, halo)
        z1, z1_halo = _depthwise(x, x_before, halo, taps_ref, b1_ref, cols,
                                 first)
        z1_before = _a_row_later(z1, z1_halo).astype(w0.dtype)
        z1 = z1.astype(w0.dtype)
        y = (jax.lax.dot_general(z1_before, w0, _NN,
                                 preferred_element_type=f32)
             + jax.lax.dot_general(z1, w1, _NN, preferred_element_type=f32)
             + b2_ref[:, cols] + m)
        r = jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        n = y * r
        dn = do * cos + _swap_halves(do * sin, rot, 0.0)
        along = dn * n
        if tau_lanes is not None:
            dtau_ref[:, tau_lanes] += _sum8(along)
            r = r * tau_ref[:, tau_lanes]
        dy = r * (dn - n * jnp.mean(along, axis=-1, keepdims=True))
        db2_ref[:, cols] += _sum8(dy)
        # d z2 in the stream's type, as the plain form's cotangent is
        dz2 = dy.astype(w0.dtype)
        dw_ref[0, h] += jax.lax.dot_general(z1_before, dz2, _TN,
                                            preferred_element_type=f32)
        dw_ref[1, h] += jax.lax.dot_general(z1, dz2, _TN,
                                            preferred_element_type=f32)
        through_w0 = jax.lax.dot_general(dz2, w0, _NT,
                                         preferred_element_type=f32)
        dz1 = (jax.lax.dot_general(dz2, w1, _NT, preferred_element_type=f32)
               + _a_row_earlier(through_w0, carry[1:2, cols]))
        dz1_after = _a_row_earlier(dz1, carry[0:1, cols])
        carry[0:1, cols] = dz1[0:1]
        carry[1:2, cols] = through_w0[0:1]
        dtaps_ref[0, :, cols] += _sum8(dz1 * x_before)
        dtaps_ref[1, :, cols] += _sum8(dz1 * x)
        db1_ref[:, cols] += _sum8(dz1)
        return (taps_ref[1:2, cols] * dz1
                + taps_ref[0:1, cols] * dz1_after), dy

    for g in range(kv_heads):
        (k_lanes, k_cols, k_head), qs = _heads_of(g, heads, kv_heads, d)
        xk, xk_halo = _rows(kt_ref, k_lanes, kh_ref, first)
        xqs = [_rows(qt_ref, lanes, qh_ref, first) for lanes, _, _ in qs]
        m_qs = [(x + xk) / 2 for x, _ in xqs]
        dxk, dy_k = head(xk, xk_halo, k_cols, k_head, sum(m_qs) / group,
                         dk_ref[0, :, k_lanes].astype(f32), k_lanes)
        dxk = dxk + dy_k / 2
        for (lanes, cols, h), (x, halo), m_q in zip(qs, xqs, m_qs):
            dx, dy = head(x, halo, cols, h, m_q,
                          dq_ref[0, :, lanes].astype(f32))
            dqt_ref[0, :, lanes] = (dx + dy / 2 + dy_k / (2 * group)
                                    ).astype(dqt_ref.dtype)
            dxk = dxk + dy / 2
        dkt_ref[0, :, k_lanes] = dxk.astype(dkt_ref.dtype)
    # the value shift's transpose: the later half a row EARLIER
    for lanes, shifted, stays in _v_blocks(dvo_ref.shape[2]):
        if not shifted:
            dv_ref[0, :, lanes] = dvo_ref[0, :, lanes]
            continue
        dvo = dvo_ref[0, :, lanes].astype(f32)
        earlier = _a_row_earlier(dvo, v_carry[0:1, lanes])
        v_carry[0:1, lanes] = dvo[0:1]
        if stays is not None:
            earlier = jnp.where(stays, dvo, earlier)
        dv_ref[0, :, lanes] = earlier.astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_call(qt, kt, dq, dk, dvo, taps, b1, w, b2, tau, cos, sin, heads,
              kv_heads, rot, eps, rows):
    from jax.experimental.pallas import tpu as pltpu

    b, s, cq = qt.shape
    ck, d = kt.shape[2], qt.shape[2] // heads
    c, n, f32 = cq + ck, s // rows, jnp.float32
    owned, halo, whole, table = _blocks(rows, n, False)
    sums = [jax.ShapeDtypeStruct(shape, f32) for shape in (
        (2, SUBLANES, c), (SUBLANES, c), w.shape, (SUBLANES, c),
        (SUBLANES, ck))]
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, kv_heads=kv_heads,
                          rot=rot, eps=eps),
        grid=(b, n),
        in_specs=[owned(cq), owned(ck), halo(cq), halo(ck),
                  owned(cq), owned(ck), owned(ck)]
        + [whole(t) for t in (taps, b1, w, b2, tau)] + [table(d), table(d)],
        out_specs=[owned(cq), owned(ck), owned(ck)]
        + [whole(t) for t in sums],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype)
                   for t in (qt, kt, dvo)] + sums,
        scratch_shapes=[pltpu.VMEM((SUBLANES, c), f32),
                        pltpu.VMEM((SUBLANES, ck), f32)],
        cost_estimate=pl.CostEstimate(
            flops=(12 * d + 100) * (qt.size + kt.size),
            transcendentals=b * s * (heads + kv_heads),
            bytes_accessed=(3 * (qt.size + kt.size) + 2 * dvo.size)
            * qt.dtype.itemsize),
        interpret=interpret_mode(),
        **_params("arbitrary", "arbitrary", vmem=_vmem_limit(
            _need(rows, cq + 2 * ck, d, qt.dtype.itemsize, True))))
    with jax.named_scope(KERNEL_NAME):
        return call(qt, kt, qt, kt, dq, dk, dvo, taps, b1, w, b2, tau, cos,
                    sin)


# ------------------------------------------------------------- public API
@functools.partial(jax.custom_vjp, nondiff_argnums=(10, 11, 12, 13))
def _mix(qt, kt, v, taps, b1, w, b2, tau, cos, sin, heads, kv_heads, eps,
         rows):
    return _mix_fwd(qt, kt, v, taps, b1, w, b2, tau, cos, sin, heads,
                    kv_heads, eps, rows)[0]


def _operands(qt, taps, b1, w, b2, tau, cos, sin, heads):
    """The parameters and rotary's tables as the kernels read them: rows of
    the packed width, the matrices in the stream's type, k's temperature a
    lane a channel, the tables a head wide."""
    d = qt.shape[2] // heads
    f32 = jnp.float32
    return (taps.astype(f32), b1.astype(f32)[None], w.astype(qt.dtype),
            b2.astype(f32)[None], jnp.repeat(tau.astype(f32), d)[None],
            *rotation_tables(cos, sin, d))


def _mix_fwd(qt, kt, v, taps, b1, w, b2, tau, cos, sin, heads, kv_heads,
             eps, rows):
    out = _fwd_call(qt, kt, v, *_operands(qt, taps, b1, w, b2, tau, cos, sin,
                                          heads),
                    heads=heads, kv_heads=kv_heads, rot=cos.shape[-1],
                    eps=eps, rows=rows)
    return tuple(out), (qt, kt, taps, b1, w, b2, tau, cos, sin)


def _mix_bwd(heads, kv_heads, eps, rows, res, cts):
    qt, kt, taps, b1, w, b2, tau, cos, sin = res
    dqt, dkt, dv, dtaps, db1, dw, db2, dtau = _bwd_call(
        qt, kt, *cts, *_operands(qt, taps, b1, w, b2, tau, cos, sin, heads),
        heads=heads, kv_heads=kv_heads, rot=cos.shape[-1], eps=eps,
        rows=rows)
    return (dqt, dkt, dv, dtaps.sum(axis=1).astype(taps.dtype),
            db1.sum(axis=0).astype(b1.dtype), dw.astype(w.dtype),
            db2.sum(axis=0).astype(b2.dtype),
            dtau.reshape(SUBLANES, kv_heads, -1).sum(axis=(0, 2)).astype(
                tau.dtype), jnp.zeros_like(cos), jnp.zeros_like(sin))


_mix.defvjp(_mix_fwd, _mix_bwd)


def mix(qt, kt, v, taps, taps_bias, head_kernel, head_bias, temperature,
        cos, sin, *, heads, kv_heads, eps):
    """``qt`` [B, S, n_q d], ``kt`` and ``v`` [B, S, n_kv d] of one type
    (float32 or bfloat16), the depthwise filter ``taps`` [2, c] with
    ``taps_bias`` [c] (``c = (n_q + n_kv) d``), the per-head one
    ``head_kernel`` [2, n_q + n_kv, d, d] with ``head_bias`` [c],
    ``temperature`` [n_kv], rotary's ``cos, sin`` [S, rot] -> (q, k, v) of
    the inputs' shapes and type.  ``S`` a multiple of ``HALO`` rows.
    Differentiable (custom VJP) in the three streams and the five
    parameters."""
    from ...telemetry.trace import count_kernel_path

    S = qt.shape[1]
    rows = mix_rows(S)
    if rows % HALO or not qt.dtype == kt.dtype == v.dtype:
        raise ValueError(
            f"{S} rows of {qt.dtype} | {kt.dtype} | {v.dtype} are not whole "
            f"tiles of {HALO} rows of one type")
    if head_kernel.shape[0] != 2 or taps.shape[0] != 2:
        raise ValueError("the kernels hold filters two rows wide")
    count_kernel_path(KERNEL_NAME, "pallas")
    return _mix(qt, kt, v, taps, taps_bias, head_kernel, head_bias,
                temperature, cos, sin, heads, kv_heads, float(eps), rows)

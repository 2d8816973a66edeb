"""Pallas kernels of EVA's chunk summaries (``eva.chunk_summaries``): per
head and chunk ``c`` of ``C`` rows, with learned directions ``mu, phi``,

    kb_c = sum_j softmax_j(mu . k_j) k_j,   vb_c = sum_j softmax_j(phi . k_j) v_j

forward + backward, everything between the loads and the stores float32.

The kernels take rotated k and v ``[B, S, N*D]`` as the projections hold
them, a head a block of ``D`` lanes (``pallas_eva``'s layout), and write
``[B, S / C, N*D]`` in k's dtype: what ``pallas_eva.eva_mha`` takes.

* a grid program owns a block of whole chunks of one head (``pool_rows``):
  k is read once for both directions, v once;
* the logits of both directions are ONE matmul of the block against a
  ``[D, 128]`` matrix whose left 64 columns are ``mu`` and right 64 ``phi``
  (so a logit comes out spread over the lanes it will weigh), the float32
  directions split into three bfloat16 parts and accumulated in float32
  (bfloat16 keys are exact, so the products are; float32 keys are split the
  same way): the reduction over D's lanes is the MXU's;
* the rows are read A ROW OF EVERY CHUNK at a time (a sublane-strided load,
  start ``j``, stride ``C``: eight chunks a vector register), so the
  softmax over a chunk's rows and the two pooled sums are elementwise over
  ``C`` such slabs, with no reduction across sublanes and no loop: a
  program's body is straight-line code over its block.  Strided loads are of
  32-bit words: a bfloat16 block is read as the uint32 words its rows pair
  up in, a word's halves two rows' float32 upper bits;
* backward is one call that recomputes the weights from k (no residual but
  k, v and the directions).  With ``w = softmax(l)``, ``g_j = dkb_c . k_j``,
  ``dl_j = w_j (g_j - sum_i w_i g_i)`` (and ``'`` for ``phi``, whose ``g'``
  is ``dvb_c . v_j``): ``dk_j = w_j dkb_c + dl_j mu + dl'_j phi``,
  ``dv_j = w'_j dvb_c``, ``dmu = sum dl_j k_j``, ``dphi = sum dl'_j k_j``,
  the last two in a float32 block that stays in VMEM while the grid walks
  the head's row blocks (that axis sequential).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..pallas_utils import LANES, SUBLANES, interpret_mode
from .pallas_flash import _NN, _params, _vmem_limit

#: the scope the kernels run under (never ``pallas_eva.KERNEL_NAME``, whose
#: events ``eva_attention_roofline`` counts)
KERNEL_NAME = "eva_pool"
#: rows of a head a grid program owns, at most: 512 read a third slower
#: forward, 1024 a sixth (BENCH_KERNELS.md, PR 51)
ROWS = 2048
#: bfloat16 parts a float32 number is split into for the MXU
PARTS = 3
_HALF = LANES // 2


def _strides(chunk, dtype):
    """Rows of 32 bits, or pairs of 16 within a chunk."""
    return dtype == jnp.float32 or (dtype == jnp.bfloat16 and chunk % 2 == 0)


def compiles_for_tpu(seq, chunk, head_dim, dtype):
    """Whether the TPU compiler takes these shapes: a head whole 128-lane
    tiles, row blocks whose summaries are whole sublane tiles of either
    dtype (16 chunks), rows a strided load takes.  (In interpret mode any
    multiple of 8 chunks runs.)"""
    return (head_dim % LANES == 0 and seq % (2 * SUBLANES * chunk) == 0
            and _strides(chunk, dtype))


def pool_rows(seq, chunk):
    """The row block: the most rows up to ``ROWS`` that divide ``seq`` into
    blocks of whole 16-chunk tiles; a sequence with no such divisor is one
    block."""
    step = 2 * SUBLANES * chunk
    for rows in range(min(ROWS, seq) // step * step, 0, -step):
        if seq % rows == 0:
            return rows
    return seq


def _split(x):
    """float32 ``x`` -> ``PARTS`` bfloat16 parts whose float32 sum is ``x``
    exactly: each the top 16 bits of what the parts before it left, so no
    part is ROUNDED to bfloat16 (XLA on a TPU may drop a float32 -> bfloat16
    -> float32 round trip as excess precision, which left a rounded first
    part and zeros: the chip read the summaries 1e-3 off; PERF.md, PR 51)."""
    out = []
    for _ in range(PARTS - 1):
        top = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(x, jnp.uint32)
            & jnp.uint32(0xffff0000), jnp.float32)
        out.append(top.astype(jnp.bfloat16))
        x = x - top
    return out + [x.astype(jnp.bfloat16)]


def direction_parts(mu, phi):
    """``mu, phi`` [N, D] float32 -> [N, PARTS * D, 128] bfloat16: for each
    head the matrix the logits are a matmul against (``mu`` in the left 64
    columns, ``phi`` in the right), its parts one under another."""
    both = jnp.concatenate(
        [jnp.broadcast_to(t.astype(jnp.float32)[..., None], (*t.shape, _HALF))
         for t in (mu, phi)], axis=-1)
    return jnp.concatenate(_split(both), axis=1)


def _logits(k, m_ref):
    """``k`` [R, D] -> float32 [R, 128]: ``mu . k_j`` in the left 64
    columns, ``phi . k_j`` in the right; the terms a float32 product needs,
    smallest first."""
    d = k.shape[1]
    ks = [k] if k.dtype == jnp.bfloat16 else _split(k.astype(jnp.float32))
    terms = sorted(((i, j) for i in range(len(ks)) for j in range(PARTS - i)),
                   key=lambda ij: -(ij[0] + ij[1]))
    return sum(jax.lax.dot_general(ks[i], m_ref[0, j * d:(j + 1) * d, :], _NN,
                                   preferred_element_type=jnp.float32)
               for i, j in terms)


def _slabs(ref, chunk):
    """Row ``j`` of every chunk of the block ``ref`` [1, R, D], float32
    [R / chunk, D], for every ``j``."""
    n = ref.shape[1] // chunk
    if ref.dtype != jnp.bfloat16:
        return [ref[0, pl.ds(j, n, stride=chunk), :].astype(jnp.float32)
                for j in range(chunk)]
    from jax.experimental.pallas import tpu as pltpu

    # word row i holds rows 2i (low half) and 2i + 1 (high half)
    words, out = ref.bitcast(jnp.uint32), []
    for i in range(chunk // 2):
        w = words[0, pl.ds(i, n, stride=chunk // 2), :]
        out += [pltpu.bitcast(w << 16, jnp.float32),
                pltpu.bitcast(w & jnp.uint32(0xffff0000), jnp.float32)]
    return out


def _weights(l_scr, chunk, width):
    """softmax over a chunk's rows for every chunk of the block -> for every
    row ``j`` (``mu``'s weights, ``phi``'s), each [R / chunk, width]."""
    from jax.experimental.pallas import tpu as pltpu

    n = l_scr.shape[0] // chunk
    ls = [l_scr[pl.ds(j, n, stride=chunk), :] for j in range(chunk)]
    m = functools.reduce(jnp.maximum, ls)
    es = [jnp.exp(l - m) for l in ls]
    inv = 1.0 / sum(es)
    left = jax.lax.broadcasted_iota(jnp.int32, m.shape, 1) < _HALF

    def wide(x):    # a head of D lanes: whole lane tiles, or (a test) fewer
        return jnp.tile(x, (1, -(-width // LANES)))[:, :width]

    out = []
    for e in es:
        w = e * inv
        swapped = pltpu.roll(w, _HALF, 1)
        out.append((wide(jnp.where(left, w, swapped)),
                    wide(jnp.where(left, swapped, w))))
    return out


# --------------------------------------------------------------------- fwd
def _fwd_kernel(m_ref, k_ref, v_ref, kb_ref, vb_ref, l_scr, *, chunk):
    width = k_ref.shape[2]
    l_scr[...] = _logits(k_ref[0], m_ref)
    ws = _weights(l_scr, chunk, width)
    kb_ref[0] = sum(w * k for (w, _), k in zip(ws, _slabs(k_ref, chunk))
                    ).astype(kb_ref.dtype)
    vb_ref[0] = sum(w * v for (_, w), v in zip(ws, _slabs(v_ref, chunk))
                    ).astype(vb_ref.dtype)


def _specs(rows, chunk, width):
    """Block specs of a grid ``(b, head, row block)``: a head's rows, its
    summaries, its direction matrix."""
    owned = pl.BlockSpec((1, rows, width), lambda b, g, r: (b, r, g))
    pooled = pl.BlockSpec((1, rows // chunk, width), lambda b, g, r: (b, r, g))
    matrix = pl.BlockSpec((1, PARTS * width, LANES), lambda b, g, r: (g, 0, 0))
    return owned, pooled, matrix


def _need(rows, chunk, width, itemsize, owned):
    """VMEM bytes of a call with ``owned`` row blocks among its operands
    (double-buffered; the written ones a float32 scratch copy each), two
    blocks of summaries, the logits and a term of theirs."""
    return (rows * width * (owned * 2 * itemsize + (owned - 2) * 4)
            + rows // chunk * width * 4 * itemsize + rows * LANES * 4 * 3)


# Each call is behind a ``jax.jit`` of its own, as ``moe/dropless.py``'s walk:
# a model's layers and passes are then one trace and one lowered body.  A
# kernel's body is straight-line code over its block, and traced and lowered
# at every call site it cost a cell seconds of set-up in every process
# (BENCH_KERNELS.md, PR 51).
@functools.partial(jax.jit, static_argnames=("heads", "chunk", "rows"))
def _fwd_call(m, k, v, heads, chunk, rows):
    from jax.experimental.pallas import tpu as pltpu

    b, s, _ = k.shape
    d = k.shape[2] // heads
    owned, pooled, matrix = _specs(rows, chunk, d)
    out = jax.ShapeDtypeStruct((b, s // chunk, heads * d), k.dtype)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk),
        grid=(b, heads, s // rows),
        in_specs=[matrix, owned, owned],
        out_specs=[pooled, pooled],
        out_shape=[out, out],
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * PARTS * k.size * LANES + 8 * k.size,
            transcendentals=b * s * heads * LANES,
            bytes_accessed=(2 * k.size + 2 * out.size) * k.dtype.itemsize),
        interpret=interpret_mode(),
        **_params("parallel", "parallel", "arbitrary", vmem=_vmem_limit(
            _need(rows, chunk, d, k.dtype.itemsize, 2))))
    with jax.named_scope(KERNEL_NAME):      # the kernels' name in a trace
        return call(m, k, v)


# ---------------------------------------------------------------------- bwd
def _bwd_kernel(m_ref, dir_ref, k_ref, v_ref, dkb_ref, dvb_ref,
                dk_ref, dv_ref, ddir_ref, l_scr, dk_scr, dv_scr, *, chunk):
    rows, width = k_ref.shape[1:]

    @pl.when(pl.program_id(2) == 0)
    def _init_head():
        ddir_ref[...] = jnp.zeros_like(ddir_ref)

    l_scr[...] = _logits(k_ref[0], m_ref)
    ws = _weights(l_scr, chunk, width)
    ks, vs = _slabs(k_ref, chunk), _slabs(v_ref, chunk)
    dkb, dvb = (ref[0].astype(jnp.float32) for ref in (dkb_ref, dvb_ref))
    mu, phi = dir_ref[0:1, :], dir_ref[1:2, :]
    gk = [jnp.sum(dkb * k, axis=1, keepdims=True) for k in ks]
    gv = [jnp.sum(dvb * v, axis=1, keepdims=True) for v in vs]
    mean_k = sum(w * g for (w, _), g in zip(ws, gk))
    mean_v = sum(w * g for (_, w), g in zip(ws, gv))
    dmu = dphi = 0.0
    for j, (wk, wv) in enumerate(ws):
        dlk, dlv = wk * (gk[j] - mean_k), wv * (gv[j] - mean_v)
        slab = pl.ds(j, rows // chunk, stride=chunk)
        dk_scr[slab, :] = wk * dkb + dlk * mu + dlv * phi
        dv_scr[slab, :] = wv * dvb
        dmu, dphi = dmu + dlk * ks[j], dphi + dlv * ks[j]
    dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
    dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)
    # eight partial sums a direction: added up by the caller
    ddir_ref[0, :SUBLANES, :] += dmu.reshape(-1, SUBLANES, width).sum(axis=0)
    ddir_ref[0, SUBLANES:, :] += dphi.reshape(-1, SUBLANES, width).sum(axis=0)


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "rows"))
def _bwd_call(m, dirs, k, v, dkb, dvb, heads, chunk, rows):
    from jax.experimental.pallas import tpu as pltpu

    b, s, _ = k.shape
    d = k.shape[2] // heads
    owned, pooled, matrix = _specs(rows, chunk, d)
    pair = pl.BlockSpec((2, d), lambda b, g, r: (0, g))
    partial = pl.BlockSpec((1, 2 * SUBLANES, d), lambda b, g, r: (b, 0, g))
    f32 = jnp.float32
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        grid=(b, heads, s // rows),
        in_specs=[matrix, pair, owned, owned, pooled, pooled],
        out_specs=[owned, owned, partial],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, 2 * SUBLANES, heads * d), f32)],
        scratch_shapes=[pltpu.VMEM((rows, LANES), f32),
                        pltpu.VMEM((rows, d), f32), pltpu.VMEM((rows, d), f32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * PARTS * k.size * LANES + 24 * k.size,
            transcendentals=b * s * heads * LANES,
            bytes_accessed=(4 * k.size + 2 * dkb.size) * k.dtype.itemsize),
        interpret=interpret_mode(),
        **_params("parallel", "parallel", "arbitrary", vmem=_vmem_limit(
            _need(rows, chunk, d, k.dtype.itemsize, 4))))
    with jax.named_scope(KERNEL_NAME):
        return call(m, dirs, k, v, dkb, dvb)


# ------------------------------------------------------------- public API
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _pool(k, v, mu, phi, heads, chunk, rows):
    return _pool_fwd(k, v, mu, phi, heads, chunk, rows)[0]


def _pool_fwd(k, v, mu, phi, heads, chunk, rows):
    kb, vb = _fwd_call(direction_parts(mu, phi), k, v, heads, chunk, rows)
    return (kb, vb), (k, v, mu, phi)


def _pool_bwd(heads, chunk, rows, res, cts):
    k, v, mu, phi = res
    dirs = jnp.stack([mu.reshape(-1), phi.reshape(-1)])
    dk, dv, ddirs = _bwd_call(direction_parts(mu, phi), dirs, k, v, *cts,
                              heads, chunk, rows)
    ddirs = ddirs.reshape(-1, 2, SUBLANES, *mu.shape).sum(axis=(0, 2))
    return dk, dv, ddirs[0], ddirs[1]


_pool.defvjp(_pool_fwd, _pool_bwd)


def pool(k, v, mu, phi, chunk):
    """``k, v`` [B, S, N, D] float32 or bfloat16 (``k`` rotated), ``mu, phi``
    [N, D] -> the chunk summaries ``(kb, vb)`` [B, S / chunk, N, D] in
    ``k``'s dtype.  ``S`` a multiple of 8 chunks.  Differentiable (custom
    VJP) in all four operands."""
    from ...telemetry.trace import count_kernel_path

    B, S, N, D = k.shape
    chunk = int(chunk)
    if S % (SUBLANES * chunk) or not (_strides(chunk, k.dtype)
                                      and _strides(chunk, v.dtype)):
        raise ValueError(f"{S} {k.dtype} | {v.dtype} rows are not a multiple "
                         f"of {SUBLANES} chunks of {chunk} rows of 32 bits")
    count_kernel_path(KERNEL_NAME, "in_place_1")
    # not under "attention_layout": k and v go to the attention too, and
    # autodiff's adds of the two consumers' dk, dv would take that name
    k, v = (t.reshape(B, S, N * D) for t in (k, v))
    kb, vb = _pool(k, v, mu.astype(jnp.float32),
                   phi.astype(jnp.float32), N, chunk, pool_rows(S, chunk))
    with jax.named_scope("attention_layout"):
        return tuple(t.reshape(B, S // chunk, N, D) for t in (kb, vb))

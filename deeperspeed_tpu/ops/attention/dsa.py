"""Learned sparse attention (DSA, the lightning indexer of the
DeepSeek-V3.2-Exp report, here on grouped-query heads): a small second
attention scores every earlier key for every row, the row keeps its ``topk``
best, and the main attention is one softmax over those.

With ``H_I`` indexer heads of ``D_I`` over ONE shared key head, on rotated
``q^I [B, S, H_I, D_I]``, ``k^I [B, S, D_I]`` and per-row head weights
``w [B, S, H_I]`` (float32, the scale ``H_I^-1/2 D_I^-1/2`` already in them):

* scores      ``I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])``, ``s <= t``
  (operands in the compute type, products summed and weighted in float32);
* selection   ``S_t`` = the ``min(t + 1, topk)`` largest ``I[t, s]``, of
  equal scores the lower position first: exactly that many, a constant of
  the backward pass (``dsa_select``);
* attention   ``o[t, h] = sum_{s in S_t} softmax_{s in S_t}(scale q[t, h] .
  k[s, h // rep]) v[s, h // rep]``, statistics float32 (``dsa_attention``);
* the indexer's loss  ``mean_t KL(pbar_t || softmax_{S_t} I[t, .])`` with
  ``pbar_t = mean_h softmax_{S_t}(scale q[t, h] . k[s])`` held constant: its
  gradient ``(softmax_{S_t}(I) - pbar) / rows`` reaches ``w, q^I, k^I`` only
  (``dsa_indexer_loss``).

What the selection leaves for the later passes is PACKED: int32 words
``[B, Sp, W]`` (``pallas_dsa.sel_layout``; 34 MB a layer at 16k) and a count
of chosen pairs per tile of the kernels' walk; both are named
``dsa_selection`` for a remat policy, and so are the indexer's gradients
(``dsa_indexer_grads``: they are made with the loss, in one pass over the
rows), so that a recomputed layer neither scores nor selects nor imitates a
second time (``SAVED_BY_REMAT``).  No ``[heads, S, S]`` array of the main
attention exists where the kernels run: the kernels hold a tile, and the
loss reads the head-averaged probabilities ``[rows, Sp]`` a chunk of rows at
a time.  The indexer's plane exists a chunk of rows at a time too.

On a TPU the selection (with the scores), the attention, the head-averaged
probabilities and the loss with its gradients are kernels
(``pallas_dsa.py``): of the indexer's plane a tile at a time exists, in
VMEM.  The attention's three are tiled on two levels (a grid program owns a
wide block of rows, of columns in dk/dv, and walks the selection's tiles in
its body; ``pallas_dsa.attend_plan`` sizes the blocks from the shapes, and
``telemetry.kernel_paths()["dsa_attention_walk"]`` says which walk a call
got: ``resident`` | ``span``); ``SelLayout.rows`` stays the granularity of
the counts, of the loss's scan and of ``dsa_head_probs``' calls (which walk
the heads in the kernel's body, ``pallas_dsa.head_probs_plan``).
Elsewhere, and with ``use_pallas`` False, plain forms (the attention's then
holds ``[B, N, S, S]``, the loss's ``[B, H_I, rows, cols]`` a chunk of rows:
tests' sizes only).  The equations with what a published config leaves to
assumption: ``benchmarks/reference/keye_ref.py``.
"""

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ...accelerator import get_accelerator
from . import pallas_dsa
from .pallas_flash import SAVED_BY_REMAT as _FLASH_SAVED

SELECTION_SAVED, GRADS_SAVED = "dsa_selection", "dsa_indexer_grads"
#: what a recomputed layer keeps of a DSA call: the attention kernel's two
#: residuals under ``pallas_flash``'s names, the packed selection and the
#: indexer's gradients
SAVED_BY_REMAT = _FLASH_SAVED + (SELECTION_SAVED, GRADS_SAVED)


class Selection(NamedTuple):
    """What ``dsa_select`` leaves: ``words`` int32 ``[B, Sp, W]`` (bit ``j``
    of word ``(t, c)``: row ``t`` chose column ``j * W + c``), ``counts``
    int32 ``[B * nq * n]`` (chosen pairs of row block ``i`` against chunk
    ``j``, at ``(b * nq + i) * n + j``), the ``layout`` and the length."""
    words: Any
    counts: Any
    layout: pallas_dsa.SelLayout
    seq: int

    def mask(self):
        """bool ``[B, S, S]``: tests' sizes."""
        return pallas_dsa.unpack_rows(self.words, self.layout.chunks)[
            :, :self.seq, :self.seq]

    def pairs_selected(self):
        return jnp.sum(self.counts)

    def tiles_skipped(self):
        """Tiles inside the causal triangle that no row chose anything in
        (those above it are never walked)."""
        lay = self.layout
        nq = lay.padded // lay.rows
        i = jnp.arange(nq)[:, None]
        j = jnp.arange(lay.chunks)[None, :]
        inside = (j * lay.chunk <= i * lay.rows + lay.rows - 1) & (
            i * lay.rows < self.seq) & (j * lay.chunk < self.seq)
        empty = self.counts.reshape(-1, nq, lay.chunks) == 0
        return jnp.sum(empty & inside[None])

    def pairs_visited(self):
        """(row, key) pairs a pass of the attention kernels computes: the
        tiles some row chose in, whole."""
        lay = self.layout
        return jnp.sum(self.counts > 0) * (lay.rows * lay.chunk)


def _takes_kernel(use_pallas, compiles=True):
    if use_pallas is None:
        return get_accelerator().use_pallas_kernels() and compiles
    return bool(use_pallas)


def _pad_rows(x, padded, axis=1):
    extra = padded - x.shape[axis]
    if not extra:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, extra)
    return jnp.pad(x, pad)


def _by_chunks(x, rows):
    """``[B, Sp, ...]`` -> ``[Sp / rows, B, rows, ...]``: what a scan over
    chunks of rows reads."""
    b, sp = x.shape[:2]
    return jnp.moveaxis(x.reshape(b, sp // rows, rows, *x.shape[2:]), 1, 0)


def _from_chunks(x):
    """``[chunks, B, rows, ...]`` -> ``[B, Sp, ...]``."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape(x.shape[0], -1, *x.shape[3:])


def _einsum32(spec, a, b):
    """``einsum`` of compute-type operands summed in float32.  The CPU's
    dot has no bfloat16 x bfloat16 -> float32 for every layout; there the
    operands are widened first, which gives the same sums."""
    if jax.default_backend() == "tpu":
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32))


def indexer_dots(qi, ki):
    """A chunk of rows' ``q^I [B, R, H_I, D_I]`` against all keys ``[B, Sp,
    D_I]`` -> ``[B, H_I, R, Sp]`` float32, before the ReLU."""
    return _einsum32("brjd,bsd->bjrs", qi, ki)


def indexer_scores(qi, ki, w):
    """``I`` of a chunk of rows, ``[B, R, Sp]`` float32 (every column: the
    causal edge is the selection's)."""
    acts = jnp.maximum(indexer_dots(qi, ki), 0.0)
    return jnp.einsum("bjrs,brj->brs", acts, w.astype(jnp.float32))


# ------------------------------------------------------------------ select
def dsa_select(qi, ki, w, topk, use_pallas=None):
    """Each row's ``min(t + 1, topk)`` best-scored earlier keys ->
    ``Selection``.  No gradient flows through it."""
    from ...telemetry.trace import count_kernel_path

    qi, ki, w = (jax.lax.stop_gradient(t) for t in (qi, ki, w))
    B, S, H, D = qi.shape
    lay = pallas_dsa.sel_layout(S)
    qi, ki, w = (_pad_rows(t, lay.padded) for t in (qi, ki, w))
    w = w.astype(jnp.float32)
    kernel = _takes_kernel(use_pallas)
    count_kernel_path(pallas_dsa.SELECT, "pallas" if kernel else "plain")
    with jax.named_scope("dsa_select"):
        if kernel:
            words = pallas_dsa.select_call(
                jnp.swapaxes(qi, 1, 2), ki, w, S, topk, lay)
        else:
            words = _select_plain(qi, ki, w, S, topk, lay)
        chosen = pallas_dsa.unpack_rows(words, lay.chunks).reshape(
            B, lay.padded // lay.rows, lay.rows, lay.chunks, lay.chunk)
        counts = jnp.sum(chosen, axis=(2, 4), dtype=jnp.int32).reshape(-1)
        words, counts = checkpoint_name((words, counts), SELECTION_SAVED)
    return Selection(words, counts, lay, S)


def _select_plain(qi, ki, w, seq, topk, lay):
    """The kernel's code on arrays, a block of rows at a time."""
    rows, chunk = lay.rows, lay.chunk

    def block(args):
        i, q, wt = args
        scores = indexer_scores(q, ki, wt)          # [B, rows, Sp]

        def one(sc):
            def key(c):
                return pallas_dsa.masked_key(
                    sc[:, c * chunk:(c + 1) * chunk], i * rows, c * chunk, seq)
            return pallas_dsa.select_rows(key, lay.chunks, (rows, chunk),
                                          i * rows, seq, topk)

        return jax.vmap(one)(scores)

    n = lay.padded // rows
    words = jax.lax.map(block, (jnp.arange(n), _by_chunks(qi, rows),
                                _by_chunks(w, rows)))
    return _from_chunks(words)


# --------------------------------------------------------------- attention
def _lse_layout(lse, padded):
    """float32 ``[B, N, S]`` -> the kernels' ``[B * N, 1, Sp]``."""
    b, n, _ = lse.shape
    return _pad_rows(lse, padded, axis=2).reshape(b * n, 1, padded)


def _attend_plain(q, k, v, sel, scale):
    """``[B, N, S, S]`` scores: tests' sizes."""
    B, S, N, D = q.shape
    rep = N // k.shape[2]
    k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v))
    s = _einsum32("bqnd,bknd->bnqk", q * jnp.asarray(scale, q.dtype), k)
    s = jnp.where(sel.mask()[:, None], s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None]).astype(q.dtype)
    o = jnp.einsum("bnqk,bknd->bqnd", p, v)
    return o, _lse_layout(lse, sel.layout.padded)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _attend(q, k, v, words, counts, heads, scale, layout, plan):
    return _attend_fwd(q, k, v, words, counts, heads, scale, layout, plan)[0]


def _attend_fwd(q, k, v, words, counts, heads, scale, layout, plan):
    with jax.named_scope(pallas_dsa.ATTENTION):
        # pre-scaled once, as ``pallas_flash`` does; dq is post-scaled
        qp = q * jnp.asarray(scale, q.dtype)
        o, lse = pallas_dsa.fwd_call(qp, k, v, words, counts, heads, layout,
                                     plan)
        o, lse = (checkpoint_name(t, name)
                  for t, name in zip((o, lse), _FLASH_SAVED))
        return (o, lse), (qp, k, v, words, counts, o, lse)


def _attend_bwd(heads, scale, layout, plan, res, cot):
    qp, k, v, words, counts, o, lse = res
    do, _ = cot         # the statistics go to the indexer's loss, detached
    with jax.named_scope(pallas_dsa.ATTENTION):
        b, sp, hw = qp.shape
        delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32))
                        .reshape(b, sp, heads, hw // heads), axis=-1)
        delta = jnp.swapaxes(delta, 1, 2).reshape(b * heads, 1, sp)
        dq, dk, dv = pallas_dsa.bwd_call(qp, k, v, do, lse, delta, words,
                                         counts, heads, layout, plan)
        return dq * jnp.asarray(scale, dq.dtype), dk, dv, None, None


_attend.defvjp(_attend_fwd, _attend_bwd)


def dsa_attention(q, k, v, sel, scale=None, use_pallas=None):
    """``[B, S, N, D]`` q and ``[B, S, N_kv, D]`` k, v (rotated) under a
    ``Selection`` -> (``o [B, S, N, D]``, the rows' log-sum-exp over their
    chosen keys ``[B * N, 1, Sp]`` float32, which carries no gradient).
    Differentiable in q, k, v; the selection is a constant."""
    from ...telemetry.trace import count_kernel_path

    B, S, N, D = q.shape
    scale = float(D) ** -0.5 if scale is None else float(scale)
    kernel = _takes_kernel(use_pallas, pallas_dsa.compiles_for_tpu(D))
    rep = N // k.shape[2]
    count_kernel_path(pallas_dsa.ATTENTION,
                      f"grouped_{rep}" if kernel else "plain")
    # the kernels' own blocks, from the shapes alone; which walk they gave
    plan = pallas_dsa.attend_plan(sel.layout, D, rep, q.dtype)
    count_kernel_path(pallas_dsa.ATTENTION + "_walk",
                      plan.walk if kernel else "plain")
    with jax.named_scope("dsa_attend"):
        if not kernel:
            o, lse = _attend_plain(q, k, v, sel, scale)
            return o, jax.lax.stop_gradient(lse)
        sp = sel.layout.padded
        with jax.named_scope("attention_layout"):
            q, k, v = (_pad_rows(t.reshape(B, S, -1), sp) for t in (q, k, v))
        o, lse = _attend(q, k, v, sel.words, sel.counts, N, scale, sel.layout,
                         plan)
        with jax.named_scope("attention_layout"):
            o = o[:, :S].reshape(B, S, N, D)
        return o, jax.lax.stop_gradient(lse)


# --------------------------------------------------------- the indexer's loss
def _head_probs_plain(qp, k, lse, chosen, at, rows, heads):
    """``mean_h softmax_{S_t}`` of the ``rows`` rows from ``at`` -> ``[B,
    rows, Sp]`` float32; ``[B, N, rows, Sp]`` scores."""
    B, sp, hw = qp.shape
    d = hw // heads
    q = jax.lax.dynamic_slice_in_dim(qp, at, rows, 1).reshape(
        B, rows, heads, d)
    k = jnp.repeat(k.reshape(B, k.shape[1], -1, d),
                   heads // (k.shape[2] // d), 2)
    s = _einsum32("brnd,bsnd->bnrs", q, k)
    stat = jax.lax.dynamic_slice_in_dim(
        lse.reshape(B, heads, sp), at, rows, 2)
    p = jnp.where(chosen[:, None], jnp.exp(s - stat[..., None]), 0.0)
    return jnp.mean(p, axis=1)


#: the plain form of the loss's pass (the kernels end a block's walk at its
#: diagonal themselves) walks the rows in this many stretches, each against
#: the columns up to its own last row: what lies right of the diagonal is
#: chosen by no row, and a scan's chunks must be one shape, so a stretch's
#: chunks all see the stretch's columns (four: 62.5 % of the square)
LOSS_STRETCHES = 4


def _loss_pass_kernels(qi, ki, w, qp, k, lse, sel, heads, with_grads):
    """``_loss_pass`` where the kernels run: one scan over the chunks of
    ``layout.rows`` rows, ``dsa_head_probs`` then ``dsa_loss_grads`` (whose
    walk ends at the chunk's diagonal: no stretches)."""
    lay = sel.layout
    B, sp, H, D = qi.shape
    q_rows = jnp.swapaxes(qi, 1, 2)                     # [B, H, Sp, D]
    q_cols = jnp.swapaxes(q_rows, 2, 3)
    k_cols = jnp.swapaxes(ki.reshape(B, lay.chunks, lay.chunk, D), 2, 3)

    plan = pallas_dsa.head_probs_plan(lay, qp.shape[2] // k.shape[2])

    def chunk(carry, i):
        loss, dkt, dq, dw = carry
        pbar = pallas_dsa.head_probs_call(
            qp, k, lse, sel.words, sel.counts, i[None], lay.rows, heads, lay,
            plan)
        part, dq, dk, dw = pallas_dsa.loss_grads_call(
            q_rows, q_cols, ki, k_cols, w, pbar, sel.words, sel.counts,
            i[None], dq, dw, B * sel.seq, lay, with_grads)
        return (loss + jnp.sum(part[:, :, 0, 0]), dkt + dk, dq, dw), ()

    (loss, dkt, dq, dw), _ = jax.lax.scan(
        chunk, (jnp.float32(0.0), jnp.zeros(k_cols.shape, jnp.float32),
                jnp.zeros_like(q_rows), jnp.zeros_like(w)),
        jnp.arange(sp // lay.rows, dtype=jnp.int32))
    loss = loss / (B * sel.seq)
    if not with_grads:
        return loss, None
    dki = jnp.swapaxes(dkt, 2, 3).reshape(ki.shape).astype(ki.dtype)
    return loss, (jnp.swapaxes(dq, 1, 2), dki, dw)


def _loss_pass(qi, ki, w, qp, k, lse, sel, heads, kernel, with_grads):
    """One pass over the rows, a chunk at a time -> (the loss, and with
    ``with_grads`` its gradients to ``q^I, k^I, w``).  The plain form below
    is what the kernels are held to: its equations and its rounding points
    (``through`` to the compute type once, every sum float32) are theirs."""
    if kernel:
        return _loss_pass_kernels(qi, ki, w, qp, k, lse, sel, heads,
                                  with_grads)
    lay, seq = sel.layout, sel.seq
    B, sp = qi.shape[:2]
    rows = lay.rows
    total = B * seq
    mm = qi.dtype

    def chunks_against(cols):
        """A scan's step: a chunk of rows against the first ``cols``
        columns."""
        keys = ki[:, :cols]

        def chunk(carry, args):
            loss, dki = carry
            i, q_c, w_c, words = args
            chosen = pallas_dsa.unpack_rows(words, lay.chunks)[..., :cols]
            pbar = _head_probs_plain(qp, k[:, :cols], lse, chosen, i * rows,
                                     rows, heads)
            dots = indexer_dots(q_c, keys)              # [B, H, rows, cols]
            acts = jnp.maximum(dots, 0.0)
            scores = jnp.einsum("bjrs,brj->brs", acts, w_c)
            any_chosen = jnp.any(chosen, axis=-1, keepdims=True)
            masked = jnp.where(chosen, scores, -jnp.inf)
            stat = jnp.where(
                any_chosen, jax.nn.logsumexp(masked, axis=-1, keepdims=True),
                0.)
            logp = scores - stat
            seen = pbar > 0.0
            loss = loss + jnp.sum(jnp.where(
                seen, pbar * (jnp.log(jnp.where(seen, pbar, 1.0)) - logp),
                0.0))
            if not with_grads:
                return (loss, dki), ()
            dscores = (jnp.where(chosen, jnp.exp(logp), 0.0) - pbar) / total
            dw = jnp.einsum("brs,bjrs->brj", dscores, acts)
            through = (dscores[:, None] * jnp.swapaxes(w_c, 1, 2)[..., None]
                       * (dots > 0.0)).astype(mm)       # [B, H, rows, cols]
            dq = _einsum32("bjrs,bsd->brjd", through, keys)
            dki = dki + _einsum32("bjrs,brjd->bsd", through, q_c)
            return (loss, dki), (dq.astype(mm), dw)

        return chunk

    n = sp // rows
    stretches = LOSS_STRETCHES if n % LOSS_STRETCHES == 0 else 1
    per = n // stretches
    by_chunk = (jnp.arange(n, dtype=jnp.int32), _by_chunks(qi, rows),
                _by_chunks(w, rows), _by_chunks(sel.words, rows))
    loss, dki, per_chunk = jnp.float32(0.0), jnp.zeros(ki.shape,
                                                       jnp.float32), []
    for g in range(stretches):
        # whole chunks of columns up to the stretch's last row
        cols = min(sp, -(-(g + 1) * per * rows // lay.chunk) * lay.chunk)
        (loss, part), told = jax.lax.scan(
            chunks_against(cols),
            (loss, jnp.zeros((B, cols, ki.shape[2]), jnp.float32)),
            jax.tree_util.tree_map(lambda x: x[g * per:(g + 1) * per],
                                   by_chunk))
        dki = dki.at[:, :cols].add(part)
        per_chunk.append(told)
    loss = loss / total
    if not with_grads:
        return loss, None
    dq, dw = (_from_chunks(jnp.concatenate(t)) for t in zip(*per_chunk))
    return loss, (dq, dki.astype(ki.dtype), dw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _indexer_loss(qi, ki, w, qp, k, lse, words, counts, layout, seq, heads,
                  kernel):
    sel = Selection(words, counts, layout, seq)
    return _loss_pass(qi, ki, w, qp, k, lse, sel, heads, kernel, False)[0]


def _indexer_loss_fwd(qi, ki, w, qp, k, lse, words, counts, layout, seq,
                      heads, kernel):
    sel = Selection(words, counts, layout, seq)
    loss, grads = _loss_pass(qi, ki, w, qp, k, lse, sel, heads, kernel, True)
    # made once a step: a remat policy keeps them (``SAVED_BY_REMAT``), and
    # the recomputed layer's loss pass is dead code
    return loss, checkpoint_name(grads, GRADS_SAVED)


def _indexer_loss_bwd(layout, seq, heads, kernel, grads, g):
    dq, dk, dw = grads
    return (dq * g.astype(dq.dtype), dk * g.astype(dk.dtype), dw * g,
            None, None, None, None, None)


_indexer_loss.defvjp(_indexer_loss_fwd, _indexer_loss_bwd)


def dsa_indexer_loss(qi, ki, w, q, k, lse, sel, scale=None, use_pallas=None):
    """``mean_t KL(pbar_t || softmax_{S_t} I[t, .])`` -> a float32 scalar,
    differentiable in ``q^I, k^I, w`` alone: the main attention's q, k and
    its rows' log-sum-exp (``dsa_attention``'s) are held constant."""
    from ...telemetry.trace import count_kernel_path

    B, S, N, D = q.shape
    scale = float(D) ** -0.5 if scale is None else float(scale)
    kernel = _takes_kernel(use_pallas, pallas_dsa.compiles_for_tpu(D))
    for name in (pallas_dsa.HEAD_PROBS, pallas_dsa.LOSS_GRADS):
        count_kernel_path(name, "pallas" if kernel else "plain")
    sp = sel.layout.padded
    with jax.named_scope("dsa_indexer_loss"):
        q, k, lse = (jax.lax.stop_gradient(t) for t in (q, k, lse))
        qp = _pad_rows((q * jnp.asarray(scale, q.dtype)).reshape(B, S, -1), sp)
        k = _pad_rows(k.reshape(B, S, -1), sp)
        qi, ki, w = (_pad_rows(t, sp) for t in (qi, ki, w))
        return _indexer_loss(qi, ki, w.astype(jnp.float32), qp, k, lse,
                             sel.words, sel.counts, sel.layout, sel.seq, N,
                             kernel)

"""Learned sparse attention (``ops/attention/dsa.py`` over
``pallas_dsa.py``) at small sizes on the CPU, the kernels in interpret mode
and the plain forms alike: the selection is exact (``min(t + 1, topk)`` a
row, ties to the lower position, equal to a stable sort's); the attention
over the chosen and the indexer's loss, forward and backward, are a naive
``[B, N, S, S]`` computation's; the packed selection's counts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu.ops.attention import dsa, pallas_dsa

FORMS = pytest.mark.parametrize("use_pallas", [False, True],
                                ids=["plain", "kernels"])


def _operands(seq, seed=0, B=2, N=4, KV=2, D=16, HI=3, DI=8):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(keys[0], (B, seq, N, D))
    k = jax.random.normal(keys[1], (B, seq, KV, D))
    v = jax.random.normal(keys[2], (B, seq, KV, D))
    qi = jax.random.normal(keys[3], (B, seq, HI, DI))
    ki = jax.random.normal(keys[4], (B, seq, DI))
    w = jax.random.normal(keys[5], (B, seq, HI))
    return qi, ki, w, q, k, v


def _scores(qi, ki, w):
    return jnp.einsum("bjrs,brj->brs", jnp.maximum(
        jnp.einsum("brjd,bsd->bjrs", qi, ki), 0.0), w)


def _sorted_selection(scores, topk):
    """Each row's ``min(t + 1, topk)`` largest of ``s <= t`` by a stable
    sort: of equal scores the lower position first."""
    S = scores.shape[-1]
    causal = jnp.tril(jnp.ones((S, S), bool))
    order = jnp.argsort(jnp.where(causal, -scores, jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1)
    return (rank < jnp.minimum(jnp.arange(S) + 1, topk)[None, :, None]) \
        & causal


def _naive(qi, ki, w, q, k, v, topk):
    B, S, N, D = q.shape
    scores = _scores(qi, ki, w)
    chosen = _sorted_selection(scores, topk)
    kr, vr = (jnp.repeat(t, N // k.shape[2], axis=2) for t in (k, v))
    s = jnp.einsum("bqnd,bknd->bnqk", q, kr) * D ** -0.5
    p = jax.nn.softmax(jnp.where(chosen[:, None], s, -jnp.inf), -1)
    o = jnp.einsum("bnqk,bknd->bqnd", p, vr)
    pbar = jax.lax.stop_gradient(p.mean(1))
    log_pi = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), -1)
    seen = chosen & (pbar > 0)
    kl = jnp.sum(jnp.where(seen, pbar * (jnp.log(jnp.where(seen, pbar, 1.0))
                                         - jnp.where(seen, log_pi, 0.0)),
                           0.0)) / (B * S)
    return chosen, o, kl


def _program(qi, ki, w, q, k, v, topk, use_pallas):
    sel = dsa.dsa_select(qi, ki, w, topk, use_pallas=use_pallas)
    o, lse = dsa.dsa_attention(q, k, v, sel, use_pallas=use_pallas)
    kl = dsa.dsa_indexer_loss(qi, ki, w, q, k, lse, sel,
                              use_pallas=use_pallas)
    return sel, o, kl


@FORMS
@pytest.mark.parametrize("seq,topk", [(96, 16), (300, 40)],
                         ids=["one-chunk", "three-chunks"])
def test_the_selection_is_exact_and_a_stable_sorts(seq, topk, use_pallas):
    """Exactly ``min(t + 1, topk)`` a row, inside the causal triangle, the
    same set as a stable sort of the scores gives; planted ties (three keys
    alike, so three columns of every row score alike) go to the lower
    position."""
    qi, ki, w, *_ = _operands(seq, seed=seq)
    ki = ki.at[:, 5].set(ki[:, 3]).at[:, 20].set(ki[:, 3])
    sel = jax.jit(lambda *a: dsa.dsa_select(*a, topk, use_pallas=use_pallas))(
        qi, ki, w)
    chosen = np.asarray(sel.mask())
    want = np.asarray(_sorted_selection(_scores(qi, ki, w), topk))
    np.testing.assert_array_equal(chosen, want)
    np.testing.assert_array_equal(
        chosen.sum(-1), np.broadcast_to(
            np.minimum(np.arange(seq) + 1, topk), chosen.shape[:2]))
    assert not np.triu(chosen, 1).any()
    expected = 2 * int(np.minimum(np.arange(seq) + 1, topk).sum())
    assert int(sel.pairs_selected()) == expected
    # the tie: wherever a row chose the later of two equal keys it chose
    # the earlier one too
    assert (chosen[:, :, 3] >= chosen[:, :, 5]).all()
    assert (chosen[:, :, 5] >= chosen[:, :, 20]).all()
    tied = chosen[:, 21:, 3] != chosen[:, 21:, 20]
    assert tied.any(), "no row's cut fell between the tied keys"


def test_the_selection_counts_its_tiles():
    """A tile's count is the chosen pairs of its rows against its chunk;
    nothing above the diagonal; a pass visits the tiles some row chose in."""
    qi, ki, w, *_ = _operands(300, seed=2)
    sel = dsa.dsa_select(qi, ki, w, 40, use_pallas=False)
    lay = sel.layout
    assert (lay.chunk, lay.chunks, lay.padded, lay.rows) == (128, 3, 384, 128)
    counts = np.asarray(sel.counts).reshape(2, 3, 3)
    chosen = np.zeros((2, 384, 384), bool)
    chosen[:, :300, :300] = np.asarray(sel.mask())
    want = chosen.reshape(2, 3, 128, 3, 128).sum((2, 4))
    np.testing.assert_array_equal(counts, want)
    assert (np.triu(counts, 1) == 0).all()
    assert int(sel.pairs_visited()) == int((counts > 0).sum()) * 128 * 128
    assert int(sel.tiles_skipped()) == int((np.tril(counts) == 0).sum()
                                           - 2 * 3)
    assert pallas_dsa.sel_layout(16384) == (512, 32, 16384, 512)


@FORMS
@pytest.mark.parametrize("seq,topk", [(96, 16), (300, 40), (2048, 100)],
                         ids=["one-chunk", "three-chunks", "four-stretches"])
def test_attention_and_loss_forward_and_backward_are_the_naive(seq, topk,
                                                               use_pallas):
    """The attention over the chosen and the indexer's loss, and the
    gradient of a function of both with respect to all six operands, against
    a ``[B, N, S, S]`` computation differentiated by ``jax``: float32, so
    the tolerance is rounding's (sums in another order).  At 2,048 rows the
    loss's pass walks its four stretches of row chunks, each against the
    columns up to its own last row (``dsa.LOSS_STRETCHES``)."""
    args = _operands(seq, seed=seq + 1, B=2 if seq < 2048 else 1)
    if seq == 2048:
        lay = pallas_dsa.sel_layout(seq)
        assert (lay.padded // lay.rows) % dsa.LOSS_STRETCHES == 0

    def objective(args, fn):
        _, o, kl = fn(*args)
        return jnp.sum(o * jnp.cos(o)) + 3.0 * kl

    def program(*a):
        return _program(*a, topk, use_pallas)

    def naive(*a):
        return _naive(*a, topk)

    _, o, kl = jax.jit(program)(*args)
    _, want_o, want_kl = naive(*args)
    np.testing.assert_allclose(o, want_o, rtol=1e-5, atol=2e-6)
    np.testing.assert_allclose(kl, want_kl, rtol=1e-5)
    got = jax.jit(jax.grad(lambda a: objective(a, program)))(args)
    want = jax.grad(lambda a: objective(a, naive))(args)
    for name, a, b in zip("qi ki w q k v".split(), got, want):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5 * float(jnp.abs(b).max()),
            err_msg=name)


def test_the_loss_reaches_the_indexer_alone_and_the_output_it_not():
    """The indexer's loss sends nothing to q, k (held constant), and the
    attention's output nothing to the indexer's operands (the selection is
    a constant of the backward pass)."""
    args = _operands(96, seed=7)

    def parts(args):
        _, o, kl = _program(*args, 16, False)
        return jnp.sum(o * o), kl

    by_output = jax.grad(lambda a: parts(a)[0])(args)
    by_loss = jax.grad(lambda a: parts(a)[1])(args)
    for g in by_output[:3] + by_loss[3:]:
        assert not np.asarray(g).any()
    for g in by_output[3:] + by_loss[:3]:
        assert np.asarray(g).any()


def _loss_and_grads(qi, ki, w, q, k, lse, sel, use_pallas):
    """The indexer's loss with its gradients to ``q^I, k^I, w``."""
    return jax.jit(jax.value_and_grad(
        lambda qi, ki, w: dsa.dsa_indexer_loss(qi, ki, w, q, k, lse, sel,
                                               use_pallas=use_pallas),
        argnums=(0, 1, 2)))(qi, ki, w)


def test_the_loss_kernel_is_the_plain_form_in_bfloat16():
    """2,048 rows of two sequences in bfloat16: several of the kernel's row
    blocks and both batch rows' walks sum into ``dk^I``.  Both forms round
    where ``_loss_pass`` rounds, so what separates them is the order of the
    float32 sums and the casts at the end (the flash tests' bf16
    tolerance)."""
    args = [t.astype(jnp.bfloat16) for t in _operands(2048, seed=11)]
    qi, ki, w, q, k, v = args
    w = w.astype(jnp.float32)
    sel = dsa.dsa_select(qi, ki, w, 100, use_pallas=False)
    assert sel.layout.padded // pallas_dsa.loss_rows(sel.layout) > 4
    _, lse = dsa.dsa_attention(q, k, v, sel, use_pallas=False)
    got, want = (_loss_and_grads(qi, ki, w, q, k, lse, sel, use)
                 for use in (True, False))
    np.testing.assert_allclose(got[0], want[0], rtol=2e-2)
    for name, a, b in zip(("dq^I", "dk^I", "dw"), got[1], want[1]):
        assert a.dtype == b.dtype, name
        b = np.asarray(b, np.float32)
        np.testing.assert_allclose(np.asarray(a, np.float32), b, rtol=2e-2,
                                   atol=2e-2 * np.abs(b).max(), err_msg=name)


@FORMS
def test_padded_rows_and_unchosen_columns_get_exactly_zero(use_pallas):
    """300 rows pad to 384, and the first 40 rows have fewer than ``topk``
    earlier keys: the loss's pass gives the rows past the length and the
    columns no row chose exactly zero (no ``exp`` of a masked score leaks),
    and every chosen column something."""
    qi, ki, w, q, k, v = _operands(300, seed=13)
    sel = dsa.dsa_select(qi, ki, w, 40, use_pallas=False)
    _, lse = dsa.dsa_attention(q, k, v, sel, use_pallas=False)
    sp = sel.layout.padded
    qp = dsa._pad_rows((q * q.shape[-1] ** -0.5).reshape(2, 300, -1), sp)
    kp = dsa._pad_rows(k.reshape(2, 300, -1), sp)
    padded = [dsa._pad_rows(t, sp) for t in (qi, ki, w)]
    loss, (dq, dk, dw) = jax.jit(
        lambda *a: dsa._loss_pass(*a, sel, q.shape[2], use_pallas, True))(
            *padded, qp, kp, lse)
    assert np.isfinite(float(loss))
    for name, g in (("dq^I", dq), ("dk^I", dk), ("dw", dw)):
        g = np.asarray(g)
        assert g.shape[1] == sp and not g[:, 300:].any(), name
        assert np.isfinite(g).all(), name
    unchosen = ~np.asarray(sel.mask()).any(axis=1)          # [B, S] columns
    assert unchosen.any() and not unchosen[:, :40].any()
    assert not np.asarray(dk)[:, :300][unchosen].any()
    assert np.asarray(dk)[:, :300][~unchosen].any(axis=-1).all()
    # (row 0 chose its one key: both distributions are 1 there, no gradient)
    assert np.asarray(dq)[:, 1:300].any(axis=(2, 3)).mean() > 0.95


def _packed(mask, lay):
    """bool ``[B, Sp, Sp]`` -> a ``Selection``'s words and counts."""
    B, sp, _ = mask.shape
    by_chunk = mask.reshape(B, sp, lay.chunks, lay.chunk).astype(jnp.int32)
    words = jnp.sum(by_chunk << jnp.arange(lay.chunks)[:, None], axis=2)
    counts = mask.reshape(B, sp // lay.rows, lay.rows, lay.chunks,
                          lay.chunk).sum((2, 4), dtype=jnp.int32)
    return words.astype(jnp.int32), counts.reshape(-1)


def _planned(monkeypatch, **sizes):
    """The attention kernels' plan with ``sizes`` in place of what the
    shapes give (blocks of several row groups at a test's length)."""
    kept = pallas_dsa.attend_plan
    monkeypatch.setattr(pallas_dsa, "attend_plan",
                        lambda lay, d, rep, dtype: kept(lay, d, rep, dtype,
                                                        **sizes))


def _attention_grads(q, k, v, sel, use_pallas):
    """``o`` and the gradients of a function of it to q, k, v."""
    def objective(q, k, v):
        o, _ = dsa.dsa_attention(q, k, v, sel, use_pallas=use_pallas)
        return jnp.sum(o * jnp.cos(o)), o

    (_, o), grads = jax.jit(jax.value_and_grad(
        objective, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return (o,) + grads


@pytest.mark.parametrize("seq,heads,kv_heads,sizes,walk", [
    (1152, 8, 1, dict(block=384, cols=384), "resident"),
    (1152, 2, 2, dict(block=384, cols=384), "resident"),
    (1152, 4, 2, dict(block=384, span=384, cols=128), "span"),
    (1152, 2, 1, dict(block=128, cols=1152), "resident"),
    (384, 4, 2, {}, "resident"),
    (300, 4, 2, {}, "resident"),
], ids=["blocks-of-groups-rep8", "blocks-of-groups-rep1", "spans",
        "the-tiles-own-rows", "one-block", "padded"])
def test_the_kernels_walk_is_the_naive_attention(monkeypatch, seq, heads,
                                                 kv_heads, sizes, walk):
    """The three kernels where a grid program owns a block of several row
    groups and walks the tiles in its body: several blocks to the head
    (under a group of eight query heads, and with none), k and v a span at
    a time, one block to the head, a padded length; o, dq, dk and dv are a
    ``[B, N, S, S]`` computation's, differentiated by ``jax``."""
    from deeperspeed_tpu import telemetry

    _planned(monkeypatch, **sizes)
    qi, ki, w, q, k, v = _operands(seq, seed=seq + heads, B=1, N=heads,
                                   KV=kv_heads)
    topk = 40
    sel = dsa.dsa_select(qi, ki, w, topk, use_pallas=False)
    plan = pallas_dsa.attend_plan(sel.layout, q.shape[-1],
                                  heads // kv_heads, q.dtype)
    assert plan.walk == walk and plan.block % sel.layout.rows == 0
    assert (seq == 1152) == (plan.block < sel.layout.padded)
    assert plan.heads == min(4, heads // kv_heads)
    before = telemetry.kernel_paths().get("dsa_attention_walk", {}).get(
        walk, 0)
    got = _attention_grads(q, k, v, sel, True)
    assert telemetry.kernel_paths()["dsa_attention_walk"][walk] > before

    def naive(q, k, v):
        o = _naive(qi, ki, w, q, k, v, topk)[1]
        return jnp.sum(o * jnp.cos(o)), o

    (_, want_o), want = jax.value_and_grad(naive, argnums=(0, 1, 2),
                                           has_aux=True)(q, k, v)
    for name, a, b in zip("o dq dk dv".split(), got, (want_o,) + want):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5 * float(jnp.abs(b).max()),
            err_msg=name)


@pytest.mark.parametrize("seq,sizes,hole,skipped", [
    (384, {}, (slice(256, None), slice(0, 128)), 1),
    (1152, dict(block=384, cols=384), (slice(768, None), slice(0, 384)), 9),
    (1152, dict(block=384, cols=384), (slice(512, 640), slice(128, 256)), 1),
], ids=["a-tile-of-the-one-block", "a-whole-block", "a-tile-inside-a-block"])
def test_an_empty_tile_inside_the_triangle_is_walked_past(monkeypatch, seq,
                                                          sizes, hole,
                                                          skipped):
    """A selection made by hand in which some rows chose nothing in some
    chunks (tiles inside the triangle with a count of zero: one tile of the
    length's one block, every tile of a whole block of rows against a block
    of columns, one tile in the middle of a wide block): the kernels
    neither compute nor miss them; the attention and its gradients, and the
    loss and its gradients, are the plain forms'."""
    _planned(monkeypatch, **sizes)
    qi, ki, w, q, k, v = _operands(seq, seed=17)
    lay = pallas_dsa.sel_layout(seq)
    assert (lay.chunk, lay.chunks, lay.rows) == (128, seq // 128, 128)
    t = np.arange(seq)
    mask = (t[None, :] <= t[:, None]) & (t[None, :] % 3 != 1)
    mask[hole] = False
    mask = jnp.asarray(np.broadcast_to(mask, (2, seq, seq)))
    sel = dsa.Selection(*_packed(mask, lay), lay, seq)
    np.testing.assert_array_equal(sel.mask(), mask)
    assert int(sel.tiles_skipped()) == 2 * skipped
    got, want = (_attention_grads(q, k, v, sel, use) for use in (True, False))
    for name, a, b in zip("o dq dk dv".split(), got, want):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5 * float(jnp.abs(b).max()),
            err_msg=name)
    _, lse = dsa.dsa_attention(q, k, v, sel, use_pallas=False)
    got, want = (_loss_and_grads(qi, ki, w, q, k, lse, sel, use)
                 for use in (True, False))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for name, a, b in zip(("dq^I", "dk^I", "dw"), got[1], want[1]):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5 * float(jnp.abs(b).max()),
            err_msg=name)


def test_the_attentions_padded_rows_and_unchosen_columns_get_exactly_zero():
    """300 rows pad to 384, one block of three row groups: whatever the
    padded rows of ``do`` hold, the rows past the length get a dq of exactly
    zero and send nothing to dk and dv, and a column no row chose gets
    exactly zero of both (no ``exp`` of a masked score leaks); every chosen
    column gets something."""
    qi, ki, w, q, k, v = _operands(300, seed=13)
    sel = dsa.dsa_select(qi, ki, w, 40, use_pallas=False)
    lay = sel.layout
    B, S, N, D = q.shape
    plan = pallas_dsa.attend_plan(lay, D, 2, q.dtype)
    assert (plan.block, lay.rows, lay.padded) == (384, 128, 384)
    qp, kp, vp = (dsa._pad_rows(t.reshape(B, S, -1), lay.padded)
                  for t in (q, k, v))
    _, res = dsa._attend_fwd(qp, kp, vp, sel.words, sel.counts, N,
                             D ** -0.5, lay, plan)
    do = jax.random.normal(jax.random.PRNGKey(5), qp.shape)
    given = [dsa._attend_bwd(N, D ** -0.5, lay, plan, res, (cot, None))[:3]
             for cot in (do, do.at[:, S:].set(0.0))]
    for name, a, b in zip(("dq", "dk", "dv"), *given):
        a = np.asarray(a)
        assert np.isfinite(a).all(), name
        assert not a[:, S:].any(), name
        np.testing.assert_array_equal(a, b, err_msg=name)
    unchosen = ~np.asarray(sel.mask()).any(axis=1)          # [B, S] columns
    assert unchosen.any()
    for name, g in zip(("dk", "dv"), given[0][1:]):
        g = np.asarray(g).reshape(B, lay.padded, -1, D)[:, :S]
        assert not g[unchosen].any(), name
        assert g[~unchosen].any(axis=(-1, -2)).all(), name


def test_the_attentions_plan_is_its_own_and_a_function_of_shapes_alone():
    """The attention kernels' blocks come from the selection's layout, the
    head's width and the type, and move nothing else: ``SelLayout.rows``,
    the loss's block and the rows of a ``dsa_head_probs`` call are what they
    were, and ``dsa_attention`` takes no size."""
    import inspect

    lay, bf = pallas_dsa.sel_layout, jnp.bfloat16

    def plan(seq, d=128, rep=8, dtype=bf):
        return pallas_dsa.attend_plan(lay(seq), d, rep, dtype)

    assert lay(16384) == (512, 32, 16384, 512)
    assert pallas_dsa.loss_rows(lay(16384)) == 128
    # the cell: four heads of the group side by side on a block of a row
    # group each (2,048 rows together), the KV head's whole k and v resident
    assert plan(16384) == (512, 16384, 4096, 4, "resident")
    assert plan(16384) == plan(16384, dtype="bfloat16")
    assert plan(16384, rep=2) == (1024, 16384, 4096, 2, "resident")
    assert plan(16384, rep=1) == plan(16384, rep=3) == (
        2048, 16384, 4096, 1, "resident")
    # twice the length, or float32 at the cell's: a span of k and v
    assert plan(32768) == (1024, 16384, 4096, 2, "span")
    assert plan(16384, dtype=jnp.float32) == (512, 8192, 4096, 4, "span")
    # 128k: a tile is 512 x 4096: one head, a block of rows one group (its
    # words), a block of columns one chunk
    assert plan(131072) == (512, 16384, 4096, 1, "span")
    # one block to the head; the tile's own rows where nothing wider divides
    assert plan(300, d=16, rep=2, dtype=jnp.float32) == (
        384, 384, 384, 2, "resident")
    assert plan(1024) == (512, 1024, 1024, 4, "resident")
    assert lay(2176).rows == 128
    assert plan(2176) == (128, 2176, 2176, 4, "resident")
    assert list(inspect.signature(dsa.dsa_attention).parameters) == [
        "q", "k", "v", "sel", "scale", "use_pallas"]
    # ``dsa_head_probs`` is called a chunk of ``SelLayout.rows`` rows: a
    # grid program an output tile, the heads walked in its body (no grid
    # step a head), behind a jit of its own
    B, S, N, KV, D = 1, 16384, 32, 4, 128
    layout = lay(S)
    f32 = jnp.float32
    probs = pallas_dsa.head_probs_plan(layout, N // KV)
    jaxpr = jax.make_jaxpr(
        lambda *a: pallas_dsa.head_probs_call(*a, layout.rows, N, layout,
                                              probs))(
            jax.ShapeDtypeStruct((B, S, N * D), bf),
            jax.ShapeDtypeStruct((B, S, KV * D), bf),
            jax.ShapeDtypeStruct((B * N, 1, S), f32),
            jax.ShapeDtypeStruct((B, S, layout.chunk), jnp.int32),
            jax.ShapeDtypeStruct((B * 32 * 32,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32))
    jitted, = jaxpr.eqns
    assert jitted.params["name"] == "head_probs_call"
    call, = (e for e in jitted.params["jaxpr"].eqns
             if e.primitive.name == "pallas_call")
    assert tuple(call.params["grid_mapping"].grid) == (1, 1, 32)
    assert call.outvars[0].aval.shape == (B, 512, S)


def test_head_probs_plan_is_its_own_and_a_function_of_shapes_alone():
    """``dsa_head_probs``' one size, the heads side by side in a program's
    body, comes from the selection's tile and the query heads a KV head by
    the attention's rule (a program owns the tile: no other size);
    ``dsa_indexer_loss`` takes no size."""
    import inspect

    lay = pallas_dsa.sel_layout

    def plan(seq, rep=8, **over):
        return pallas_dsa.head_probs_plan(lay(seq), rep, **over)

    # the cell: four heads of a group's eight side by side, as the attention
    assert plan(16384) == (4,) == pallas_dsa.attend_plan(
        lay(16384), 128, 8, jnp.bfloat16)[3:4]
    assert plan(16384, rep=2) == plan(16384, rep=6) == (2,)
    assert plan(16384, rep=1) == plan(16384, rep=3) == (1,)
    # twice the length: a tile is 512 x 1,024, two heads' take the budget
    assert plan(32768) == (2,) and plan(131072) == (1,)
    assert plan(300, rep=2) == (2,) and plan(2176) == (4,)
    assert plan(16384, heads=8) == (8,)
    assert list(inspect.signature(dsa.dsa_indexer_loss).parameters) == [
        "qi", "ki", "w", "q", "k", "lse", "sel", "scale", "use_pallas"]


@pytest.mark.parametrize("seq,heads,kv_heads,blocks,over,side_by_side", [
    (1152, 8, 1, 1, None, 4),
    (1152, 4, 2, 1, None, 2),
    (1152, 2, 2, 3, None, 1),
    (1152, 8, 2, 1, 1, 1),
    (300, 4, 2, 1, None, 2),
], ids=["groups-of-8", "groups-of-2", "no-groups-rows-of-3",
        "a-head-at-a-time", "padded"])
def test_head_probs_kernel_is_the_plain_form(seq, heads, kv_heads, blocks,
                                             over, side_by_side):
    """``dsa_head_probs`` (interpret mode) against ``_head_probs_plain`` a
    call of every row block, the first (its one tile at the diagonal) to the
    last (tiles far under it): the heads of a KV group side by side and in
    the body's loop, a head at a time over two groups, a call of several row
    blocks, a padded length.  The selection is made by hand with a tile of
    count 0 inside the triangle, and a key no row chose scores so high that
    its ``exp`` overflows: the output is exactly zero wherever a row did not
    choose (above the diagonal, in the empty tile, past the length) and
    finite everywhere."""
    qi, ki, w, q, k, v = _operands(seq, seed=seq + heads, B=2, N=heads,
                                   KV=kv_heads)
    B, S, N, D = q.shape
    lay = pallas_dsa.sel_layout(seq)
    sp, rows = lay.padded, blocks * lay.rows
    t = np.arange(sp)
    mask = (t[None, :] <= t[:, None]) & (t[None, :] % 3 != 1) \
        & (t[:, None] < seq)
    if seq == 1152:
        mask[512:640, 128:256] = False
    mask = jnp.asarray(np.broadcast_to(mask, (B, sp, sp)))
    words, counts = _packed(mask, lay)
    sel = dsa.Selection(words, counts, lay, seq)
    assert int(sel.tiles_skipped()) == B * (seq == 1152)
    _, lse = dsa.dsa_attention(q, k, v, sel, use_pallas=False)
    k = k.at[:, 4].multiply(1e3)            # 4 % 3 == 1: chosen by no row
    qp = dsa._pad_rows((q * D ** -0.5).reshape(B, S, -1), sp)
    kp = dsa._pad_rows(k.reshape(B, S, -1), sp)
    plan = pallas_dsa.head_probs_plan(lay, N // kv_heads, over)
    assert plan == (side_by_side,)
    overflowed = False
    for at in range(0, sp // lay.rows, blocks):
        chosen = mask[:, at * lay.rows:at * lay.rows + rows]
        got = np.asarray(pallas_dsa.head_probs_call(
            qp, kp, lse, words, counts, jnp.array([at], jnp.int32), rows, N,
            lay, plan))
        assert got.shape == (B, rows, sp) and np.isfinite(got).all()
        assert not got[~np.asarray(chosen)].any()
        want = dsa._head_probs_plain(qp, kp, lse, chosen, at * lay.rows,
                                     rows, N)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
        s = jnp.einsum("brd,bd->br", qp[:, at * lay.rows:at * lay.rows + rows,
                                        :D], kp[:, 4, :D])
        overflowed |= bool(jnp.any(s - lse[0, 0, at * lay.rows] > 100.0))
    assert overflowed, "no exp of an unchosen pair overflowed"

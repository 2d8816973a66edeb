"""The two arguments of the one routed walk (``moe/dropless.py``):
``softmax_topk`` scoring and the gated expert body (``gated_silu`` on a fused
gate | up matrix), through the walk against a dense loop, values and
gradients; dropless when every token picks the same experts; and the two
forms of the walk (an expert's slots, or the sorted slots through the grouped
matmul, whose kernels run in interpret mode here at widths they tile), chosen
from the share of the pairs that even routing would choose and from the bytes
the walk by slots would pass over."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu.moe import dropless
from deeperspeed_tpu.ops import pallas_gmm
from deeperspeed_tpu.telemetry import kernel_paths

T, L, F, E, K = 96, 24, 20, 16, 3
# widths the grouped matmul's kernels tile (whole blocks of 128 lanes)
WIDE = (256, 128)


def _layer(seed=0, gated=True, widths=(L, F)):
    L, F = widths
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (T, L))
    logits = jax.random.normal(ks[1], (T, E))
    w_in = 0.3 * jax.random.normal(ks[2], (E, L, 2 * F if gated else F))
    w_out = 0.3 * jax.random.normal(ks[3], (E, F, L))
    if widths == WIDE:
        w_in, w_out = w_in / 3, w_out / 3
    return x, logits, w_in, w_out


def _dense(x, logits, w_in, w_out, first, held, normalize=True):
    """A dense loop over the held experts, written here: softmax over all,
    the K largest, renormalised; gated experts."""
    probs = jax.nn.softmax(logits, -1)
    top, chosen = jax.lax.top_k(probs, K)
    if normalize:
        top = top / jnp.sum(top, -1, keepdims=True)
    out = jnp.zeros_like(x)
    F = w_out.shape[1]
    for e in range(first, first + held):
        w = jnp.sum(jnp.where(chosen == e, top, 0.0), -1)
        h = x @ w_in[e]
        out = out + w[:, None] * ((jax.nn.silu(h[:, :F]) * h[:, F:])
                                  @ w_out[e])
    return out


def _walk(x, logits, w_in, w_out, first, held, normalize=True):
    return dropless.dropless_moe(
        x, logits, w_in[first:first + held], w_out[first:first + held], k=K,
        first_expert=first, experts_held=held, normalize=normalize,
        scoring=dropless.softmax_topk, activation=dropless.gated_silu)


def test_softmax_topk_by_hand():
    logits = jnp.log(jnp.asarray([[1.0, 2.0, 3.0, 4.0], [4.0, 1.0, 1.0, 2.0]]))
    chosen, weights = dropless.softmax_topk(logits, 2)
    np.testing.assert_array_equal(chosen, [[3, 2], [0, 3]])
    np.testing.assert_allclose(weights, [[4 / 7, 3 / 7], [4 / 6, 2 / 6]],
                               rtol=1e-6)
    _, raw = dropless.softmax_topk(logits, 2, normalize=False, scale=2.0)
    np.testing.assert_allclose(raw, [[0.8, 0.6], [1.0, 0.5]], rtol=1e-6)
    # a selection bias moves the choice, not the weight
    chosen, weights = dropless.softmax_topk(
        logits, 1, selection_bias=jnp.asarray([0.0, 1.0, 0.0, 0.0]),
        normalize=False)
    np.testing.assert_array_equal(chosen[:, 0], [1, 1])
    np.testing.assert_allclose(weights[:, 0], [0.2, 0.125], rtol=1e-6)


def test_gated_silu_splits_a_fused_matrix():
    h = jnp.asarray([[1.0, -2.0, 3.0, 0.5]])
    np.testing.assert_allclose(
        dropless.gated_silu(h),
        [[jax.nn.silu(1.0) * 3.0, jax.nn.silu(-2.0) * 0.5]], rtol=1e-6)


@pytest.mark.parametrize("first,held", [(0, 16), (4, 4), (12, 4), (5, 1)])
@pytest.mark.parametrize("normalize", [True, False])
def test_softmax_scoring_and_gated_experts_through_the_walk(first, held,
                                                            normalize):
    x, logits, w_in, w_out = _layer()
    out, counters, is_chosen = _walk(x, logits, w_in, w_out, first, held,
                                     normalize)
    np.testing.assert_allclose(
        out, _dense(x, logits, w_in, w_out, first, held, normalize),
        rtol=2e-5, atol=2e-5)
    assert int(counters["slots"]) == int(counters["done"]) == int(
        is_chosen.sum())
    if held == E:
        assert int(counters["slots"]) == T * K


def test_gradients_through_the_walk_are_the_dense_loops():
    x, logits, w_in, w_out = _layer(1)
    g = jax.random.normal(jax.random.PRNGKey(9), (T, L))

    def through(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a, 4, 8) * g),
                        argnums=(0, 1, 2, 3))(x, logits, w_in, w_out)

    got = through(lambda *a: _walk(*a)[0])
    want = through(_dense)
    for a, b, name in zip(got, want, ("x", "logits", "w_in", "w_out")):
        scale = max(float(jnp.max(jnp.abs(b))), 1e-8)
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, rtol=0, atol=3e-5,
                                   err_msg=name)
    # experts not held get no gradient
    assert float(jnp.max(jnp.abs(got[2][:4]))) == 0.0
    assert float(jnp.max(jnp.abs(got[2][12:]))) == 0.0


@pytest.mark.parametrize("form", [(False, 8), (False, 64), (True, 32), None])
def test_dropless_when_every_token_picks_the_same_experts(form, monkeypatch):
    """Every token the same scores: all T tokens pick the same K experts,
    and a share that holds them computes T x K slots (the worst case the
    shapes allow), none dropped, the fullest held expert ``held / K`` times
    the mean; by slots, and by the grouped form at widths it tiles."""
    grouped = bool(form and form[0])
    if form:
        monkeypatch.setattr(dropless, "walk_form", lambda *_: form)
    x, _, w_in, w_out = _layer(2, widths=WIDE if grouped else (L, F))
    logits = jnp.tile(jnp.arange(E, dtype=jnp.float32)[None], (T, 1))
    out, counters, is_chosen = _walk(x, logits, w_in, w_out, E - 4, 4)
    assert int(counters["slots"]) == int(counters["done"]) == T * K
    assert ("computed" in counters) == grouped
    np.testing.assert_array_equal(np.asarray(counters["counts"]),
                                  [0, T, T, T])
    told = dropless.load_counters([counters])
    assert float(told["moe_slots_dropped"]) == 0.0
    assert float(told["moe_load_max_over_mean"]) == pytest.approx(4 / 3)
    assert ("moe_rows_computed" in told) == grouped
    np.testing.assert_allclose(
        out, _dense(x, logits, w_in, w_out, E - 4, 4), rtol=2e-5, atol=2e-5)


_ROWS = dropless.ROWS_PER_GROUPED_CHUNK
# the three models' layers: (tokens, k, experts, widths, held)
HYBRID = (16384, 22, 512, (1024, 2688, 2688), 8)
MELLUM = (32768, 8, 64, (2304, 1792, 896), 16)
LAGUNA = (16384, 10, 256, (3072, 2048, 1024), 8)


@pytest.mark.parametrize("shape,form", [
    # the hybrid cell: top-22 of 512 chooses 4.3 % of the pairs and its
    # table is narrow: an expert's slots, 256 a chunk, as before, whatever
    # the widths and with the experts held told
    ((16384, 22, 512), (False, 256)),
    ((16384, 22, 512, (1024, 2688, 2688)), (False, 256)),
    (HYBRID, (False, 256)),
    # Mellum's: top-8 of 64 chooses an eighth: the sorted slots through the
    # grouped matmul, at its own widths and where none are given
    ((32768, 8, 64, (2304, 1792, 896)), (True, _ROWS)),
    (MELLUM, (True, _ROWS)),
    ((16384, 8, 64), (True, _ROWS)),
    ((8191, 8, 64), (True, _ROWS)),
    # Laguna's: top-10 of 256 chooses 3.9 %, but by slots every chunk would
    # pass a [16384, 3072] float32 table: grouped, one chunk a layer's slots;
    # not so where the share held is not told, or is one expert's
    (LAGUNA, (True, _ROWS)),
    (LAGUNA[:4], (False, 256)),
    (LAGUNA[:4] + (1,), (False, 256)),
    # no more rows a chunk than the slots there can be, in whole tiles
    ((80, 3, 16, WIDE + (128,)), (True, 256)),
    # a width the kernels cannot tile (the CPU rehearsal's 48) walks slots,
    # however many bytes that passes over
    ((32768, 8, 64, (2304, 96, 48)), (False, 256)),
    ((80, 3, 16, (24, 40, 20)), (False, 80)),
    ((16384, 10, 256, (3072, 2048, 1000), 8), (False, 256)),
    (MELLUM[:3] + ((2304, 96, 48), 16), (False, 256)),
    # from a twentieth of the pairs chosen on (the forms cross lower, at
    # either model's shapes; the threshold is held there)
    ((32768, 4, 64), (True, _ROWS)),
    ((80, 1, 20), (True, 128)),
    ((80, 1, 21), (False, 80)),
    # ... or from ``GROUPED_FROM_TABLE_BYTES`` on: Laguna's layer at the
    # hybrid's width passes the hybrid's bytes and walks by slots; the
    # hybrid's at Laguna's width walks grouped
    ((16384, 10, 256, (1024, 2048, 1024), 8), (False, 256)),
    ((16384, 22, 512, (3072, 2688, 2688), 8), (True, _ROWS)),
])
def test_the_walks_form_follows_the_share_even_routing_chooses(shape, form):
    assert dropless.walk_form(*shape) == form


@pytest.mark.parametrize("shape,gigabytes", [
    (HYBRID, 1.61), (LAGUNA, 4.83), (MELLUM, 77.3)])
def test_the_bytes_the_walk_by_slots_would_pass_over(shape, gigabytes):
    """Held x ceil(an expert's slots by even routing / 256) chunks, each a
    pass over the float32 ``[tokens, width]`` table: 24 x 67.1 MB, 24 x
    201.3 MB, 256 x 302.0 MB; the constant lies between the first two."""
    tokens, k, experts, widths, held = shape
    got = dropless.slots_walk_bytes(tokens, k, experts, widths[0], held)
    assert got / 1e9 == pytest.approx(gigabytes, rel=2e-3)
    assert (got >= dropless.GROUPED_FROM_TABLE_BYTES) == (shape != HYBRID)
    assert 1.61e9 < dropless.GROUPED_FROM_TABLE_BYTES < 4.83e9


def test_two_forms_and_no_third():
    assert not hasattr(dropless, "_Blocks")
    assert not hasattr(dropless, "ROWS_PER_BLOCK")


def _through(x, held_w, is_chosen, w_in, w_out, g, rows, grouped):
    """Outputs, counters and all four gradients of one form of the walk."""
    def loss(x, held_w, w_in, w_out):
        out, counters = dropless.routed_experts(
            x, held_w, is_chosen, w_in, w_out, dropless.gated_silu, rows,
            grouped, K)
        return jnp.sum(out * g), (out, counters)
    (_, (out, counters)), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3), has_aux=True)(x, held_w, w_in, w_out)
    return out, counters, grads


def _same(got, want, is_chosen, names=("x", "held_w", "w_in", "w_out")):
    out_g, counted_g, grads_g = got
    out_s, counted_s, grads_s = want
    np.testing.assert_allclose(out_g, out_s, rtol=2e-5, atol=2e-5)
    for name in ("slots", "done", "counts"):
        np.testing.assert_array_equal(counted_g[name], counted_s[name])
    for a, b, name in zip(grads_g, grads_s, names):
        if name == "held_w":
            # a pair nobody chose has no slot: its weight's gradient is
            # exactly zero, in both forms
            assert float(jnp.max(jnp.abs(a[~is_chosen]))) == 0.0
            assert float(jnp.max(jnp.abs(b[~is_chosen]))) == 0.0
        scale = max(float(jnp.max(jnp.abs(b))), 1e-8)
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, rtol=0, atol=3e-5,
                                   err_msg=name)


@pytest.mark.parametrize("rows", [16, 32, 96])
def test_blocks_and_slots_are_the_same_sums(rows):
    """The two forms of the walk (the name is from when the second was by
    blocks of tokens; it is the grouped matmul over the sorted slots):
    outputs, counters and all four gradients agree, for a share and for all
    the experts, with counts that are no multiples of a tile."""
    x, logits, w_in, w_out = _layer(3, widths=WIDE)
    g = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    chosen, weights = dropless.softmax_topk(logits, K)
    for first, held in ((4, 8), (0, E)):
        held_w, is_chosen = dropless.held_weights(chosen, weights, first,
                                                  held)
        assert any(int(n) % dropless._tile(rows) for n in is_chosen.sum(0))
        args = (x, held_w, is_chosen, w_in[first:first + held],
                w_out[first:first + held], g, rows)
        _same(_through(*args, True), _through(*args, False), is_chosen)


def test_lagunas_layer_is_the_same_sums_in_the_form_it_now_takes(monkeypatch):
    """A tiny layer of Laguna's kind (a scaled softmax top-10 of 256, 8 gated
    experts held, widths the kernels tile): even routing chooses 3.9 % of
    the pairs, so it is the bytes the walk by slots would pass over that
    send it to the grouped form; outputs, counters and all four gradients
    agree with the walk by slots, which it took before."""
    tokens, width, inner = 128, 256, 128
    experts, k, first, held = 256, 10, 40, 8
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    x = jax.random.normal(ks[0], (tokens, width))
    # the held experts a little likelier than the rest: a few hundred slots
    logits = jax.random.normal(ks[1], (tokens, experts)).at[
        :, first:first + held].add(2.0)
    w_in = 0.1 * jax.random.normal(ks[2], (held, width, 2 * inner))
    w_out = 0.1 * jax.random.normal(ks[3], (held, inner, width))
    g = jax.random.normal(ks[4], (tokens, width))
    shape = (tokens, k, experts, (width, 2 * inner, inner), held)
    passed = dropless.slots_walk_bytes(tokens, k, experts, width, held)

    def through(from_bytes):
        monkeypatch.setattr(dropless, "GROUPED_FROM_TABLE_BYTES", from_bytes)
        before = collections.Counter(kernel_paths().get("grouped_matmul"))

        def loss(x, logits, w_in, w_out):
            out, counters, is_chosen = dropless.dropless_moe(
                x, logits, w_in, w_out, k=k, first_expert=first,
                experts_held=held, scale=2.5, scoring=dropless.softmax_topk,
                activation=dropless.gated_silu)
            return jnp.sum(out * g), (out, counters, is_chosen)
        (_, (out, counters, is_chosen)), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True)(x, logits, w_in, w_out)
        return (out, counters, grads), is_chosen, dict(
            collections.Counter(kernel_paths()["grouped_matmul"]) - before)

    by_slots, chosen, path_s = through(float("inf"))
    assert dropless.walk_form(*shape) == (False, tokens)
    grouped, _, path_g = through(passed)
    assert dropless.walk_form(*shape) == (True, 1280)
    assert (path_s, path_g) == ({"slots": 1}, {"pallas": 1})
    counted = grouped[1]
    assert int(counted["slots"]) == int(counted["done"]) == int(
        chosen.sum()) > 200
    assert int(counted["computed"]) >= int(counted["slots"])
    _same(grouped, by_slots, chosen, ("x", "logits", "w_in", "w_out"))


@pytest.mark.parametrize("form", ["slots", "grouped"])
def test_done_is_what_the_walks_chunks_counted(form, monkeypatch, request):
    """``done`` is added up chunk by chunk inside the walk, in both forms:
    a walk cut one chunk short reports the slots it left out, so
    ``moe_slots_dropped`` = 0 says that every chunk ran."""
    x, logits, w_in, w_out = _layer(4, widths=WIDE)
    chosen, weights = dropless.softmax_topk(logits, K)
    held_w, is_chosen = dropless.held_weights(chosen, weights, 4, 8)
    grouped = form == "grouped"

    def counters():
        return dropless.routed_experts(x, held_w, is_chosen, w_in[4:12],
                                       w_out[4:12], dropless.gated_silu, 32,
                                       grouped, K)[1]

    whole = counters()
    assert int(whole["slots"]) == int(whole["done"]) > 0
    if grouped:
        chunks = dropless._grouped_chunks
        monkeypatch.setattr(dropless, "_grouped_chunks",
                            lambda *a: chunks(*a) - 1)
        # the grouped walk is jitted: neither the whole walk's trace may
        # serve the short one, nor the short one's a later test
        jax.clear_caches()
        request.addfinalizer(jax.clear_caches)
    else:
        chunks = dropless._Slots.chunks
        monkeypatch.setattr(dropless._Slots, "chunks", staticmethod(
            lambda *a: chunks(*a) - 1))
    short = counters()
    assert int(short["slots"]) == int(whole["slots"])
    # the last chunk is the last sorted slots: the last held expert's last
    # tokens by slots, the last of them all by the grouped form
    last = int(whole["slots"]) if grouped else int(whole["counts"][-1])
    assert int(short["slots"]) - int(short["done"]) == (last - 1) % 32 + 1


@pytest.mark.parametrize("per_token", [3, 8])
def test_the_grouped_plan_counts_the_tokens_places_by_load(per_token):
    """The plan's one table by load: a token's place by falling number of
    slots is counted (tokens of one number keep their own order: a stable
    sort's places, without the sort), its sorted positions are brought into
    that order, and a token's positions are its chosen pairs' places among
    the sorted pairs."""
    _, logits, _, _ = _layer(7)
    chosen, weights = dropless.softmax_topk(logits, K)
    _, is_chosen = dropless.held_weights(chosen, weights, 4, 8)
    plan = dropless._grouped_plan(is_chosen, 32, per_token)
    slots = np.asarray(is_chosen).sum(1)
    order = np.argsort(-slots, kind="stable")
    np.testing.assert_array_equal(np.asarray(plan.rank)[order],
                                  np.arange(T))
    np.testing.assert_array_equal(plan.by_load, np.asarray(plan.pos)[order])
    # slot_plan's pairs are ``expert * T + token``, sorted
    pairs = np.asarray(plan.pairs)
    for t in (0, 17, T - 1):
        mine = sorted(np.flatnonzero(pairs % T == t)[:slots[t]])
        np.testing.assert_array_equal(
            np.asarray(plan.pos)[t, :slots[t]],
            [p for p in mine if pairs[p] < 8 * T])
    assert int(jnp.sum(plan.counts)) == slots.sum()


@pytest.mark.parametrize("case", ["nobody", "everybody"])
def test_the_grouped_form_when_no_token_or_every_token_picks_an_expert(case):
    """No held expert chosen by any token: no chunk, no kernel call, zeros
    and zero gradients.  One held expert chosen by every token and no
    other: one group, the others' matrices get no gradient."""
    x, _, w_in, w_out = _layer(6, widths=WIDE)
    g = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    is_chosen = jnp.zeros((T, 4), bool)
    if case == "everybody":
        is_chosen = is_chosen.at[:, 2].set(True)
    held_w = jnp.where(is_chosen, 0.25 + jax.random.uniform(
        jax.random.PRNGKey(8), (T, 4)), 0.0)
    args = (x, held_w, is_chosen, w_in[:4], w_out[:4], g, 32)
    got = _through(*args, True)
    _same(got, _through(*args, False), is_chosen)
    out, counters, grads = got
    if case == "nobody":
        assert int(counters["slots"]) == int(counters["computed"]) == 0
        assert float(jnp.max(jnp.abs(out))) == 0.0
        assert all(float(jnp.max(jnp.abs(d))) == 0.0 for d in grads)
    else:
        assert int(counters["slots"]) == int(counters["computed"]) == T
        assert float(jnp.max(jnp.abs(grads[2][jnp.array([0, 1, 3])]))) == 0.0
        assert float(jnp.max(jnp.abs(grads[2][2]))) > 0.0


def _rows_the_kernels_multiply(counts, rows, tile):
    """The padding's arithmetic: in every chunk of ``rows`` sorted slots a
    held expert with rows there is visited once for each tile its rows
    touch."""
    ends = np.cumsum(counts)
    visits = 0
    for lo in range(0, int(ends[-1]), rows):
        for start, end in zip(ends - counts, ends):
            a, b = max(start, lo) - lo, min(end, lo + rows) - lo
            if b > a:
                visits += (b - 1) // tile - a // tile + 1
    return visits * tile


def test_rows_computed_over_slots_held_is_the_paddings_arithmetic(
        monkeypatch):
    """``moe_rows_computed`` counts whole tiles, a straddled tile once an
    expert: by the arithmetic above at an uneven load, and within 15 % of
    the slots at an even one where an expert's slots are many tiles."""
    monkeypatch.setattr(pallas_gmm, "TILE_ROWS", 16)
    tokens, held = 1024, 4
    x = jax.random.normal(jax.random.PRNGKey(0), (tokens, WIDE[0]))
    _, _, w_in, w_out = _layer(5, widths=WIDE)
    rng = np.random.default_rng(0)
    for share, most in (((0.5,) * held, 1.15), ((0.03, 0.6, 0.0, 0.21), 2.0)):
        chosen = np.stack([rng.random(tokens) < p for p in share], axis=1)
        is_chosen = jnp.asarray(chosen)
        held_w = jnp.where(is_chosen, 0.5, 0.0)
        out, counters = dropless.routed_experts(
            x, held_w, is_chosen, w_in[:held], w_out[:held],
            dropless.gated_silu, 256, True)
        told = dropless.load_counters([counters])
        assert float(told["moe_slots_dropped"]) == 0.0
        assert float(told["moe_rows_computed"]) == _rows_the_kernels_multiply(
            chosen.sum(0), 256, 16)
        assert 1.0 <= float(told["moe_rows_computed"]) / float(
            told["moe_slots_held"]) <= most
        want, _ = dropless.routed_experts(
            x, held_w, is_chosen, w_in[:held], w_out[:held],
            dropless.gated_silu, 256)
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)

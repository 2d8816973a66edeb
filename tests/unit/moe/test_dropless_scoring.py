"""The two arguments of the one routed walk (``moe/dropless.py``):
``softmax_topk`` scoring and the gated expert body (``gated_silu`` on a fused
gate | up matrix), through the walk against a dense loop, values and
gradients; dropless when every token picks the same experts; and the chunk
size chosen from what even routing would give an expert."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu.moe import dropless

T, L, F, E, K = 96, 24, 20, 16, 3


def _layer(seed=0, gated=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (T, L))
    logits = jax.random.normal(ks[1], (T, E))
    w_in = 0.3 * jax.random.normal(ks[2], (E, L, 2 * F if gated else F))
    w_out = 0.3 * jax.random.normal(ks[3], (E, F, L))
    return x, logits, w_in, w_out


def _dense(x, logits, w_in, w_out, first, held, normalize=True):
    """A dense loop over the held experts, written here: softmax over all,
    the K largest, renormalised; gated experts."""
    probs = jax.nn.softmax(logits, -1)
    top, chosen = jax.lax.top_k(probs, K)
    if normalize:
        top = top / jnp.sum(top, -1, keepdims=True)
    out = jnp.zeros_like(x)
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
    and a share that holds them computes T x K slots, none dropped, the
    fullest held expert ``held / K`` times the mean; by slots and by
    blocks."""
    if form:
        monkeypatch.setattr(dropless, "walk_form", lambda *_: form)
    x, _, w_in, w_out = _layer(2)
    logits = jnp.tile(jnp.arange(E, dtype=jnp.float32)[None], (T, 1))
    out, counters, is_chosen = _walk(x, logits, w_in, w_out, E - 4, 4)
    assert int(counters["slots"]) == int(counters["done"]) == T * K
    np.testing.assert_array_equal(np.asarray(counters["counts"]),
                                  [0, T, T, T])
    told = dropless.load_counters([counters])
    assert float(told["moe_slots_dropped"]) == 0.0
    assert float(told["moe_load_max_over_mean"]) == pytest.approx(4 / 3)
    np.testing.assert_allclose(
        out, _dense(x, logits, w_in, w_out, E - 4, 4), rtol=2e-5, atol=2e-5)


def test_the_walks_form_follows_the_share_even_routing_chooses():
    # the hybrid cell: top-22 of 512 chooses 4.3 % of the pairs: an expert's
    # slots, 256 a chunk, as before
    assert dropless.walk_form(16384, 22, 512) == (False, 256)
    # Mellum's: top-8 of 64 chooses an eighth: blocks of 2048 tokens
    assert dropless.walk_form(16384, 8, 64) == (True, 2048)
    assert dropless.walk_form(32768, 8, 64) == (True, 2048)
    assert dropless.walk_form(3072, 8, 64) == (True, 1024)
    # blocks divide the tokens; a few tokens are one block
    assert dropless.walk_form(1536, 8, 64) == (True, 512)
    assert dropless.walk_form(80, 3, 16) == (True, 80)
    # tokens no block divides walk slots
    assert dropless.walk_form(8191, 8, 64) == (False, 256)
    assert dropless.walk_form(80, 1, 16) == (False, 80)


@pytest.mark.parametrize("rows", [16, 32, 96])
def test_blocks_and_slots_are_the_same_sums(rows):
    """The two chunk forms of the one walk: outputs, counters and all four
    gradients agree, for a share and for all the experts."""
    x, logits, w_in, w_out = _layer(3)
    g = jax.random.normal(jax.random.PRNGKey(5), (T, L))
    chosen, weights = dropless.softmax_topk(logits, K)
    held_w, is_chosen = dropless.held_weights(chosen, weights, 4, 8)

    def run(blocks):
        def loss(x, held_w, w_in, w_out):
            out, counters = dropless.routed_experts(
                x, held_w, is_chosen, w_in[4:12], w_out[4:12],
                dropless.gated_silu, rows, blocks)
            return jnp.sum(out * g), (out, counters)
        (_, (out, counters)), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True)(x, held_w, w_in, w_out)
        return out, counters, grads

    out_b, counted_b, grads_b = run(True)
    out_s, counted_s, grads_s = run(False)
    np.testing.assert_allclose(out_b, out_s, rtol=2e-5, atol=2e-5)
    for name in ("slots", "done", "counts"):
        np.testing.assert_array_equal(counted_b[name], counted_s[name])
    for a, b, name in zip(grads_b, grads_s, ("x", "held_w", "w_in", "w_out")):
        if name == "held_w":
            # by blocks a pair nobody chose has the derivative its zero
            # weight would have; ``held_weights`` drops it (a ``where``)
            assert float(jnp.max(jnp.abs(b[~is_chosen]))) == 0.0
            a = jnp.where(is_chosen, a, 0.0)
        scale = max(float(jnp.max(jnp.abs(b))), 1e-8)
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, rtol=0, atol=3e-5,
                                   err_msg=name)


@pytest.mark.parametrize("form", [dropless._Slots, dropless._Blocks])
def test_done_is_what_the_walks_chunks_counted(form, monkeypatch):
    """``done`` is added up chunk by chunk inside the walk, by slots and by
    blocks: a walk cut one chunk short reports the slots it left out, so
    ``moe_slots_dropped`` = 0 says that every chunk ran."""
    x, logits, w_in, w_out = _layer(4)
    chosen, weights = dropless.softmax_topk(logits, K)
    held_w, is_chosen = dropless.held_weights(chosen, weights, 4, 8)
    blocks = form is dropless._Blocks

    def counters():
        return dropless.routed_experts(x, held_w, is_chosen, w_in[4:12],
                                       w_out[4:12], dropless.gated_silu, 32,
                                       blocks)[1]

    whole = counters()
    assert int(whole["slots"]) == int(whole["done"]) > 0
    chunks = form.chunks
    monkeypatch.setattr(form, "chunks", staticmethod(
        lambda *a: chunks(*a) - 1))
    short = counters()
    assert int(short["slots"]) == int(whole["slots"])
    # the last chunk is the last held expert's last tokens
    left_out = (int(jnp.sum(is_chosen[-32:, -1])) if blocks
                else (int(whole["counts"][-1]) - 1) % 32 + 1)
    assert int(short["slots"]) - int(short["done"]) == left_out > 0

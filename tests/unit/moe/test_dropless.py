"""The dropless top-k path over a share of the experts, on the CPU: against
a dense loop over the experts (values and gradients), every share of a
layer added up, no slot dropped when every token is the same, the scoring's
arithmetic, and the counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu.moe import dropless

T, L, F, E, K = 64, 16, 24, 32, 6


def _layer(seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (T, L)),
            jax.random.normal(ks[1], (T, E)),
            0.3 * jax.random.normal(ks[2], (E, L, F)),
            0.3 * jax.random.normal(ks[3], (E, F, L)))


def _dense(x, logits, w_in, w_out, first, held, scale=5.0,
           activation=dropless.relu2):
    """Every expert of the range on every token, a dense mask an expert."""
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores, K)
    weights = jnp.take_along_axis(scores, chosen, -1)
    weights = scale * weights / weights.sum(-1, keepdims=True)
    out = jnp.zeros((T, L))
    for e in range(first, first + held):
        w = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
        out = out + w[:, None] * (activation(x @ w_in[e]) @ w_out[e])
    return out


def _share(x, logits, w_in, w_out, first, held, rows=32):
    """A share's part, with the walk's chunk cut to ``rows`` slots so that
    these small layers take several chunks an expert."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(dropless, "ROWS_PER_CHUNK", rows)
        return dropless.dropless_moe(
            x, logits, w_in[first:first + held], w_out[first:first + held],
            k=K, first_expert=first, experts_held=held, scale=5.0)


@pytest.mark.parametrize("first,held", [(0, 4), (12, 4), (24, 8), (0, 32)])
def test_a_share_is_the_dense_sum_over_its_experts(first, held):
    ops = _layer(first + held)
    out, counters, chosen = jax.jit(
        lambda *a: _share(*a, first, held))(*ops)
    np.testing.assert_allclose(out, _dense(*ops, first, held), rtol=1e-4,
                               atol=1e-4)
    assert int(counters["slots"]) == int(counters["done"]) == int(
        chosen.sum()) == int(counters["counts"].sum())
    assert chosen.shape == (T, held)


@pytest.mark.parametrize("rows", [8, 32, 1000])
def test_the_chunk_size_changes_no_number(rows):
    ops = _layer(3)
    want = _dense(*ops, 8, 8)
    got, counters, _ = _share(*ops, 8, 8, rows=rows)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert int(counters["slots"]) == int(counters["done"])


def test_gradients_are_the_dense_sums():
    ops = _layer(5)
    every = (0, 1, 2, 3)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(_share(*a, 4, 8)[0])),
                           argnums=every))(*ops)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(_dense(*a, 4, 8))),
                    argnums=every)(*ops)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-4)
    # the experts outside the share get no gradient
    assert float(jnp.abs(got[2][:4]).max()) == 0.0
    assert float(jnp.abs(got[2][12:]).max()) == 0.0


def test_the_shares_add_up_to_the_whole_layer():
    ops = _layer(7)
    whole = _dense(*ops, 0, E)
    parts = sum(_share(*ops, first, 4)[0] for first in range(0, E, 4))
    np.testing.assert_allclose(parts, whole, rtol=1e-4, atol=1e-4)
    slots = sum(int(_share(*ops, first, 4)[1]["slots"])
                for first in range(0, E, 4))
    assert slots == T * K          # every choice of every token, once


@pytest.mark.parametrize("rows", [16, 64])
def test_dropless_when_every_token_is_the_same(rows):
    """Every token the same: all of them choose the same K experts, so a
    share that holds some of those gets ``tokens`` slots for each, the worst
    case the shapes allow, and computes every one."""
    x, logits, w_in, w_out = _layer(9)
    x, logits = jnp.tile(x[:1], (T, 1)), jnp.tile(logits[:1], (T, 1))
    favourites = np.asarray(jax.lax.top_k(logits[0], K)[1])
    first = int(favourites[0]) // 4 * 4
    mine = [e for e in favourites if first <= e < first + 4]
    out, counters, _ = _share(x, logits, w_in, w_out, first, 4, rows=rows)
    assert int(counters["slots"]) == int(counters["done"]) == T * len(mine)
    assert sorted(np.asarray(counters["counts"]).tolist())[-len(mine):] == [
        T] * len(mine)
    np.testing.assert_allclose(out, _dense(x, logits, w_in, w_out, first, 4),
                               rtol=1e-4, atol=1e-4)
    told = dropless.load_counters([counters])
    assert float(told["moe_slots_dropped"]) == 0.0
    assert float(told["moe_load_max_over_mean"]) == pytest.approx(
        4 / len(mine))


def test_a_share_nobody_chose_gives_nothing_and_walks_no_chunk():
    """No token chooses the experts held: no chunk of the walk holds a slot,
    the result and every gradient are zero, and nothing is dropped."""
    x, logits, w_in, w_out = _layer(13)
    logits = logits.at[:, 8:12].set(-30.0)

    def part(*a):
        out, counters, _ = _share(*a, 8, 4)
        return jnp.sum(jnp.sin(out)), (out, counters)

    (_, (out, counters)), grads = jax.value_and_grad(
        part, argnums=(0, 1, 2, 3), has_aux=True)(x, logits, w_in, w_out)
    assert int(counters["slots"]) == int(counters["done"]) == 0
    assert float(jnp.abs(out).max()) == 0.0
    assert all(float(jnp.abs(g).max()) == 0.0 for g in grads)


def test_sigmoid_topk_by_hand():
    logits = jnp.asarray([[0.0, 2.0, -1.0, 1.0]])
    chosen, weights = dropless.sigmoid_topk(logits, 2, scale=5.0)
    s = 1 / (1 + np.exp(-np.asarray([2.0, 1.0])))
    assert chosen.tolist() == [[1, 3]]
    np.testing.assert_allclose(weights[0], 5.0 * s / s.sum(), rtol=1e-6)
    # the bias moves the choice and not the weights
    chosen, weights = dropless.sigmoid_topk(
        logits, 2, selection_bias=jnp.asarray([1.0, 0.0, 0.0, 0.0]),
        normalize=False)
    assert chosen.tolist() == [[0, 1]]
    np.testing.assert_allclose(weights[0], [0.5, s[0]], rtol=1e-6)


def test_held_weights_and_the_slot_plan():
    chosen = jnp.asarray([[5, 2], [9, 4], [4, 5]])
    weights = jnp.asarray([[0.6, 0.4], [0.7, 0.3], [0.2, 0.8]])
    held_w, is_chosen = dropless.held_weights(chosen, weights, 4, 2)
    np.testing.assert_allclose(held_w, [[0, 0.6], [0.3, 0], [0.2, 0.8]])
    assert is_chosen.tolist() == [[False, True], [True, False], [True, True]]
    order, counts = dropless.slot_plan(is_chosen)
    assert counts.tolist() == [2, 2]
    # expert 4's tokens 1 and 2, then expert 5's tokens 0 and 2, as
    # ``expert * 3 + token``; 6 where there is no slot
    assert order.tolist() == [1, 2, 3, 5, 6, 6]


@pytest.mark.parametrize("rows", [8, 24])
def test_rows_that_are_no_slots_add_nothing(rows):
    """A chunk is ``rows`` long whatever its expert has left, and the rows
    past its slots read zeros and are dropped.  With an activation that is
    not zero at zero (so that such a row comes out of its expert as
    something), the output and every gradient are still the dense sums."""
    def bent(h):
        return jnp.cos(h) + 1.0

    ops = _layer(11)
    every = (0, 1, 2, 3)

    def share(x, logits, w_in, w_out):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(dropless, "ROWS_PER_CHUNK", rows)
            chosen, weights = dropless.sigmoid_topk(logits, K, scale=5.0)
            held_w, is_chosen = dropless.held_weights(chosen, weights, 4, 8)
            out, counters = dropless.routed_experts(
                x, held_w, is_chosen, w_in[4:12], w_out[4:12],
                activation=bent)
        return jnp.sum(jnp.sin(out)), (out, counters)

    def dense(*a):
        out = _dense(*a, 4, 8, activation=bent)
        return jnp.sum(jnp.sin(out)), out

    (_, (got, counters)), got_grads = jax.value_and_grad(
        share, argnums=every, has_aux=True)(*ops)
    (_, want), want_grads = jax.value_and_grad(
        dense, argnums=every, has_aux=True)(*ops)
    assert any(int(n) % rows for n in counters["counts"])   # part-empty chunks
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-4)

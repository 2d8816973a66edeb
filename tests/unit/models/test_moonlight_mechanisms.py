"""Moonlight's mechanisms one at a time, each against a hand count or a
second way of writing it (CPU, tiny sizes): the rotary key is ONE head; the
absorbed form of the latent gives the expanded form's scores (the algebra
the serving half will stand on); the eight shares of a sparse layer add up
to the uncut layer (the guide's share test); the balance term by hand; the
selection bias moves the choice and not the weights; the dense layer and the
shared experts are where the configuration puts them."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core
from benchmarks.reference import moonlight_ref as ref
from deeperspeed_tpu.models.moonlight import (MLAttention, MoonlightBlock,
                                              MoonlightConfig, MoonlightMoE)
from deeperspeed_tpu.moe import dropless
from deeperspeed_tpu.ops.attention.core import _reference_latent_attention

TINY = core.load_json(core.BENCH_DIR
                      + "/configs/tiny-moonlight-rehearsal.json")
WHOLE = {k: v for k, v in TINY.items() if not k.endswith("_held")}


def _stream(seed, s=24, h=64):
    return jax.random.normal(jax.random.PRNGKey(seed), (s, h), jnp.float32)


def _layer(seed, kind=ref.SPARSE, cfg=WHOLE):
    """One layer's seeded leaves, the router's scores spread out so that
    the choice is far from a tie."""
    shapes = ref.layer_shapes(cfg, ref.share(cfg), kind)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    flat = {path: (jnp.ones(shape) if path[-1].endswith("norm_scale")
                   else jnp.zeros(shape) if path[-1] == "selection_bias"
                   else (0.5 if path[-1] == "router_kernel" else 0.05)
                   * jax.random.normal(key, shape, jnp.float32))
            for key, (path, shape) in zip(keys, shapes.items())}
    return ref._nest(flat)


# ------------------------------------------------------------ the attention
def test_the_rotary_key_is_one_head_its_gradient_the_sum_of_the_copies():
    """In the expanded form every head has a copy of ``k_r``; the gradient
    of the ONE key the program holds is the sum of the copies' gradients."""
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    B, S, N, dn, dr, dv = 1, 20, 4, 16, 8, 16
    q_nope, k_nope = (jax.random.normal(k, (B, S, N, dn)) for k in ks[:2])
    q_rope = jax.random.normal(ks[2], (B, S, N, dr))
    k_rope = jax.random.normal(ks[3], (B, S, dr))
    v = jax.random.normal(ks[4], (B, S, N, dv))

    def expanded(copies):                   # [B, S, N, dr]: a key a head
        q = jnp.concatenate([q_nope, q_rope], -1)
        k = jnp.concatenate([k_nope, copies], -1)
        scores = jnp.einsum("bqnd,bknd->bnqk", q, k) * (dn + dr) ** -0.5
        seen = jnp.arange(S)[None] <= jnp.arange(S)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.sum(jnp.square(jnp.einsum("bnqk,bknd->bqnd", probs, v)))

    copies = jnp.repeat(k_rope[:, :, None], N, axis=2)
    per_head = jax.grad(expanded)(copies)
    shared = jax.grad(lambda k: jnp.sum(jnp.square(
        _reference_latent_attention(q_nope, q_rope, k_nope, k, v))))(k_rope)
    np.testing.assert_allclose(shared, per_head.sum(axis=2), rtol=1e-4,
                               atol=1e-6)
    assert float(jnp.abs(per_head[:, :, 0] - per_head[:, :, 1]).max()) > 1e-3


def test_the_absorbed_form_gives_the_expanded_forms_scores():
    """``q_nope . (c W_kb)`` = ``(q_nope W_kb^T) . c``: the score against the
    512-wide latent (here 32) and the ONE rotary key is the expanded
    score, so a cache of ``[c | k_r]`` a token is enough to decode from."""
    p = _layer(1)["attn"]
    u = _stream(2)
    n, rank, dn, dr, _ = ref.widths(WHOLE)
    q, k, _ = ref.latent(u, p, WHOLE)
    expanded = jnp.einsum("qnd,knd->nqk", q, k)
    down = u @ p["kv_a_proj"]["kernel"]
    c = ref._rms_norm(down[:, :rank], p["kv_a_norm_scale"], 1e-5)
    k_r = k[:, 0, dn:]                         # turned, the same in each head
    np.testing.assert_array_equal(k[:, 1, dn:], k_r)
    w_kb = p["k_b_proj"]["kernel"].reshape(rank, n, dn)
    absorbed_q = jnp.einsum("qnd,rnd->qnr", q[..., :dn], w_kb)
    absorbed = (jnp.einsum("qnr,kr->nqk", absorbed_q, c)
                + jnp.einsum("qnd,kd->nqk", q[..., dn:], k_r))
    np.testing.assert_allclose(absorbed, expanded, rtol=1e-4, atol=1e-5)


def test_the_programs_sublayer_is_the_references_and_rotary_turns_a_part():
    cfg = MoonlightConfig.tiny()
    p = _layer(3)["attn"]
    u = _stream(4)
    got = MLAttention(cfg).apply({"params": p}, u[None])[0]
    np.testing.assert_allclose(got, ref.attention(u, p, WHOLE), rtol=1e-4,
                               atol=1e-6)
    # position reaches the score through the rotary part alone: with it
    # left out the sublayer commutes with a permutation of the PAST
    still = ref.attention(u, p, WHOLE, without=("rotary",))
    swapped = ref.attention(u.at[jnp.array([0, 1])].set(u[jnp.array([1, 0])]),
                            p, WHOLE, without=("rotary",))
    np.testing.assert_allclose(still[5:], swapped[5:], rtol=1e-4, atol=1e-6)
    turned = ref.attention(u.at[jnp.array([0, 1])].set(u[jnp.array([1, 0])]),
                           p, WHOLE)
    assert float(jnp.abs(turned[5:] - ref.attention(u, p, WHOLE)[5:]).max()
                 ) > 1e-5


# ----------------------------------------------------------------- the mixture
def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The guide's share test: every share's routed part, with what every
    chip computes alike (attention, the shared experts, the norms, the
    residual) counted once, is the uncut reference's layer output."""
    p = _layer(5)
    x = _stream(6)
    (want, _), _ = ref._layer(x, p, ref.SPARSE, WHOLE, ref.share(WHOLE),
                              "float32")
    held, routed, once = 2, 0.0, None
    for first in range(0, WHOLE["n_routed_experts"], held):
        cfg = MoonlightConfig.tiny(routed_experts_held=held,
                                   first_expert_held=first)
        mine = jax.tree_util.tree_map(lambda t: t, p)
        for name in ("experts_gate_up_proj", "experts_down_proj"):
            mine["moe"][name] = p["moe"][name][first:first + held]
        y, said = MoonlightBlock(cfg, ref.SPARSE).apply({"params": mine},
                                                        x[None])
        u = ref._rms_norm(x + ref.attention(ref._rms_norm(
            x, p["input_norm_scale"], 1e-5), p["attn"], WHOLE),
            p["post_norm_scale"], 1e-5)
        part, _, _, _ = MoonlightMoE(cfg).apply({"params": mine["moe"]},
                                                u[None])
        routed = routed + part[0]
        once = y[0] - part[0]           # the same on every share
        assert said["chosen"].shape == (1, 24, held)
    np.testing.assert_allclose(once + routed, want, rtol=1e-4, atol=1e-5)
    # and one share alone is not the layer
    assert float(jnp.abs(y[0] - want).max()) > 1e-3


def test_the_balance_term_against_a_hand_count():
    """Two sequences of 3 tokens over 4 experts, top-2."""
    logits = jnp.log(jnp.asarray([
        [3.0, 1.0, 1.0, 1.0], [1.0, 3.0, 1.0, 1.0], [3.0, 1.0, 1.0, 1.0],
        [1.0, 1.0, 1.0, 3.0], [1.0, 1.0, 3.0, 1.0], [1.0, 1.0, 3.0, 1.0]]))
    chosen = jnp.asarray([[0, 1], [1, 0], [0, 2], [3, 2], [2, 3], [2, 0]])
    s = np.asarray(jax.nn.sigmoid(logits))
    share = s / s.sum(-1, keepdims=True)
    want = 0.0
    for rows, took in ((slice(0, 3), [3, 2, 1, 0]), (slice(3, 6),
                                                     [1, 0, 3, 2])):
        f = np.asarray(took) * 4 / (2 * 3)
        want += float((f * share[rows].mean(0)).sum()) / 2
    got = dropless.sequence_balance(logits, chosen, seqs=2)
    assert float(got) == pytest.approx(want, rel=1e-6)
    # even scores and an even choice read 1; its gradient reaches the scores
    even = dropless.sequence_balance(
        jnp.zeros((4, 4)), jnp.asarray([[0, 1], [2, 3], [0, 1], [2, 3]]), 1)
    assert float(even) == pytest.approx(1.0)
    grad = jax.grad(lambda x: dropless.sequence_balance(x, chosen, 2))(logits)
    assert float(jnp.abs(grad).max()) > 1e-3
    # the reference's own, one sequence at a time, is the same count
    cfg = dict(aux_loss_alpha=1.0)
    by_ref = sum(float(ref.balance(jnp.asarray(s[rows]), chosen[rows], cfg))
                 for rows in (slice(0, 3), slice(3, 6))) / 2
    assert by_ref == pytest.approx(want, rel=1e-6)


def test_the_selection_bias_moves_the_choice_and_not_the_weights():
    p = _layer(7)["moe"]
    u = _stream(8)
    chosen, weights, scores = ref.route(u, p, WHOLE)
    np.testing.assert_allclose(weights.sum(-1), 2.446, rtol=1e-5)
    top = jnp.argsort(-scores, -1)[:, :3]
    np.testing.assert_array_equal(jnp.sort(chosen, -1), jnp.sort(top, -1))
    # a bias of 1 on expert 5: every token takes it; its weight is still its
    # score over the chosen scores' sum
    biased = dict(p, selection_bias=p["selection_bias"].at[5].set(1.0))
    chosen_b, weights_b, _ = ref.route(u, biased, WHOLE)
    assert bool(jnp.all(jnp.any(chosen_b == 5, axis=-1)))
    picked = jnp.take_along_axis(scores, chosen_b, -1)
    np.testing.assert_allclose(
        weights_b, 2.446 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    # the program's layer under the same bias chooses the same
    cfg = MoonlightConfig.tiny(routed_experts_held=None, first_expert_held=0)
    _, _, is_chosen, _ = MoonlightMoE(cfg).apply({"params": biased}, u[None])
    assert bool(jnp.all(is_chosen[0, :, 5]))
    assert float(jnp.abs(jax.grad(lambda b: jnp.sum(MoonlightMoE(cfg).apply(
        {"params": dict(p, selection_bias=b)}, u[None])[0]))(
            biased["selection_bias"])).max()) == 0.0


def test_the_dense_layer_and_the_shared_experts_are_where_the_file_says():
    cfg = MoonlightConfig.tiny(routed_experts_held=None, first_expert_held=0)
    x = _stream(9)
    for kind in (ref.DENSE, ref.SPARSE):
        p = _layer(10, kind)
        y, said = MoonlightBlock(cfg, kind).apply({"params": p}, x[None])
        (want, term), _ = ref._layer(x, p, kind, WHOLE, ref.share(WHOLE),
                                     "float32")
        np.testing.assert_allclose(y[0], want, rtol=1e-4, atol=1e-5)
        assert bool(said) is (kind == ref.SPARSE)
        if said:
            assert float(said["loss"]) == pytest.approx(float(term), rel=1e-5)
    # the shared experts are ONE gated MLP of 2 x 48, on every token
    p = _layer(10, ref.SPARSE)
    assert p["shared_experts"]["gate_proj"]["kernel"].shape == (64, 96)
    (without, _), _ = ref._layer(x, p, ref.SPARSE, WHOLE, ref.share(WHOLE),
                                 "float32", without=("shared_experts",))
    (with_, _), _ = ref._layer(x, p, ref.SPARSE, WHOLE, ref.share(WHOLE),
                               "float32")
    h = x + ref.attention(ref._rms_norm(x, p["input_norm_scale"], 1e-5),
                          p["attn"], WHOLE)
    np.testing.assert_allclose(
        with_ - without, ref.gated_mlp(ref._rms_norm(
            h, p["post_norm_scale"], 1e-5), p["shared_experts"]),
        rtol=1e-4, atol=1e-6)
    with pytest.raises(ValueError, match="a layer of MoonlightBlock"):
        from deeperspeed_tpu.models.moonlight import Moonlight

        class Other(Moonlight):
            def stack(self):
                return super().stack()._replace(kinds=("linear",))

        Other(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert isinstance(MoonlightBlock(cfg, ref.DENSE), nn.Module)

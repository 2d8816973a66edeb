"""Zaya's mechanisms one at a time, by hand on a few tokens on the CPU: the
value shift, both convolutions against an explicit loop, the q-k mean with
four query heads a KV head, unit-RMS heads and k's temperature, rotary on
half a head, depth averaging over three layers, the unrenormalised top-1
weight and its gradient to the router; and the plain reference
(``benchmarks/reference/zaya_ref.py``) with one mechanism left out at a
time: each control of the benchmark's check is another model than the one
``test_zaya.py`` holds the program to."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core
from benchmarks.reference import zaya_ref as ref
from deeperspeed_tpu.models.zaya import ZayaConfig, ZayaMoE, cca_mix
from deeperspeed_tpu.ops.ssm import (causal_depthwise_conv1d,
                                     causal_headwise_conv1d)

TINY = core.load_json(core.BENCH_DIR + "/configs/tiny-zaya-rehearsal.json")
#: 8 query heads over 2 KV heads of 16, as the published model's 8 over 2
HEADS, KV, D, S = 8, 2, 16, 7
C = (HEADS + KV) * D


def _ids(seed, b=2, s=96):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, TINY["vocab_size"], size=(b, s + 1),
                        dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


def _normal(seed, *shape):
    return np.random.default_rng(seed).standard_normal(shape).astype(
        np.float32)


def _mix(qt, kt, v, taps=None, kernel=None, tau=None, rotary_dim=D // 2,
         bias=0.0):
    """``cca_mix`` of one sequence; identity convolutions unless given."""
    if taps is None:
        taps = np.stack([np.zeros(C), np.ones(C)]).astype(np.float32)
    if kernel is None:
        kernel = np.stack([np.zeros((HEADS + KV, D, D)),
                           np.tile(np.eye(D), (HEADS + KV, 1, 1))]).astype(
                               np.float32)
    q, k, v = cca_mix(
        *(jnp.asarray(x)[None] for x in (qt, kt, v)), jnp.asarray(taps),
        jnp.full((C,), bias), jnp.asarray(kernel), jnp.zeros((C,)),
        jnp.ones((KV,)) if tau is None else jnp.asarray(tau), heads=HEADS,
        kv_heads=KV, rotary_dim=rotary_dim, rope_theta=5e6, eps=1e-5)
    return np.asarray(q[0]), np.asarray(k[0]), np.asarray(v[0])


def _unit(x):
    """Every head of [S, heads * D] over its RMS."""
    x = x.reshape(x.shape[0], -1, D)
    return (x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5)).reshape(
        x.shape[0], -1)


def test_the_value_shift():
    """KV head 0's value is the token's own, KV head 1's the previous
    token's, zeros before the sequence."""
    qt, kt, v = _normal(1, S, HEADS * D), _normal(2, S, KV * D), _normal(
        3, S, KV * D)
    got = _mix(qt, kt, v)[2]
    np.testing.assert_array_equal(got[:, :D], v[:, :D])
    np.testing.assert_array_equal(got[1:, D:], v[:-1, D:])
    assert not got[0, D:].any()
    # the reference's, through its projection
    u = _normal(4, S, 64)
    p = ref.init_params(TINY, 1)["layers_0"]["attn"]
    mine = ref.cca_mix(jnp.asarray(u), p, TINY)[2]
    plain = np.asarray(u @ p["v_proj"]["kernel"])
    np.testing.assert_allclose(mine[:, 0], plain[:, :16], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(mine[1:, 1], plain[:-1, 16:], rtol=1e-5,
                               atol=1e-6)
    assert not np.asarray(mine[0, 1]).any()


def test_both_convolutions_are_the_explicit_loops():
    z = _normal(5, S, C)
    taps, bias = _normal(6, 2, C), _normal(7, C)
    kernel, head_bias = _normal(8, 2, HEADS + KV, D, D), _normal(9, C)
    z1 = np.zeros_like(z)
    for t in range(S):
        for c in range(C):
            before = z[t - 1, c] if t else 0.0
            z1[t, c] = taps[0, c] * before + taps[1, c] * z[t, c] + bias[c]
    z2 = np.zeros_like(z)
    for t in range(S):
        for h in range(HEADS + KV):
            at = slice(h * D, (h + 1) * D)
            before = z1[t - 1, at] if t else np.zeros(D, np.float32)
            z2[t, at] = (before @ kernel[0, h] + z1[t, at] @ kernel[1, h]
                         + head_bias[at])
    got1 = causal_depthwise_conv1d(jnp.asarray(z)[None], jnp.asarray(taps),
                                   jnp.asarray(bias))
    np.testing.assert_allclose(got1[0], z1, rtol=1e-5, atol=1e-5)
    got2 = causal_headwise_conv1d(got1, jnp.asarray(kernel),
                                  jnp.asarray(head_bias))
    np.testing.assert_allclose(got2[0], z2, rtol=1e-4, atol=1e-5)
    # the reference's shifted sums are the same loops
    np.testing.assert_allclose(
        ref.headwise_conv(ref.depthwise_conv(jnp.asarray(z), taps, bias),
                          jnp.asarray(kernel), head_bias), z2, rtol=1e-4,
        atol=1e-5)
    # no channel of one head reaches another head's, nor a later step an
    # earlier one
    moved = z.copy()
    moved[3, :D] += 1.0
    again = causal_headwise_conv1d(
        jnp.asarray(moved)[None], jnp.asarray(kernel),
        jnp.asarray(head_bias))[0]
    first = causal_headwise_conv1d(
        jnp.asarray(z)[None], jnp.asarray(kernel), jnp.asarray(head_bias))[0]
    changed = np.abs(np.asarray(again - first)) > 0
    assert changed[3:5, :D].any() and not changed[:3].any()
    assert not changed[:, D:].any() and not changed[5:].any()


def test_the_qk_mean_with_four_query_heads_a_kv_head():
    """With the convolutions' output ZERO (zero taps) q and k are the means
    alone: ``m_q[j] = (qt[j] + kt[j // 4]) / 2`` and ``m_k[i]`` the mean of
    its four query heads' ``m_q``, each head then over its RMS."""
    qt, kt, v = _normal(10, S, HEADS * D), _normal(11, S, KV * D), _normal(
        12, S, KV * D)
    q, k, _ = _mix(qt, kt, v, taps=np.zeros((2, C), np.float32),
                   kernel=np.zeros((2, HEADS + KV, D, D), np.float32),
                   rotary_dim=0)
    m_q = (qt.reshape(S, HEADS, D) + np.repeat(kt.reshape(S, KV, D), 4,
                                               axis=1)) / 2
    m_k = m_q.reshape(S, KV, 4, D).mean(2)
    np.testing.assert_allclose(q, _unit(m_q.reshape(S, -1)), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(k, _unit(m_k.reshape(S, -1)), rtol=1e-5,
                               atol=1e-6)
    # and they are taken BEFORE the convolutions and added AFTER them:
    # identity convolutions give z + m
    q, k, _ = _mix(qt, kt, v, rotary_dim=0)
    np.testing.assert_allclose(q, _unit(qt + m_q.reshape(S, -1)), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(k, _unit(kt + m_k.reshape(S, -1)), rtol=1e-5,
                               atol=1e-6)


def test_unit_rms_heads_and_ks_temperature():
    qt, kt, v = 7.0 * _normal(13, S, HEADS * D), 0.01 * _normal(
        14, S, KV * D), _normal(15, S, KV * D)
    tau = np.asarray([0.5, 3.0], np.float32)
    q, k, _ = _mix(qt, kt, v, tau=tau, rotary_dim=0)
    rms = np.sqrt((q.reshape(S, HEADS, D) ** 2).mean(-1))
    np.testing.assert_allclose(rms, 1.0, rtol=1e-4)
    rms = np.sqrt((k.reshape(S, KV, D) ** 2).mean(-1))
    np.testing.assert_allclose(rms, np.broadcast_to(tau, (S, KV)), rtol=1e-2)
    # rotary turns a head and does not stretch it
    q2, k2, _ = _mix(qt, kt, v, tau=tau)
    np.testing.assert_allclose(
        np.sqrt((q2.reshape(S, HEADS, D) ** 2).mean(-1)), 1.0, rtol=1e-4)


def test_rotary_on_half_a_head():
    """The first 8 of a head's 16 dims turn by the halves convention with
    ``theta^(-2i/8)``; the last 8 pass through; position 0 does not turn."""
    qt, kt, v = _normal(16, S, HEADS * D), _normal(17, S, KV * D), _normal(
        18, S, KV * D)
    still = _mix(qt, kt, v, rotary_dim=0)
    turned = _mix(qt, kt, v)
    inv_freq = 5e6 ** (-2 * np.arange(4) / 8)
    angle = np.arange(S)[:, None] * inv_freq[None]
    cos, sin = (np.concatenate([f(angle)] * 2, -1)[:, None] for f in (
        np.cos, np.sin))
    for got, x, heads in zip(turned[:2], still[:2], (HEADS, KV)):
        got, x = got.reshape(S, heads, D), x.reshape(S, heads, D)
        np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
        half = np.concatenate([-x[..., 4:8], x[..., :4]], -1)
        np.testing.assert_allclose(got[..., :8], x[..., :8] * cos + half * sin,
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(got[0], x[0], rtol=1e-6)
    cos_ref, sin_ref = ref.rotary(TINY, jnp.arange(S))
    np.testing.assert_allclose(cos_ref, cos[:, 0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(sin_ref, sin[:, 0], rtol=1e-5, atol=1e-6)


def test_depth_averaging_over_three_layers():
    """``rho_l = u W_D + b_D + gamma_l rho_{l-1}``, ``rho_{l-1}`` after its
    own averaging: three layers by hand, the first with no ``gamma``."""
    params = ref.init_params(TINY, 19)
    cfg = ZayaConfig.tiny()
    u = jnp.asarray(_normal(20, 1, S, 64))
    rho, by_hand = None, None
    for i in range(3):
        p = params[f"layers_{i}"]["moe"]
        assert ("router_gamma" in p) == (i > 0)
        _, _, _, rho = ZayaMoE(cfg).apply({"params": p}, u, rho)
        own = np.asarray(u[0] @ p["router_down_kernel"]
                         + p["router_down_bias"])
        by_hand = own if i == 0 else own + np.asarray(
            p["router_gamma"]) * by_hand
        np.testing.assert_allclose(rho[0], by_hand, rtol=1e-5, atol=1e-6)
    # the third layer's state holds the first's, by gamma_2 gamma_1
    p1, p2 = (params[f"layers_{i}"]["moe"]["router_gamma"] for i in (1, 2))
    first = np.asarray(u[0] @ params["layers_0"]["moe"]["router_down_kernel"]
                       + params["layers_0"]["moe"]["router_down_bias"])
    own = [np.asarray(u[0] @ params[f"layers_{i}"]["moe"][
        "router_down_kernel"] + params[f"layers_{i}"]["moe"][
            "router_down_bias"]) for i in (1, 2)]
    np.testing.assert_allclose(
        by_hand, own[1] + np.asarray(p2) * (own[0] + np.asarray(p1) * first),
        rtol=1e-5, atol=1e-6)


def test_the_unrenormalised_top_1_weight_and_its_gradient_to_the_router():
    """The layer's output is ``p[chosen] * Expert_chosen(u)`` with ``p`` the
    softmax over all 16: the router's last matrix gets a gradient through
    that weight, which a renormalised weight (1) would not give it; the
    balancing bias chooses and gets none."""
    whole = ZayaConfig.tiny(routed_experts_held=None, first_expert_held=0)
    u = jnp.asarray(_normal(21, 1, S, 64))
    moe = ZayaMoE(whole)
    p = moe.init(jax.random.PRNGKey(22), u)["params"]
    out, counters, chosen, _ = moe.apply({"params": p}, u)
    x = np.asarray(u[0])
    rho = x @ p["router_down_kernel"] + p["router_down_bias"]
    hidden = np.asarray(ref._rms_norm(jnp.asarray(rho), p["router_norm_scale"],
                                      1e-5))
    for name in ("router_mlp_1", "router_mlp_2"):
        hidden = np.asarray(jax.nn.gelu(hidden @ p[name], approximate=False))
    probs = np.asarray(jax.nn.softmax(hidden @ p["router_mlp_3"], axis=-1))
    np.testing.assert_array_equal(np.asarray(chosen[0]).argmax(-1),
                                  probs.argmax(-1))
    assert int(counters["slots"]) == S
    for t in range(S):
        e = probs[t].argmax()
        mid = x[t] @ np.asarray(p["experts_gate_up_proj"][e])
        mid = np.asarray(jax.nn.silu(mid[:48])) * mid[48:]
        np.testing.assert_allclose(
            out[0, t], probs[t, e] * (mid @ np.asarray(
                p["experts_down_proj"][e])), rtol=1e-4, atol=1e-6)
    assert 1 / 16 < probs.max(-1).mean() < 0.5      # far from a weight of 1
    grads = jax.grad(lambda q: jnp.sum(moe.apply({"params": q}, u)[0] ** 2))(p)
    for name in ("router_mlp_3", "router_mlp_1", "router_down_kernel"):
        assert np.asarray(grads[name]).any(), name
    assert not np.asarray(grads["selection_bias"]).any()
    # a bias moves the choice and not the weight
    biased = dict(p, selection_bias=jnp.zeros(16).at[5].set(1.0))
    out5, _, chosen5, _ = moe.apply({"params": biased}, u)
    assert np.asarray(chosen5[0])[:, 5].all()
    mid = x @ np.asarray(p["experts_gate_up_proj"][5])
    mid = np.asarray(jax.nn.silu(mid[:, :48])) * mid[:, 48:]
    np.testing.assert_allclose(
        out5[0], probs[:, 5:6] * (mid @ np.asarray(p["experts_down_proj"][5])),
        rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("mechanism", ref.MECHANISMS)
def test_a_model_without_a_mechanism_is_another_model(mechanism):
    """Each control of the benchmark's check computes something else: by
    its log-probabilities, and without the carried state by the experts
    chosen and the state itself."""
    params = ref.init_params(TINY, 11)
    ids, labels = _ids(11, b=1)
    lp, picked, states = ref.token_logprobs(params, TINY, ids[0], labels[0])
    lp2, picked2, states2 = ref.token_logprobs(
        params, TINY, ids[0], labels[0], without=(mechanism,))
    assert np.abs(np.asarray(lp2 - lp)).max() > 1e-4
    if mechanism == "router_state":
        np.testing.assert_array_equal(states2[0], states[0])
        assert np.abs(np.asarray(states2[1:] - states[1:])).max() > 0.1
        assert (np.asarray(picked2) != np.asarray(picked)).any()
    if mechanism == "tied_head":
        # the stack's hidden states are the same: the head alone differs
        np.testing.assert_array_equal(states2, states)
        grads = ref.loss_and_grads(params, TINY, ids, labels,
                                   without=(mechanism,))[1]
        tied = ref.loss_and_grads(params, TINY, ids, labels)[1]
        unseen = np.setdiff1d(np.arange(256), np.asarray(ids))
        table = grads["embed_tokens"]["embedding"]
        assert not np.asarray(table)[unseen].any()      # the scatter alone
        assert np.asarray(tied["embed_tokens"]["embedding"])[unseen].any()
    with pytest.raises(ValueError, match="without"):
        ref.token_logprobs(params, TINY, ids[0], labels[0], without=("x",))

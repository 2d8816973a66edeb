"""The chunked head + cross entropy with several labels a token
(``ops/transformer/cross_entropy.py::multi_label_linear_cross_entropy``):
one product of ``K * V`` columns a chunk and ``K`` softmaxes of ``V`` over
it, against the plain form (the whole ``[T, K, V]`` logits), value and all
three gradients, for chunks that divide the tokens and that do not; the
logits stay float32 under bfloat16 operands; what the walk counts; and the
single-label entry points are untouched by it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu import telemetry
from deeperspeed_tpu.ops.transformer import cross_entropy as ce

T, H, K, V = 150, 32, 8, 40


def _operands(seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (T, H), dtype)
    w = 0.3 * jax.random.normal(ks[1], (H, K * V), jnp.float32)
    labels = jax.random.randint(ks[2], (T, K), 0, V)
    mask = (jax.random.uniform(ks[3], (T, K)) > 0.2).astype(jnp.float32)
    return x, w, labels, -mask / mask.sum()


def _plain(x, w, labels, weights):
    logits = (x.astype(jnp.float32) @ w).reshape(T, K, V)
    ll = (jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
          - jax.nn.logsumexp(logits, axis=-1))
    return jnp.sum(weights * ll), ll


@pytest.mark.parametrize("chunk", [150, 64, 50, 1000])
def test_value_and_gradients_against_the_whole_logits(chunk):
    x, w, labels, weights = _operands(chunk)
    fused = lambda x, w, wt: ce.multi_label_linear_cross_entropy(  # noqa: E731
        x, w, labels, wt, chunk)
    (got, ran), grads = jax.value_and_grad(fused, argnums=(0, 1, 2),
                                           has_aux=True)(x, w, weights)
    want, want_grads = jax.value_and_grad(
        lambda x, w, wt: _plain(x, w, labels, wt)[0], argnums=(0, 1, 2))(
            x, w, weights)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert int(ran) == -(-T // min(chunk, T))
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    # the weights' gradient is the tokens' log-probabilities
    np.testing.assert_allclose(grads[2], _plain(x, w, labels, weights)[1],
                               rtol=1e-5, atol=1e-6)
    # not under differentiation: the same value from the forward walk
    value, _ = ce.multi_label_linear_cross_entropy(x, w, labels, weights,
                                                   chunk)
    assert float(value) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(
        ce.multi_label_logprobs(x, w, labels, chunk),
        _plain(x, w, labels, weights)[1], rtol=1e-5, atol=1e-6)


def test_the_logits_are_float32_under_bfloat16_operands():
    """``fp32_logits``: the product accumulates in float32 and is never
    rounded to the operands' type.  Against the same bfloat16 operands
    multiplied in float32 the fused form agrees to float32 rounding; a
    product ROUNDED to bfloat16 (the single-label body's) is two orders
    further off."""
    x, w, labels, weights = _operands(3, jnp.bfloat16)
    wb = w.astype(jnp.bfloat16)
    exact = _plain(x.astype(jnp.float32), wb.astype(jnp.float32), labels,
                   weights)[1]
    got = ce.multi_label_logprobs(x, wb, labels, 64)
    assert got.dtype == jnp.float32
    err = float(jnp.abs(got - exact).max())
    rounded = (x @ wb).astype(jnp.float32).reshape(T, K, V)
    rounded = (jnp.take_along_axis(rounded, labels[..., None], -1)[..., 0]
               - jax.nn.logsumexp(rounded, axis=-1))
    assert err < 1e-5 < 100 * err < float(jnp.abs(rounded - exact).max())
    # no [T, K * V] float32 buffer: a chunk's logits are the largest value
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda x: ce.multi_label_linear_cross_entropy(
            x, w, labels, weights, 50)[0]))(x))
    assert f"f32[50,{K * V}]" in jaxpr and f"f32[{T},{K * V}]" not in jaxpr
    assert f"f32[{T},{K},{V}]" not in jaxpr


def test_the_walk_is_counted_under_a_name_of_its_own():
    x, w, labels, weights = _operands(4)
    before = dict(telemetry.kernel_paths().get("head_ce", {}))
    jax.grad(lambda x: ce.multi_label_linear_cross_entropy(
        x, w, labels, weights, 64)[0])(x)
    ce.multi_label_logprobs(x, w, labels, 64)
    after = telemetry.kernel_paths()["head_ce"]
    assert after["fused_multi_label"] == before.get(
        "fused_multi_label", 0) + 1
    assert after["per_token_multi_label"] == before.get(
        "per_token_multi_label", 0) + 1
    # the single-label forms count as they did
    assert after.get("fused", 0) == before.get("fused", 0)
    assert after.get("per_token", 0) == before.get("per_token", 0)


def test_one_label_a_token_is_the_single_label_form():
    x, w, labels, weights = _operands(5)
    one = ce.multi_label_linear_cross_entropy(
        x, w[:, :V], labels[:, :1], weights[:, :1], 64)[0]
    want = ce.weighted_linear_cross_entropy(x, w[:, :V], labels[:, 0],
                                            weights[:, 0], 64)[0]
    assert float(one) == pytest.approx(float(want), rel=1e-5)

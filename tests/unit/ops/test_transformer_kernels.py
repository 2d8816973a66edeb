"""Pallas transformer-kernel numerics tests.

Pattern: reference ``tests/unit/ops/transformer`` -- each fused op is
compared against plain jnp math, fwd and grad.  On the CPU mesh the kernels
run in Pallas interpret mode, so the exact kernel code paths execute.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu.ops.transformer import (
    apply_rotary_pos_emb,
    bias_gelu,
    fused_softmax,
    gelu_tanh,
    layer_norm,
    rms_norm,
    rotary_tables,
)


def _ref_ln(x, g, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / np.sqrt(var + eps) * g + b


class TestLayerNorm:
    @pytest.mark.parametrize("shape", [(4, 16, 256), (2, 128)])
    def test_forward_matches_reference(self, shape):
        rng = np.random.RandomState(0)
        x = rng.randn(*shape).astype(np.float32)
        g = rng.randn(shape[-1]).astype(np.float32)
        b = rng.randn(shape[-1]).astype(np.float32)
        got = np.asarray(layer_norm(jnp.asarray(x), jnp.asarray(g),
                                    jnp.asarray(b), use_pallas=True))
        np.testing.assert_allclose(got, _ref_ln(x, g, b), rtol=1e-5, atol=1e-5)

    def test_grads_match_autodiff(self):
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(6, 256).astype(np.float32))
        g = jnp.asarray(rng.randn(256).astype(np.float32))
        b = jnp.asarray(rng.randn(256).astype(np.float32))

        def loss_pallas(x, g, b):
            return jnp.sum(layer_norm(x, g, b, use_pallas=True) ** 2)

        def loss_ref(x, g, b):
            mu = x.mean(-1, keepdims=True)
            var = ((x - mu) ** 2).mean(-1, keepdims=True)
            y = (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b
            return jnp.sum(y ** 2)

        got = jax.grad(loss_pallas, argnums=(0, 1, 2))(x, g, b)
        want = jax.grad(loss_ref, argnums=(0, 1, 2))(x, g, b)
        for a, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                       rtol=1e-4, atol=1e-4)

    def test_multiblock_grad_accumulation(self):
        """Row counts spanning multiple grid blocks with a partial last
        block: dgamma/dbeta must only accumulate real rows (rows are padded
        to a block multiple with explicit zeros)."""
        rng = np.random.RandomState(7)
        x = jnp.asarray(rng.randn(264, 128).astype(np.float32))  # 2 blocks, partial
        g = jnp.asarray(rng.randn(128).astype(np.float32))
        b = jnp.asarray(rng.randn(128).astype(np.float32))
        got = jax.grad(lambda gg: jnp.sum(
            layer_norm(x, gg, b, use_pallas=True) ** 2))(g)
        want = jax.grad(lambda gg: jnp.sum(
            ((x - x.mean(-1, keepdims=True)) * jax.lax.rsqrt(
                x.var(-1, keepdims=True) + 1e-5) * gg + b) ** 2))(g)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-3)

    def test_row_padding(self):
        """Row counts that don't tile onto sublanes are padded correctly."""
        rng = np.random.RandomState(2)
        x = rng.randn(5, 128).astype(np.float32)  # 5 rows: pads to 8
        g = np.ones(128, np.float32)
        b = np.zeros(128, np.float32)
        got = np.asarray(layer_norm(jnp.asarray(x), jnp.asarray(g),
                                    jnp.asarray(b), use_pallas=True))
        np.testing.assert_allclose(got, _ref_ln(x, g, b), rtol=1e-5, atol=1e-5)

    def test_unsupported_hidden_falls_back(self):
        x = jnp.ones((4, 100))  # 100 not a multiple of 128
        g, b = jnp.ones(100), jnp.zeros(100)
        out = layer_norm(x, g, b)  # auto dispatch must not crash
        assert out.shape == (4, 100)


class TestRMSNorm:
    def test_forward_and_grad(self):
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(4, 256).astype(np.float32))
        g = jnp.asarray(rng.randn(256).astype(np.float32))
        got = np.asarray(rms_norm(x, g, use_pallas=True))
        xn = np.asarray(x)
        want = xn / np.sqrt((xn ** 2).mean(-1, keepdims=True) + 1e-5) * np.asarray(g)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

        gp = jax.grad(lambda a: jnp.sum(rms_norm(a, g, use_pallas=True) ** 2))(x)
        gr = jax.grad(lambda a: jnp.sum(
            (a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + 1e-5) * g) ** 2))(x)
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   rtol=1e-4, atol=1e-4)


class TestSoftmax:
    def test_forward_and_grad(self):
        rng = np.random.RandomState(4)
        x = jnp.asarray(rng.randn(3, 7, 128).astype(np.float32))
        got = np.asarray(fused_softmax(x, scale=0.5, use_pallas=True))
        want = np.asarray(jax.nn.softmax(np.asarray(x) * 0.5, axis=-1))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

        gp = jax.grad(lambda a: jnp.sum(
            fused_softmax(a, scale=0.5, use_pallas=True) * a))(x)
        gr = jax.grad(lambda a: jnp.sum(
            jax.nn.softmax(a * 0.5, axis=-1) * a))(x)
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   rtol=1e-4, atol=1e-5)


class TestGelu:
    def test_forward_and_grad(self):
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(1000).astype(np.float32) * 3)
        got = np.asarray(gelu_tanh(x, use_pallas=True))
        want = np.asarray(jax.nn.gelu(x, approximate=True))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

        gp = jax.grad(lambda a: jnp.sum(gelu_tanh(a, use_pallas=True) * a))(x)
        gr = jax.grad(lambda a: jnp.sum(jax.nn.gelu(a, approximate=True) * a))(x)
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   rtol=1e-4, atol=1e-5)

    def test_bias_gelu(self):
        x = jnp.ones((4, 64))
        b = jnp.full((64,), 0.5)
        np.testing.assert_allclose(
            np.asarray(bias_gelu(x, b, use_pallas=True)),
            np.asarray(jax.nn.gelu(x + b, approximate=True)),
            rtol=1e-5, atol=1e-6)


class TestRope:
    def test_partial_rotation_roundtrip(self):
        rng = np.random.RandomState(6)
        B, S, N, D, rot = 2, 8, 4, 64, 16
        q = jnp.asarray(rng.randn(B, S, N, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, S, N, D).astype(np.float32))
        pos = jnp.broadcast_to(jnp.arange(S), (B, S))
        cos, sin = rotary_tables(pos, rot)
        q2, k2 = apply_rotary_pos_emb(q, k, cos, sin)
        # pass-through dims untouched
        np.testing.assert_array_equal(np.asarray(q2[..., rot:]),
                                      np.asarray(q[..., rot:]))
        # rotation preserves norms of the rotated pairs
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(q2[..., :rot]), axis=-1),
            np.linalg.norm(np.asarray(q[..., :rot]), axis=-1), rtol=1e-5)
        # position 0 is identity
        np.testing.assert_allclose(np.asarray(q2[:, 0]), np.asarray(q[:, 0]),
                                   rtol=1e-6, atol=1e-6)


    @pytest.mark.parametrize("head_dim,rot,yarn", [
        (128, 64, True),      # Laguna's full layers: half a head under YaRN
        (128, 128, False),    # its sliding layers: the whole head, plain
        (64, 16, False)])
    def test_first_dims_turn_and_the_rest_pass(self, head_dim, rot, yarn):
        """``apply_rotary_pos_emb`` turns the first ``rot`` dims of a head
        (``cos.shape[-1]``) and passes the rest through; under YaRN the
        frequencies are those of the ``rot`` dims that turn (factor 128 over
        8192 at theta 500,000: c(32) = 9.04, c(1) = 17.49, so the first ten
        of 32 are plain, those from the nineteenth on divided by 128) and
        both tables carry the attention factor."""
        from deeperspeed_tpu.ops.transformer.rope import yarn_inv_freq

        rng = np.random.RandomState(7)
        q = jnp.asarray(rng.randn(1, 8, 3, head_dim).astype(np.float32))
        k = jnp.asarray(rng.randn(1, 8, 2, head_dim).astype(np.float32))
        pos = jnp.arange(8)[None]
        factor = 1.4852030263919618
        if yarn:
            inv = yarn_inv_freq(rot, 500000.0, 128.0, 8192, 32, 1)
            plain = 500000.0 ** (-2 * np.arange(rot // 2) / rot)
            np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
            np.testing.assert_allclose(inv[18:], plain[18:] / 128, rtol=1e-6)
            np.testing.assert_allclose(      # i = 12: ramp 3 / 9
                inv[12], plain[12] * (1 / 3 / 128 + 2 / 3), rtol=1e-6)
            cos, sin = rotary_tables(pos, rot, 500000.0, inv_freq=inv,
                                     scale=factor)
        else:
            cos, sin = rotary_tables(pos, rot)
        assert cos.shape == (1, 8, 1, rot)
        q2, k2 = apply_rotary_pos_emb(q, k, cos, sin)
        for got, was in ((q2, q), (k2, k)):
            np.testing.assert_array_equal(np.asarray(got[..., rot:]),
                                          np.asarray(was[..., rot:]))
            np.testing.assert_allclose(
                np.linalg.norm(np.asarray(got[..., :rot]), axis=-1),
                np.linalg.norm(np.asarray(was[..., :rot]), axis=-1)
                * (factor if yarn else 1.0), rtol=1e-5)
            assert float(jnp.min(jnp.abs(got[:, 1:, :, :rot]
                                         - was[:, 1:, :, :rot]))) > 0


class TestTransformerLayer:
    def test_layer_runs_and_differentiates(self):
        from deeperspeed_tpu.ops.transformer.transformer import (
            DeeperSpeedTransformerConfig, DeeperSpeedTransformerLayer)

        cfg = DeeperSpeedTransformerConfig(hidden_size=128, heads=4,
                                           attn_dropout_ratio=0.0,
                                           hidden_dropout_ratio=0.0)
        layer = DeeperSpeedTransformerLayer(cfg)
        x = jnp.ones((2, 16, 128))
        params = layer.init(jax.random.PRNGKey(0), x)["params"]
        y = layer.apply({"params": params}, x)
        assert y.shape == x.shape
        g = jax.grad(lambda p: jnp.sum(
            layer.apply({"params": p}, x) ** 2))(params)
        assert jnp.isfinite(jax.tree_util.tree_leaves(g)[0]).all()

"""The fused optimizer names build optax's own chain (the Pallas Adam and
Lion kernels lost to XLA's fusion of it and went, PR 31): ``FusedAdam`` and
``FusedLion`` give optax's update bit for bit, and train through the engine."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from deeperspeed_tpu.runtime.config import OptimizerParams
from deeperspeed_tpu.runtime.optimizers import build_optimizer


def _same_updates(ours, ref, steps):
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(64, 32).astype(np.float32)),
              "b": jnp.asarray(rng.randn(4096).astype(np.float32))}
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.randn(*p.shape).astype(np.float32)), params)
    s1, s2 = ours.init(params), ref.init(params)
    for _ in range(steps):
        u1, s1 = jax.jit(ours.update)(grads, s1, params)
        u2, s2 = jax.jit(ref.update)(grads, s2, params)
        for k in params:
            assert np.array_equal(np.asarray(u1[k]), np.asarray(u2[k]))
        grads = jax.tree_util.tree_map(lambda g: g * 0.7, grads)


def test_fused_adam_matches_optax():
    ours = build_optimizer("FusedAdam", OptimizerParams(
        betas=[0.9, 0.999], eps=1e-8))
    _same_updates(ours, optax.scale_by_adam(b1=0.9, b2=0.999, eps=1e-8), 5)


def test_fused_lion_matches_optax():
    ours = build_optimizer("FusedLion", OptimizerParams(betas=[0.9, 0.99]))
    _same_updates(ours, optax.scale_by_lion(b1=0.9, b2=0.99), 3)


def test_lion_trains_via_engine():
    _trains_via_engine("Lion")


@pytest.mark.parametrize("name", ["FusedLion", "FusedAdam"])
def test_fused_names_train_via_engine(name):
    _trains_via_engine(name)


def _trains_via_engine(name):
    import deeperspeed_tpu as dst
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    model = GPTNeoX(GPTNeoXConfig.tiny())
    cfg = {"train_batch_size": 8, "gradient_accumulation_steps": 1,
           "optimizer": {"type": name,
                         "params": {"lr": 1e-4, "betas": [0.9, 0.99],
                                    "weight_decay": 0.1}}}
    engine, _, _, _ = dst.initialize(model=model, config=cfg)
    batch = model.example_batch(batch_size=8, seq_len=32)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(5)]
    assert losses[-1] < losses[0]

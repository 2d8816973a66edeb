"""Moonlight (``models/moonlight.py``) against the plain reference
(``benchmarks/reference/moonlight_ref.py``) at a tiny size on the CPU:
logits, log-probabilities and the chosen experts, the loss with its balance
terms, the gradient of every parameter and one Adam update; the reference's
chain rule a layer at a time is ``jax.grad`` of its own loss; the cut's
arithmetic at the published preset; and the model through
``dst.initialize`` / ``engine.train_batch`` under a warm-up.  (Each
mechanism by hand, and the shares: ``test_moonlight_mechanisms.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core
from benchmarks.reference import moonlight_ref as ref
from deeperspeed_tpu import telemetry
from deeperspeed_tpu.models.moonlight import (DENSE, SPARSE, Moonlight,
                                              MoonlightConfig)
from deeperspeed_tpu.moe import dropless
from deeperspeed_tpu.ops.attention.pallas_flash import SAVED_BY_REMAT

runner = core.load_runner("train_mla_moe")
TINY = core.load_json(core.BENCH_DIR
                      + "/configs/tiny-moonlight-rehearsal.json")
CELL = core.load_json(core.BENCH_DIR + "/configs/moonlight-16b-a3b.json")
TRAFFIC = {"seq_len": 64, "micro_batch": 2, "dtype": "float32",
           "ce_chunk_tokens": 48}


def _model(cfg=TINY, **traffic):
    return runner.program_model(cfg, dict(TRAFFIC, **traffic))


def _ids(seed, cfg=TINY, b=2, s=64):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg["vocab_size"], size=(b, s + 1), dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


def _leaves(tree):
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def test_the_preset_is_the_rehearsal_configuration():
    assert _model().config == MoonlightConfig.tiny()
    assert _model().config.kinds == (DENSE, SPARSE, SPARSE)
    got = _leaves(jax.eval_shape(lambda: _model().init(
        jax.random.PRNGKey(0), _ids(0)[0]))["params"])
    want = _leaves(ref.init_params(TINY, 0))
    assert {k: v.shape for k, v in got.items()} == {
        k: v.shape for k, v in want.items()}
    # the dense layer has an MLP and no router; the rotary key is one head
    assert "['layers_0']['mlp']['down_proj']['kernel']" in got
    assert not any("layers_0']['moe" in k for k in got)
    assert got["['layers_1']['attn']['kv_a_proj']['kernel']"].shape == (
        64, 32 + 8)


def test_logits_logprobs_and_routing_are_the_references():
    """float32 on both sides: what differs is the order of sums (the chunked
    head, the walk), so the logits and log-probabilities agree to 1e-5 and
    the top-3 choice exactly."""
    params = ref.init_params(TINY, 3)
    ids, labels = _ids(3)
    model = _model()
    assert model.num_params() == ref.num_params(TINY) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    lp, chosen, counters = jax.jit(model.logprobs)(params, ids, labels)
    hidden, _ = jax.jit(lambda p, x: model.apply({"params": p}, x))(params,
                                                                    ids)
    slots = 0
    for b in range(2):
        want_lp, picked = ref.token_logprobs(params, TINY, ids[b], labels[b])
        np.testing.assert_allclose(lp[b], want_lp, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(chosen)[:, b], picked)
        np.testing.assert_allclose(
            hidden[b] @ params["lm_head_kernel"],
            ref.logits(params, TINY, ids[b]), rtol=1e-4, atol=1e-5)
        slots += int(np.asarray(picked).sum())
    assert np.asarray(chosen).shape == (2, 2, 64, 4)    # the sparse layers
    assert int(counters["mla_layer_applications"]) == 3
    assert int(counters["dense_mlp_layer_applications"]) == 1
    assert int(counters["moe_layer_applications"]) == 2
    assert int(counters["shared_expert_layer_applications"]) == 2
    assert float(counters["moe_slots_held"]) == pytest.approx(slots / 2)
    assert int(counters["moe_slots_dropped"]) == 0


def test_loss_gradients_and_an_adam_update_are_the_references():
    """The loss (cross entropy + the balance terms) to 1e-6, every leaf's
    gradient to 1e-4 of the reference's largest entry of that leaf, and one
    Adam step of the runner's plain first step from them."""
    params = ref.init_params(TINY, 5)
    ids, labels = _ids(5)
    model = _model()
    (loss, counters), grads = jax.jit(jax.value_and_grad(
        model.loss_fn(), has_aux=True))(params, {"input_ids": ids,
                                                 "labels": labels})
    want_loss, want, _, _ = ref.loss_and_grads(params, TINY, ids, labels)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    # the balance terms are in the loss, and counted: about alpha a layer
    balance = float(counters["moe_balance_loss"])
    assert 1.5e-4 < balance < 4e-4
    lp = jax.jit(model.logprobs)(params, ids, labels)[0]
    np.testing.assert_allclose(float(loss), balance - float(jnp.mean(lp)),
                               rtol=1e-6)
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


def test_the_references_chain_rule_is_jax_grad_of_its_own_loss():
    params = ref.init_params(TINY, 7)
    ids, labels = _ids(7, s=32)
    loss, grads, first_lp, picked = ref.loss_and_grads(params, TINY, ids,
                                                       labels)
    want_loss, want = jax.value_and_grad(ref.loss)(params, TINY, ids, labels)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for (name, g), w in zip(_leaves(grads).items(), _leaves(want).values()):
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=1e-6 * max(1.0, float(jnp.abs(w).max())),
            err_msg=name)
    np.testing.assert_allclose(
        first_lp, ref.token_logprobs(params, TINY, ids[0], labels[0])[0],
        rtol=1e-6)
    assert picked.shape == (2, 2, 32, 4)


def test_a_lower_precision_and_a_mechanism_left_out_move_the_reference():
    """What the cell's controls stand on: the reference in fp8, in fp8 in
    the attention's products alone and in the up-projection alone, and with
    each mechanism left out, is another function."""
    params = ref.init_params(TINY, 9)
    ids, labels = _ids(9, b=1)
    sound = ref.token_logprobs(params, TINY, ids[0], labels[0])[0]
    for changed in (dict(precision="fp8"),
                    dict(precision="fp8", low="products"),
                    dict(precision="fp8", low="up_projection"),
                    *(dict(without=(m,)) for m in ref.MECHANISMS)):
        moved = ref.token_logprobs(params, TINY, ids[0], labels[0],
                                   **changed)[0]
        assert float(jnp.sqrt(jnp.mean((moved - sound) ** 2))) > 1e-4, changed
    with pytest.raises(ValueError, match="without"):
        ref.token_logprobs(params, TINY, ids[0], labels[0],
                           without=("the_window",))
    with pytest.raises(ValueError, match="low"):
        ref.token_logprobs(params, TINY, ids[0], labels[0], low="some")


def test_the_cuts_arithmetic_at_the_published_preset():
    """ISSUE 61's sizing, from shapes alone (nothing is allocated): the
    program's, the reference's and the configuration file's counts agree."""
    model = _model(CELL, seq_len=8192, ce_chunk_tokens=2048)
    assert model.config == MoonlightConfig.moonlight_16b_a3b(
        layers_held=6, first_layer_held=0, routed_experts_held=8,
        first_expert_held=0, vocab_rows_held=20480)
    assert model.config.kinds == (DENSE,) + (SPARSE,) * 5
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    held = sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(shapes))
    assert held == model.num_params() == ref.num_params(CELL) == 668_890_432
    assert held == CELL["sizing"]["held_params"]
    attention = 2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048
    assert attention == 13_763_072
    assert model.attention_params() == ref.attention_params(CELL) == (
        attention - 512)
    dense = attention + 4096 + 3 * 2048 * 11264
    sparse = (attention + 4096 + 131_072 + 64 + 3 * 2048 * 2816
              + 8 * 3 * 2048 * 1408)
    assert (dense, sparse) == (82_973_184, 100_405_824)
    assert held == dense + 5 * sparse + 2 * 20480 * 2048 + 2048
    # five layers, the rule's other depth; and the whole model: the card's 16B
    five = Moonlight(MoonlightConfig.moonlight_16b_a3b(
        layers_held=5, routed_experts_held=8, vocab_rows_held=20480))
    assert five.num_params() == 568_484_608
    whole = Moonlight(MoonlightConfig.moonlight_16b_a3b())
    assert whole.num_params() == dense + 26 * (
        sparse + 56 * 3 * 2048 * 1408) + 2 * 163840 * 2048 + 2048
    assert round(whole.num_params() / 1e9, 2) == 15.96
    # 439.2M matmul weight-equivalents a token, of which MLA is 208.4M
    per_token = model.flops_per_token()
    assert per_token == ref.flops_per_token(CELL, 8192) == pytest.approx(
        6 * 439.2e6, rel=1e-3)
    kernel = 3 * 16 * (192 + 128) * 8192
    assert 6 * (6 * (attention - 512)) + 6 * kernel == pytest.approx(
        6 * 208.4e6, rel=1e-3)
    assert ref.flops_per_token(CELL, 8192, 1.0) - per_token == (
        6 * 5 * 0.25 * 3 * 2048 * 1408)
    assert ref.even_slots_per_token(CELL) == 0.75


def test_no_cast_paths_and_partition_rules_name_leaves_that_exist():
    import re

    model = _model()
    names = ["/".join(str(k.key) for k in path) for path, _ in
             jax.tree_util.tree_leaves_with_path(ref.init_params(TINY, 0))]
    for pattern in model.no_cast_paths():
        assert any(re.search(pattern, n) for n in names), pattern
    float32 = [n for n in names
               if any(re.search(p, n) for p in model.no_cast_paths())]
    assert {n.split("/")[-1] for n in float32} == {
        "embedding", "router_kernel", "selection_bias", "input_norm_scale",
        "post_norm_scale", "kv_a_norm_scale", "final_norm_scale"}
    for pattern, _ in model.param_partition_rules():
        assert any(re.search(pattern, n) for n in names), pattern


def test_a_recomputed_layer_keeps_the_kernels_residuals_and_the_plan():
    assert Moonlight.saved_by_remat == SAVED_BY_REMAT + (
        dropless.PLAN_SAVED_BY_REMAT,)
    params = ref.init_params(TINY, 11)
    ids, labels = _ids(11)
    batch = {"input_ids": ids, "labels": labels}
    plain, again = (jax.jit(jax.grad(lambda p, m=_model(remat=remat):
                                     m.loss_fn()(p, batch)[0]))(params)
                    for remat in (False, True))
    for (name, g), w in zip(_leaves(again).items(), _leaves(plain).values()):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-7, err_msg=name)


def test_trains_through_the_engine_under_the_warm_up():
    import deeperspeed_tpu as dst
    from deeperspeed_tpu.parallel.topology import MeshTopology

    traffic = dict(TRAFFIC, dtype="bfloat16", remat=True, zero_stage=0,
                   grad_accum=1, clip=1.0, optimizer={
                       "type": "Adam", "lr": 1e-3, "betas": [0.9, 0.999],
                       "eps": 1e-8},
                   scheduler={"type": "WarmupLR", "params": {
                       "warmup_min_lr": 1e-4, "warmup_max_lr": 1e-3,
                       "warmup_num_steps": 4, "warmup_type": "linear"}})
    model = _model(**traffic)
    engine, _, _, _ = dst.initialize(
        model=model, model_parameters=ref.init_params(TINY, 13),
        mesh=MeshTopology(devices=jax.devices()[:1]),
        config=runner.engine_config(traffic, 13))
    ids, labels = _ids(13)
    losses = [float(engine.train_batch(batch={"input_ids": ids,
                                              "labels": labels}))
              for _ in range(6)]
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    told = telemetry.step_counters()["train_step"]
    assert told["mla_layer_applications"] == 3
    assert told["moe_slots_dropped"] == 0
    assert 1e-4 < told["moe_balance_loss"] < 1e-3
    assert runner.layers_counted(TINY, told)

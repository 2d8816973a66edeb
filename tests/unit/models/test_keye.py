"""Keye (``models/keye.py``) against the plain reference
(``benchmarks/reference/keye_ref.py``) at a tiny size on the CPU, the
kernels on (interpret mode) and off: log-probabilities, routed sets, chosen
keys, both loss terms, the gradient of every parameter and one Adam update;
the indexer's leaves see the indexer's loss alone and every other leaf the
LM loss alone; the eight expert shares of a layer add up to the uncut
reference's layer (attention, indexer, router and norms counted once);
rotary by three axes is plain rotary where the axes coincide and another
where they do not; the cut's arithmetic; a recomputed layer keeps what is
made once a step; and the model through ``dst.initialize`` / ``engine.train_batch`` under a warm-up.  (Each
mechanism left out is another model: ``test_keye_mechanisms.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core
from benchmarks.reference import keye_ref as ref
from deeperspeed_tpu import telemetry
from deeperspeed_tpu.models.keye import Keye, KeyeAttention, KeyeConfig
from deeperspeed_tpu.models.mellum import MellumMoE
from deeperspeed_tpu.ops.attention import dsa, pallas_dsa
from deeperspeed_tpu.ops.transformer.rope import (mrope_tables,
                                                  rotary_tables)

runner = core.load_runner("train_dsa_moe")
TINY = core.load_json(core.BENCH_DIR + "/configs/tiny-keye-rehearsal.json")
CELL = core.load_json(core.BENCH_DIR + "/configs/keye-vl-2.0-30b-a3b.json")
TRAFFIC = {"seq_len": 96, "micro_batch": 2, "dtype": "float32",
           "ce_chunk_tokens": 48}
FORMS = pytest.mark.parametrize("use_pallas", [False, True],
                                ids=["plain", "kernels"])


def _model(cfg=TINY, use_pallas=None, **traffic):
    model = runner.program_model(cfg, dict(TRAFFIC, **traffic))
    return Keye(model.config.__class__(**dict(
        model.config.__dict__, use_pallas=use_pallas)))


def _ids(seed, cfg=TINY, b=2, s=96):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg["vocab_size"], size=(b, s + 1), dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


def _leaves(tree):
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


@FORMS
def test_logprobs_routing_and_chosen_keys_are_the_references(use_pallas):
    """float32 on both sides: what differs is the order of sums (the
    kernels' tiles, the chunked head), so log-probabilities agree to 1e-5
    and both discrete choices exactly."""
    params = ref.init_params(TINY, 3)
    ids, labels = _ids(3)
    model = _model(use_pallas=use_pallas)
    assert model.num_params() == ref.num_params(TINY) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    lp, chosen, counters = jax.jit(model.logprobs)(params, ids, labels)
    words = jax.jit(model.selections)(params, ids)
    kl = 0.0
    for b in range(2):
        want_lp, part, picked, keys = ref.token_logprobs(
            params, TINY, ids[b], labels[b])
        kl += float(part) / 2
        np.testing.assert_allclose(lp[b], want_lp, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(chosen)[:, b], picked)
        mine = np.stack([np.asarray(pallas_dsa.unpack_rows(w, 1))[b, :96, :96]
                         for w in words])
        np.testing.assert_array_equal(mine, np.asarray(ref.unpack(keys, 96)))
    np.testing.assert_allclose(counters["dsa_indexer_kl"], kl, rtol=1e-5)
    assert int(counters["dsa_pairs_selected"]) == runner.pairs_expected(
        TINY, 2, 96) == 2 * 2 * (16 * 17 // 2 + 80 * 16)
    assert int(counters["dsa_layer_applications"]) == 2
    assert int(counters["moe_layer_applications"]) == 2
    assert int(counters["dsa_tiles_skipped"]) == 0
    # one tile of 128 x 128 a sequence and layer
    assert int(counters["dsa_pairs_visited"]) == 2 * 2 * 128 * 128


@FORMS
def test_both_losses_gradients_and_an_adam_update_are_the_references(
        use_pallas):
    """The two loss terms to 1e-6, every leaf's gradient to 1e-4 of the
    reference's largest entry of that leaf (float32; the reference's chain
    rule a layer at a time is ``jax.grad`` of its own loss: held below), and
    one Adam step of the runner's plain first step from them."""
    params = ref.init_params(TINY, 5)
    ids, labels = _ids(5)
    model = _model(use_pallas=use_pallas)
    (total, told), grads = jax.jit(jax.value_and_grad(
        model.loss_fn(), has_aux=True))(params, {"input_ids": ids,
                                                 "labels": labels})
    (lm, kl), want, _, _, _ = ref.loss_and_grads(params, TINY, ids, labels)
    np.testing.assert_allclose(told["lm_loss"], lm, rtol=1e-6)
    np.testing.assert_allclose(told["dsa_indexer_kl"], kl, rtol=1e-5)
    np.testing.assert_allclose(total, lm + kl, rtol=1e-6)
    got, want_leaves = _leaves(grads), _leaves(want)
    assert set(got) == set(want_leaves)
    for name, g in want_leaves.items():
        np.testing.assert_allclose(
            got[name], g, rtol=1e-3, atol=1e-4 * float(jnp.abs(g).max()),
            err_msg=name)
    traffic = {"optimizer": {"lr": 1e-3, "betas": [0.9, 0.999], "eps": 1e-8},
               "clip": 1.0}
    got_step, want_step = (runner.plain_first_step(TINY, traffic, params, g)
                           for g in (grads, want))
    # Adam's first step is lr * g / (|g| + eps): where g is next to nothing
    # its sign is rounding's, so the update is compared as the benchmark
    # compares it, over the entries whose gradient is sure
    init = runner.train.sample_leaves(params, runner.sampled_tops(TINY))
    told = runner.train.compare_first_step(got_step, want_step, init)
    assert told["grad_rel_err"] < 1e-5
    assert told["adam_update_rel_err"] < 1e-3


def test_the_reference_by_layers_is_jax_grad_of_its_own_loss():
    params = ref.init_params(TINY, 9)
    ids, labels = _ids(9)
    (lm, kl), grads, _, _, _ = ref.loss_and_grads(params, TINY, ids, labels)
    want_lm, want_kl = ref.loss(params, TINY, ids, labels)
    np.testing.assert_allclose([lm, kl], [want_lm, want_kl], rtol=1e-6)
    want = jax.grad(lambda p: sum(ref.loss(p, TINY, ids, labels)))(params)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))


@FORMS
def test_each_loss_reaches_its_own_leaves_alone(use_pallas):
    """The indexer's leaves have zero gradient under ``L_LM`` alone and
    every other leaf zero under the indexers' loss alone: the indexer reads
    the sublayer's input with no gradient through it, and imitates the main
    attention's probabilities with none through them."""
    params = ref.init_params(TINY, 13)
    ids, labels = _ids(13)
    batch = {"input_ids": ids, "labels": labels}
    model = _model(use_pallas=use_pallas)

    def parts(p):
        hidden, told = model.apply({"params": p}, ids)
        ce, _ = model.head_loss(hidden, p["lm_head_kernel"], batch)
        return ce, sum(t["indexer_kl"] for t in told)

    by_lm = _leaves(jax.jit(jax.grad(lambda p: parts(p)[0]))(params))
    by_kl = _leaves(jax.jit(jax.grad(lambda p: parts(p)[1]))(params))
    indexer = [n for n in by_lm if ref.INDEXER in n]
    assert len(indexer) == 2 * 5
    for name in by_lm:
        mine, other = ((by_kl, by_lm) if name in indexer else (by_lm, by_kl))
        assert np.asarray(mine[name]).any(), name
        assert not np.asarray(other[name]).any(), name


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """Eight expert shares (two experts each of the tiny model's sixteen),
    as the program computes them, are the uncut reference's layer: a share's
    routed sum holds its own experts' terms, and what every chip computes
    alike -- the norms, the attention with its indexer, the router -- is
    counted once."""
    whole = {k: v for k, v in TINY.items()
             if k not in ("routed_experts_held", "first_expert_held")}
    sh = ref.share(whole)
    assert sh["experts"] == 16
    p = ref.init_params(whole, 23)["layers_0"]
    x = jnp.asarray(np.random.default_rng(23).standard_normal(
        (96, whole["hidden_size"])), jnp.float32)
    (want, want_kl), (want_picked, want_keys) = ref._layer(
        x, p, whole, sh, "float32")
    eps = whole["rms_norm_eps"]

    def share_cfg(**held):
        return runner.program_model(dict(whole, **held), TRAFFIC).config

    u = ref._rms_norm(x, p["input_norm_scale"], eps)
    attended, said = KeyeAttention(share_cfg()).apply(
        {"params": p["attn"]}, u[None])
    np.testing.assert_allclose(said["indexer_kl"], want_kl / 96, rtol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(pallas_dsa.unpack_rows(said["selection"], 1))[0, :96, :96],
        np.asarray(ref.unpack(want_keys, 96)))
    h = x + attended[0]
    m = ref._rms_norm(h, p["post_norm_scale"], eps)
    out, slots = 0.0, 0
    for e in range(8):
        mine = {"router_kernel": p["moe"]["router_kernel"],
                "experts_gate_up_proj": p["moe"]["experts_gate_up_proj"][
                    2 * e:2 * e + 2],
                "experts_down_proj": p["moe"]["experts_down_proj"][
                    2 * e:2 * e + 2]}
        part, counters, chosen = MellumMoE(
            share_cfg(routed_experts_held=2, first_expert_held=2 * e)).apply(
                {"params": mine}, m[None])
        np.testing.assert_array_equal(
            np.asarray(chosen[0]), np.asarray(want_picked)[:, 2 * e:2 * e + 2])
        assert int(counters["slots"]) == int(counters["done"])
        out, slots = out + part[0], slots + int(counters["slots"])
    assert slots == 96 * whole["num_experts_per_tok"]
    np.testing.assert_allclose(h + out, want, rtol=1e-4, atol=3e-5)


def test_rotary_by_three_axes():
    """Where the three axes' positions coincide the tables are plain
    rotary's; where they do not, each frequency pair follows its own axis
    (the sections deal the pairs out in order), as the reference's."""
    text = jnp.broadcast_to(jnp.arange(40), (3, 2, 40))
    cos, sin = mrope_tables(text, (2, 3, 3), 16, 1e7)
    want_cos, want_sin = rotary_tables(text[0], 16, 1e7)
    np.testing.assert_allclose(cos, want_cos, rtol=1e-6)
    np.testing.assert_allclose(sin, want_sin, rtol=1e-6)
    image = text.at[1].set(text[1] // 4).at[2].set(text[2] % 4)
    cos2, sin2 = mrope_tables(image, (2, 3, 3), 16, 1e7)
    assert np.abs(np.asarray(cos2 - cos)).max() > 0.1
    # pairs 0-1 temporal (unchanged), 2-4 height, 5-7 width
    np.testing.assert_allclose(cos2[..., :2], cos[..., :2], rtol=1e-6)
    ref_cos, ref_sin = ref.rotary(1e7, image[:, 0], 16, (2, 3, 3))
    np.testing.assert_allclose(cos2[0, :, 0], ref_cos, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(sin2[0, :, 0], ref_sin, rtol=1e-5, atol=1e-6)
    by_axis = [rotary_tables(image[a], 16, 1e7)[0] for a in range(3)]
    for pair, axis in enumerate((0, 0, 1, 1, 1, 2, 2, 2)):
        np.testing.assert_allclose(cos2[..., pair], by_axis[axis][..., pair],
                                   rtol=1e-6)
    with pytest.raises(ValueError, match="sections"):
        mrope_tables(text, (2, 3, 4), 16, 1e7)


def test_the_cut_and_its_arithmetic():
    """The cell's share: six layers of 96,899,456 and two tables' slices,
    659,190,016 parameters, equal to the reference's count and to the
    configuration's ``sizing``; the FLOPs a token by hand."""
    model = runner.program_model(CELL, dict(TRAFFIC, seq_len=16384,
                                            ce_chunk_tokens=2048))
    cfg = model.config
    assert (len(cfg.kinds), cfg.experts, cfg.vocab_rows,
            cfg.first_layer_held) == (6, 16, 18992, 18)
    layer = (2048 * 4096 * 2 + 2 * 2048 * 512 + 256            # attention
             + 2048 * 1024 + 2048 * 64 + 128 + 2048 * 16       # indexer
             + 2048 * 128 + 2 * 2048                           # router, norms
             + 16 * 3 * 2048 * 768)
    assert layer == CELL["sizing"]["layer_held"] == 96899456
    assert model.num_params() == ref.num_params(CELL) == CELL["sizing"][
        "held_params"] == 6 * layer + 2 * 18992 * 2048 + 2048 == 659190016
    chosen = 2048 * 2049 // 2 + (16384 - 2048) * 2048
    causal = 16384 * 16385 // 2
    assert model.pairs() == ref.pairs(CELL, 16384) == (chosen, causal)
    matmul = 6 * (model.layer_matmul_params() + 1.0 * 3 * 2048 * 768) \
        + 2048 * 18992
    by_hand = 6 * matmul + 6 * (14 * 4096 * chosen
                                + 6 * 1024 * causal) / 16384
    assert model.flops_per_token() == pytest.approx(by_hand)
    assert ref.flops_per_token(CELL, 16384, 1.0) == pytest.approx(by_hand)
    with pytest.raises(ValueError, match="outside"):
        KeyeConfig.tiny(layers_held=2, first_layer_held=1).kinds


def test_a_recomputed_layer_keeps_what_is_made_once_a_step():
    """Under the model's remat policy the gradient program holds the
    attention's output and log-sum-exp, the packed selection and the
    indexer's gradients as saved residuals: counted in a fresh trace of the
    jaxpr (nothing process-wide is touched)."""
    model = Keye(KeyeConfig.tiny(remat=True, use_pallas=True))
    assert set(model.saved_by_remat) >= set(dsa.SAVED_BY_REMAT)
    ids, labels = _ids(17)
    params = ref.init_params(TINY, 17)
    before = {k: dict(v) for k, v in telemetry.kernel_paths().items()}
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: model.loss_fn()(p, {"input_ids": ids,
                                      "labels": labels})[0]))(params))
    after = telemetry.kernel_paths()

    def traced(kernel, path):
        return after.get(kernel, {}).get(path, 0) - before.get(
            kernel, {}).get(path, 0)

    # a layer's call is traced once for the forward and once again inside
    # the remat wrap's recomputation
    assert traced("dsa_attention", "grouped_2") >= 2
    assert traced("dsa_select", "pallas") >= 2
    assert traced("dsa_head_probs", "pallas") >= 2
    assert traced("dsa_loss_grads", "pallas") >= 2
    assert dsa.GRADS_SAVED in dsa.SAVED_BY_REMAT
    for name in dsa.SAVED_BY_REMAT:
        assert f"name={name}" in text, name


def test_trains_through_the_engine_under_a_warm_up():
    import deeperspeed_tpu as dst
    from deeperspeed_tpu.parallel.topology import MeshTopology

    model = Keye(KeyeConfig.tiny(remat=True, dtype=jnp.bfloat16))
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
    assert told["dsa_layer_applications"] == 2
    assert told["moe_layer_applications"] == 2
    assert told["dsa_pairs_selected"] == 2 * 2 * (136 + 80 * 16)
    assert told["moe_slots_dropped"] == 0 and told["moe_slots_held"] > 0
    assert told["lm_loss"] + told["dsa_indexer_kl"] == pytest.approx(
        losses[-1], rel=1e-3)
    mask = engine._no_cast_mask(engine.state["master_params"])
    kept = {jax.tree_util.keystr(p) for p, m in
            jax.tree_util.tree_leaves_with_path(mask) if m}
    assert any("router_kernel" in k for k in kept)
    assert any("embed_tokens" in k for k in kept)
    assert not any("experts" in k or "q_proj" in k or "index" in k
                   for k in kept)

"""Mellum 2 (``models/mellum.py``) against the plain reference
(``benchmarks/reference/mellum_ref.py``) at a tiny size on the CPU:
log-probabilities, routed sets and gradients for each kind of layer alone
and for a period; the four expert shares add up to the uncut reference's
layer (router counted once); YaRN's frequencies against numbers worked by
hand; every windowed call of the model goes to the kernel; the grouped
walk's bodies in the lowered gradient program do not grow with the layers;
and the model through ``dst.initialize`` / ``engine.train_batch`` under a
warm-up."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core
from benchmarks.reference import mellum_ref as ref
from deeperspeed_tpu import telemetry
from deeperspeed_tpu.models.mellum import (FULL, SLIDING, Mellum,
                                           MellumConfig, MellumMoE, Rope)
from deeperspeed_tpu.ops.transformer.rope import yarn_inv_freq

runner = core.load_runner("train_swa_moe")
TINY = core.load_json(core.BENCH_DIR + "/configs/tiny-mellum-rehearsal.json")
CELL = core.load_json(core.BENCH_DIR + "/configs/mellum2-12b-a2.5b.json")
TRAFFIC = {"seq_len": 40, "micro_batch": 2, "dtype": "float32",
           "ce_chunk_tokens": 48}
PERIOD = [SLIDING, SLIDING, SLIDING, FULL]


def _cfg(kinds, **more):
    return dict(TINY, layer_types=list(kinds),
                mlp_layer_types=["sparse"] * len(kinds), **more)


def _ids(seed, cfg, b=2, s=40):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg["vocab_size"], size=(b, s + 1), dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


# --------------------------------------------------- model against reference
@pytest.mark.parametrize("kinds", [[SLIDING], [FULL], PERIOD],
                         ids=["sliding", "full", "period"])
def test_logprobs_and_routing_are_the_references(kinds):
    cfg = _cfg(kinds)
    params = ref.init_params(cfg, 11)
    ids, labels = _ids(11, cfg)
    model = runner.program_model(cfg, TRAFFIC)
    got_lp, got_chosen, counters = jax.jit(model.logprobs)(params, ids, labels)
    for b in range(2):
        want_lp, want_chosen = ref.token_logprobs(params, cfg, ids[b],
                                                  labels[b])
        np.testing.assert_allclose(got_lp[b], want_lp, rtol=2e-4, atol=2e-4)
        np.testing.assert_array_equal(np.asarray(got_chosen)[:, b],
                                      np.asarray(want_chosen))
    assert int(counters["window_layer_applications"]) == kinds.count(SLIDING)
    assert int(counters["full_layer_applications"]) == kinds.count(FULL)
    assert int(counters["moe_layer_applications"]) == len(kinds)
    assert float(counters["moe_slots_dropped"]) == 0.0
    assert float(counters["moe_slots_held"]) == pytest.approx(
        np.asarray(got_chosen).sum() / len(kinds))


@pytest.mark.parametrize("kinds", [[SLIDING], [FULL], PERIOD],
                         ids=["sliding", "full", "period"])
def test_loss_and_gradients_are_the_references(kinds):
    cfg = _cfg(kinds)
    params = ref.init_params(cfg, 13)
    ids, labels = _ids(13, cfg)
    model = runner.program_model(cfg, TRAFFIC)
    (loss, _), grads = jax.jit(jax.value_and_grad(
        model.loss_fn(), has_aux=True))(params, {"input_ids": ids,
                                                 "labels": labels})
    want_loss, want, _, _ = ref.loss_and_grads(params, cfg, ids, labels)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    got_flat = dict(jax.tree_util.tree_leaves_with_path(grads))
    for path, w in jax.tree_util.tree_leaves_with_path(want):
        scale = max(float(jnp.max(jnp.abs(w))), 1e-8)
        np.testing.assert_allclose(np.asarray(got_flat[path]) / scale,
                                   np.asarray(w) / scale, rtol=0, atol=3e-4,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_window_matters_and_a_model_without_it_is_another_model():
    """The windowed layers' log-probabilities differ from the same weights'
    with every layer full (what the runner's third control computes), from
    the first row past the window on and not before."""
    cfg = _cfg(PERIOD)
    params = ref.init_params(cfg, 17)
    ids, labels = _ids(17, cfg)
    windowed, _ = ref.token_logprobs(params, cfg, ids[0], labels[0])
    full, _ = ref.token_logprobs(params, cfg, ids[0], labels[0],
                                 every_layer_full=True)
    w = cfg["sliding_window"]
    np.testing.assert_allclose(windowed[:w], full[:w], rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(windowed[w:] - full[w:]))) > 1e-3
    model = runner.program_model(cfg, TRAFFIC)
    got = jax.jit(model.logprobs)(params, ids[:1], labels[:1])[0][0]
    assert (runner.train.compare_logprobs(got, windowed)
            < 0.01 * runner.train.compare_logprobs(got, full))


def test_the_reference_by_blocks_is_jax_grad_of_its_own_logprobs():
    cfg = _cfg([SLIDING, FULL])
    params = ref.init_params(cfg, 19)
    ids, labels = _ids(19, cfg)

    def mean_loss(p):
        with jax.default_matmul_precision("highest"):
            return -jnp.mean(jnp.stack([ref.token_logprobs(
                p, cfg, ids[b], labels[b])[0] for b in range(2)]))

    want_loss, want = jax.value_and_grad(mean_loss)(params)
    loss, grads, first, picked = ref.loss_and_grads(params, cfg, ids, labels)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert picked.shape == (2, 2, 40, cfg["routed_experts_held"])
    np.testing.assert_allclose(
        first, ref.token_logprobs(params, cfg, ids[0], labels[0])[0],
        rtol=1e-6, atol=1e-6)
    for (path, g), (_, w) in zip(jax.tree_util.tree_leaves_with_path(grads),
                                 jax.tree_util.tree_leaves_with_path(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))


# ------------------------------------------------------------------- shares
def test_the_four_expert_shares_add_up_to_the_uncut_layer():
    """Every share routes over all 16 experts and computes its four; the
    parts of the four shares are the uncut reference's layer: there is no
    shared expert to count once, and the router, which every share holds
    alike, is counted once because a share's output holds only its own
    experts' terms."""
    cfg = _cfg([FULL], routed_experts_held=16, first_expert_held=0)
    p = ref.init_params(cfg, 23)["layers_0"]["moe"]
    rng = np.random.default_rng(23)
    u = jnp.asarray(rng.standard_normal((32, cfg["hidden_size"])), jnp.float32)
    want, want_picked = ref.moe(u, p, cfg, ref.share(cfg))
    total, slots = 0.0, 0
    for j in range(4):
        held = runner.program_model(
            dict(cfg, routed_experts_held=4, first_expert_held=4 * j),
            TRAFFIC).config
        mine = dict(p, experts_gate_up_proj=p["experts_gate_up_proj"][
            4 * j:4 * j + 4], experts_down_proj=p["experts_down_proj"][
                4 * j:4 * j + 4])
        out, counters, chosen = MellumMoE(held).apply({"params": mine},
                                                      u[None])
        assert int(counters["slots"]) == int(counters["done"]) == int(
            chosen.sum())
        np.testing.assert_array_equal(np.asarray(chosen[0]),
                                      np.asarray(want_picked)[:, 4 * j:4 * j + 4])
        total, slots = total + out[0], slots + int(counters["slots"])
    assert slots == 32 * cfg["num_experts_per_tok"]
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=2e-5)
    # and the reference's own shares add up the same way
    parts = sum(ref.moe(u, dict(
        p, experts_gate_up_proj=p["experts_gate_up_proj"][lo:lo + 8],
        experts_down_proj=p["experts_down_proj"][lo:lo + 8]), cfg,
        dict(ref.share(cfg), experts=8, first_expert=lo))[0] for lo in (0, 8))
    np.testing.assert_allclose(parts, want, rtol=1e-4, atol=2e-5)


def test_the_cut_and_its_arithmetic():
    model = runner.program_model(CELL, dict(TRAFFIC, seq_len=8192,
                                            ce_chunk_tokens=2048))
    cfg = model.config
    assert cfg.kinds == (SLIDING, SLIDING, SLIDING, FULL)
    assert (cfg.experts, cfg.vocab_rows, cfg.first_layer_held) == (
        16, 24576, 12)
    layer = 21_233_664 + 147_456 + 4_608 + 16 * 6_193_152
    assert layer == 120_476_160
    assert model.num_params() == 4 * layer + 2 * 24_576 * 2304 + 2304 \
        == 595_153_152 == ref.num_params(CELL)
    whole = Mellum(MellumConfig.mellum2_12b())
    assert whole.config.kinds.count(SLIDING) == 21
    assert whole.config.kinds.count(FULL) == 7
    assert 12.1e9 < whole.num_params() < 12.2e9
    assert model.flops_per_token(2.0) == pytest.approx(
        ref.flops_per_token(CELL, 8192, 2.0))
    assert model.flops_per_token() == model.flops_per_token(8 * 16 / 64)


# -------------------------------------------------------------------- rotary
def test_yarn_frequencies_by_hand():
    """d = 128, theta = 500,000, factor 16 over 8192: c(32) = 128 ln(8192 /
    (64 pi)) / (2 ln 500000) = 18.08, c(1) = 128 ln(8192 / (2 pi)) / (2 ln
    500000) = 34.98, so low 18, high 35: the first 19 frequencies are plain,
    those from the 36th on are divided by 16, the ramp between is linear."""
    d, theta = 128, 500000.0
    c32 = d * math.log(8192 / (32 * 2 * math.pi)) / (2 * math.log(theta))
    c1 = d * math.log(8192 / (2 * math.pi)) / (2 * math.log(theta))
    assert (math.floor(c32), math.ceil(c1)) == (18, 35)
    for make in (yarn_inv_freq, ref.yarn_inv_freq):
        got = np.asarray(make(d, theta, 16.0, 8192, 32, 1), np.float64)
        plain = theta ** (-2 * np.arange(64) / d)
        np.testing.assert_allclose(got[:19], plain[:19], rtol=1e-6)
        np.testing.assert_allclose(got[35:], plain[35:] / 16, rtol=1e-6)
        # i = 26: ramp 8 / 17
        ramp = 8 / 17
        np.testing.assert_allclose(
            got[26], plain[26] * (ramp / 16 + 1 - ramp), rtol=1e-6)
        assert np.all(np.diff(got) < 0)
    rope = CELL["rope_parameters"]["full_attention"]
    cos, sin = ref.rotary(CELL, FULL, jnp.arange(4))
    assert float(cos[0, 0]) == pytest.approx(rope["attention_factor"])
    assert float(sin[0, 0]) == 0.0
    cos_s, _ = ref.rotary(CELL, SLIDING, jnp.arange(4))
    assert float(cos_s[0, 0]) == 1.0
    # the program's tables are the reference's
    got_cos, got_sin = Rope(factor=16.0, attention_factor=rope[
        "attention_factor"]).tables(jnp.arange(4)[None], 128, jnp.float32)
    np.testing.assert_allclose(got_cos[0, :, 0], cos, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_sin[0, :, 0], sin, rtol=1e-6, atol=1e-6)


# ----------------------------------------------------------------- the kernel
def test_every_windowed_call_of_the_model_takes_the_kernel(monkeypatch):
    """With the accelerator's kernels on, the model's three windowed layers
    count three ``flash_attention_window`` calls and its full layer one
    ``flash_attention`` call (interpret mode here), and the result is the
    plain path's."""
    from deeperspeed_tpu.accelerator import get_accelerator

    cfg = _cfg(PERIOD, num_attention_heads=2, num_key_value_heads=1,
               head_dim=64, hidden_size=128)
    params = ref.init_params(cfg, 29)
    ids, labels = _ids(29, cfg, b=1, s=256)
    model = runner.program_model(cfg, dict(TRAFFIC, seq_len=256))
    plain = model.logprobs(params, ids, labels)[0]
    monkeypatch.setattr(type(get_accelerator()), "use_pallas_kernels",
                        lambda self: True)
    # ``count_kernel_path`` counts when a call is TRACED: whatever this
    # process traced before (another model's test, the same shapes) must
    # not answer for the counted call from jit's cache
    jax.clear_caches()
    before = telemetry.kernel_paths()
    got = model.logprobs(params, ids, labels)[0]
    after = telemetry.kernel_paths()

    def calls(paths, kernel):
        return sum(paths.get(kernel, {}).values())

    # layers of one kind share one trace of the jitted entry point
    assert calls(after, "flash_attention_window") > calls(
        before, "flash_attention_window")
    assert calls(after, "flash_attention") > calls(before, "flash_attention")
    np.testing.assert_allclose(got, plain, rtol=2e-4, atol=2e-4)

    def kernels(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            # the kernel's jitted entry point (the fused norms are kernels
            # too: not counted)
            n += eqn.params.get("name") == "flash_attention"
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) else [
                        value]:
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        n += kernels(inner)
        return n

    # four attention layers, four kernel calls: none took the masked path
    assert kernels(jax.make_jaxpr(
        lambda p: model.apply({"params": p}, ids)[0])(params).jaxpr) == 4


def test_k_and_v_reach_the_kernels_at_their_kv_heads(kv_heads_go_to_the_kernel):
    """Heads of 128 (a head a lane block, as in the cell): a train step's
    windowed and full layers hand k and v to the kernel at the 2 KV heads
    of their 4 query heads, nothing copies them under ``attention_layout``
    or sums dk and dv after the kernel, and the loss and gradients are the
    plain path's on the copies."""
    cfg = _cfg(PERIOD, num_attention_heads=4, num_key_value_heads=2,
               head_dim=128, hidden_size=128)
    ids, labels = _ids(37, cfg, b=1, s=256)
    model = runner.program_model(cfg, dict(TRAFFIC, seq_len=256))
    counted = kv_heads_go_to_the_kernel(
        model.loss_fn(), ref.init_params(cfg, 37),
        {"input_ids": ids, "labels": labels}, [(4, 2, 128)])
    assert {kernel: set(paths) for kernel, paths in counted.items()
            if paths} == {
        "flash_attention_window_kv_heads": {"grouped_2"},
        "flash_attention_kv_heads": {"grouped_2"}}


# ---------------------------------------------------------------- the set-up
@pytest.fixture(scope="module")
def lowered_gradient_programs():
    """The recomputed model's gradient program, lowered and not compiled,
    with two layers and with four -> {layers: (bodies, calls) by function
    name}.  Widths of whole lane blocks, so that the expert layers walk the
    grouped form (its kernels in interpret mode here)."""
    def counted(layers):
        model = Mellum(MellumConfig.tiny(
            layer_types=(SLIDING,) * (layers - 1) + (FULL,), hidden_size=128,
            num_heads=2, num_kv_heads=1, head_dim=64,
            moe_intermediate_size=128, remat=True, dtype=jnp.bfloat16))
        loss = model.loss_fn()
        batch = model.example_batch(2, 64)
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), batch["input_ids"]))
        text = jax.jit(jax.grad(
            lambda p, b: loss(p["params"], b)[0])).lower(
                params, batch).as_text()

        def by_name(pattern):
            names = [re.sub(r"_\d+$", "", n)
                     for n in re.findall(pattern, text)]
            return {n: names.count(n) for n in set(names)}

        return (by_name(r"func\.func private @(\w+)\("),
                by_name(r"call @(\w+)\("))

    return {layers: counted(layers) for layers in (2, 4)}


@pytest.mark.parametrize("body", ["_grouped_forward", "_grouped_backward",
                                  "_grouped_plan"])
def test_the_grouped_walk_is_lowered_once_for_all_the_layers(
        lowered_gradient_programs, body):
    """What a cell's set-up pays for the walk must not grow with the layers
    (PERF.md section 6, PRs 40 and 41: traced in Python for every layer and
    pass, the walk cost the cell 17 % of ``setup_s``): the jitted forward,
    backward and plan are each ONE body of the lowered text whether the
    model has two layers or four, and every layer calls each once (the plan
    is kept for the recomputed layer, ``dropless.PLAN_SAVED_BY_REMAT``: its
    sorts are not made again)."""
    (two, calls_two), (four, calls_four) = (lowered_gradient_programs[2],
                                            lowered_gradient_programs[4])
    assert two[body] == four[body] == 1
    assert calls_two[body] == 2 and calls_four[body] == 4
    # and so are the kernels' jitted entry points inside them
    for kernel in ("grouped_matmul", "grouped_outer"):
        assert two[kernel] == four[kernel] > 0


# ------------------------------------------------------------ the engine
def test_trains_through_the_engine_under_a_warm_up():
    import deeperspeed_tpu as dst
    from deeperspeed_tpu.parallel.topology import MeshTopology

    model = Mellum(MellumConfig.tiny(remat=True, dtype=jnp.bfloat16))
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
    batch = model.example_batch(2, 40)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(5)]
    assert losses[-1] < losses[0]
    told = telemetry.step_counters()["train_step"]
    assert told["window_layer_applications"] == 2
    assert told["full_layer_applications"] == 1
    assert told["moe_layer_applications"] == 3
    assert told["moe_slots_dropped"] == 0 and told["moe_slots_held"] > 0
    mask = engine._no_cast_mask(engine.state["master_params"])
    kept = {jax.tree_util.keystr(p) for p, m in
            jax.tree_util.tree_leaves_with_path(mask) if m}
    assert any("router_kernel" in k for k in kept)
    assert any("embed_tokens" in k for k in kept)
    assert not any("experts" in k or "q_proj" in k for k in kept)

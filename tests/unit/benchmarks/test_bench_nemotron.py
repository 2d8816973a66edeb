"""The hybrid cell's part of the benchmark on the CPU: the configuration
file against the published row, the plain reference (seeded weights, the
recurrence, its controls: a lower precision must come out as not correct),
the runner's limits rule and sampled leaves, the new readers on hand-made
fixtures and on nothing, the hybrid FLOP count by hand, and the cell's
rehearsal."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core, hybrid_trace, program_trace
from benchmarks.reference import nemotron_h_ref as ref

runner = core.load_runner("train_hybrid")
TINY = core.load_json(core.BENCH_DIR + "/configs/tiny-nemotron-rehearsal.json")
CELL = core.load_json(core.BENCH_DIR
                      + "/configs/nemotron-3-super-120b-a12b.json")
NAME = "train-nemotron3-super-ep64-8k"
TRAFFIC = {"seq_len": 40, "micro_batch": 2, "dtype": "float32",
           "ce_chunk_tokens": 48, "clip": 1.0,
           "optimizer": {"lr": 1e-4, "betas": [0.9, 0.999], "eps": 1e-8}}
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
ROW = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 4096, "hybrid_override_pattern":
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEM*EMEMEMEME", "intermediate_size": 2688,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072}
HELD = {"layers_held": 11, "routed_experts_held": 8, "mamba_heads_held": 32,
        "mamba_groups_held": 2, "attention_heads_held": 8,
        "key_value_heads_held": 1, "vocab_rows_held": 16384,
        "mtp_layers_held": 0}


def _ids(seed, b=2, s=40):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, TINY["vocab_size"], size=(b, s + 1), dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


# ------------------------------------------------------- the configuration
def test_the_configuration_is_the_published_row_key_for_key():
    assert {k: CELL[k] for k in ROW} == ROW
    assert CELL["reduced"] == list(HELD)
    assert {k: CELL[k] for k in HELD} == HELD
    entry = next(c for c in core.load_manifest()["configs"]
                 if c["name"] == "nemotron-3-super-120b-a12b")
    assert entry["reduced"] == CELL["reduced"]
    assert entry["source"] == CELL["source"]
    for key in ("deployment", "assumed", "sizing", "distorts"):
        assert CELL[key]
    assert set(CELL["assumed"]) >= {
        "rotary", "latent", "selection_bias", "time_step", "gate_and_norm",
        "weights", "multi_token_prediction"}


def test_the_cut_is_one_period_and_a_share_of_every_layer():
    assert ref.pattern(CELL) == "EMEMEMEMEM*"
    assert ROW["hybrid_override_pattern"][26:37] == "EMEMEMEMEM*"
    assert ref.share(CELL) == {
        "mamba_heads": 32, "mamba_groups": 2, "q_heads": 8, "kv_heads": 1,
        "first_expert": 0, "experts": 8, "vocab": 16384}
    # a group still serves 16 heads, and normalises 1024 channels
    assert 32 // 2 == 128 // 8 and 32 * 64 // 2 == 8192 // 8 == 1024
    assert ref.mamba_widths(CELL, ref.share(CELL)) == (2048, 2560, 4640)


def test_the_sizing_table_by_hand():
    """ISSUE 34's arithmetic, from the reference's shapes."""
    sh = ref.share(CELL)
    mamba = sum(int(np.prod(s)) for s in ref.mixer_shapes(
        CELL, "M", sh).values())
    assert mamba == 4096 * 4640 + 2560 * 5 + 3 * 32 + 2048 + 2048 * 4096
    assert 27.40e6 < mamba < 27.42e6
    attention = sum(int(np.prod(s)) for s in ref.mixer_shapes(
        CELL, "*", sh).values())
    assert attention == 2 * 4096 * (8 + 1) * 128 == 9_437_184
    moe = ref.mixer_shapes(CELL, "E", sh)
    outside = sum(int(np.prod(s)) for k, s in moe.items()
                  if not k[0].startswith("experts"))
    assert outside == 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
    assert 54.52e6 < outside < 54.54e6
    assert ref.routed_expert_params(CELL) == 2 * 1024 * 2688 == 5_505_024
    assert ref.num_params(CELL) == 773_579_744
    assert 12.3e9 < 16 * ref.num_params(CELL) < 12.4e9
    model = runner.program_model(CELL, {"seq_len": 8192,
                                        "ce_chunk_tokens": 2048})
    assert model.num_params() == ref.num_params(CELL)
    even = 22 * 8 / 512
    assert model.flops_per_token() == pytest.approx(
        ref.flops_per_token(CELL, 8192, even))
    # about 3.1 GFLOP a token; the routed experts about 2 % of it
    total = ref.flops_per_token(CELL, 8192, even)
    assert 3.05e9 < total < 3.15e9
    assert 0.015 < 6 * 5 * even * 5_505_024 / total < 0.025


def test_hybrid_flops_by_hand():
    cfg = {"hybrid_override_pattern": "ME*", "hidden_size": 8,
           "vocab_size": 10, "mamba_num_heads": 2, "mamba_head_dim": 4,
           "n_groups": 1, "ssm_state_size": 3, "conv_kernel": 4,
           "chunk_size": 5, "num_attention_heads": 2,
           "num_key_value_heads": 1, "head_dim": 4, "n_routed_experts": 6,
           "moe_latent_size": 4, "moe_intermediate_size": 7,
           "moe_shared_expert_intermediate_size": 9}
    inner, conv = 8, 8 + 2 * 3
    mamba = 8 * (inner + conv + 2) + inner * 8                       # 256
    attention = 2 * 8 * (2 + 1) * 4                                  # 192
    moe = 8 * 6 + 2 * 8 * 4 + 2 * 8 * 9                              # 256
    assert [ref.layer_matmul_params(cfg, k) for k in "M*E"] == [
        mamba, attention, moe]
    scan = 2 * (2 * 5 * 4 + 4 * 4 * 3) + 1 * 2 * 5 * 3 + 2 * 4 * conv
    assert ref.scan_flops_per_token(cfg) == scan == 318
    want = (6 * (mamba + attention + moe + 0.5 * 2 * 4 * 7 + 8 * 10)
            + 3 * scan + 12 * 2 * 4 * 11)
    assert ref.flops_per_token(cfg, 11, 0.5) == want


# ------------------------------------------------------------ the reference
@pytest.mark.parametrize("seed", [1, 2**31 + 11])
def test_same_seed_same_weights(seed):
    a, b = ref.init_params(TINY, seed), ref.init_params(TINY, seed)
    other = ref.init_params(TINY, seed + 1)
    ka, kb, ko = (x["lm_head_kernel"] for x in (a, b, other))
    assert np.array_equal(ka, kb) and not np.array_equal(ka, ko)
    assert abs(float(jnp.std(ka)) - 0.02) < 0.002
    mamba = a["layers_1"]["mixer"]
    assert float(mamba["D"][0]) == 1.0 and float(a["final_norm_scale"][0]) == 1
    assert 0.0 <= float(mamba["A_log"].min()) and float(
        mamba["A_log"].max()) <= float(np.log(16.0))
    step = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert (step >= 0.001 * 0.999).all() and (step <= 0.1 * 1.001).all()
    assert float(jnp.abs(mamba["conv1d_kernel"]).max()) <= 0.5
    # the router is seeded like a matrix
    assert float(jnp.std(a["layers_0"]["mixer"]["router_kernel"])) > 0.01


def test_unknown_precision_is_an_error():
    with pytest.raises(ValueError):
        ref.hidden_states(ref.init_params(TINY, 4), TINY, _ids(4)[0][0],
                          "int3")


def test_one_sequence_gives_logprobs_and_the_sets_chosen():
    params = ref.init_params(TINY, 6)
    ids, labels = _ids(6)
    lp, picked = ref.token_logprobs(params, TINY, ids[0], labels[0])
    assert lp.shape == (40,) and picked.shape == (1, 40, 4)
    assert picked.dtype == bool and float(lp.max()) < 0.0
    # top-3 of 16 over 40 tokens: the four held experts get their share
    assert 0 < int(picked.sum()) <= 40 * 3


@pytest.mark.parametrize("precision", ["float32", "fp8"])
def test_gradients_by_blocks_are_the_gradients(precision):
    """``loss_and_grads`` goes back a layer at a time; ``jax.grad`` of the
    whole loss gives the same tree."""
    params = ref.init_params(TINY, 8)
    ids, labels = _ids(8)
    got_loss, got, lp, picked = ref.loss_and_grads(params, TINY, ids, labels,
                                                   precision)

    def whole(p):
        return -jnp.mean(jnp.stack([ref.token_logprobs(
            p, TINY, ids[b], labels[b], precision)[0] for b in range(2)]))

    want_loss, want = jax.value_and_grad(whole)(params)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    flat = dict(jax.tree_util.tree_leaves_with_path(want))
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        np.testing.assert_allclose(
            g, flat[path], rtol=1e-4,
            atol=1e-6 + 1e-4 * float(jnp.abs(flat[path]).max()),
            err_msg=jax.tree_util.keystr(path))
    assert lp.shape == (40,) and picked.shape == (2, 1, 40, 4)


def test_the_recurrence_by_hand():
    """Two steps of one head with a 1 x 1 state."""
    x = jnp.asarray([[[2.0]], [[3.0]]])
    dt = jnp.asarray([[0.5], [0.25]])
    a = jnp.asarray([-1.0])
    b = jnp.asarray([[[1.0]], [[2.0]]])
    c = jnp.asarray([[[1.0]], [[0.5]]])
    y = ref.recurrence(x, dt, a, b, c)
    h1 = 0.5 * 2.0 * 1.0
    h2 = np.exp(-0.25) * h1 + 0.25 * 3.0 * 2.0
    np.testing.assert_allclose(np.asarray(y).ravel(), [h1 * 1.0, h2 * 0.5],
                               rtol=1e-6)


# ------------------------------------------------------------- the controls
@pytest.mark.parametrize("seed", [21, 22, 23])
def test_control_fails_the_forward_comparisons(seed):
    """bf16 (the stated precision) against the float32 reference reads a
    number for the log-probabilities; fp8 (the next step down) reads at
    least three times that, and flips more of the routed sets."""
    params = ref.init_params(TINY, seed)
    ids, labels = _ids(seed)
    exact_lp, exact_set = ref.token_logprobs(params, TINY, ids[0], labels[0])
    bf16_lp, bf16_set = ref.token_logprobs(params, TINY, ids[0], labels[0],
                                           "bfloat16")
    fp8_lp, fp8_set = ref.token_logprobs(params, TINY, ids[0], labels[0],
                                         "fp8")
    bf16 = runner.train.compare_logprobs(bf16_lp, exact_lp)
    fp8 = runner.train.compare_logprobs(fp8_lp, exact_lp)
    assert 0 < bf16 < 0.01 and fp8 > 3 * bf16
    assert not core.check("x", fp8, 2.0 * bf16)["ok"]
    assert runner.compare_routing(exact_set, exact_set) == 0.0
    assert runner.compare_routing(fp8_set, exact_set) >= \
        runner.compare_routing(bf16_set, exact_set)


def test_fp8_flips_routed_sets_that_bfloat16_keeps():
    """Expert layers behind other layers, so that their inputs carry the
    rounding: over three seeds fp8 flips some sets and more than bf16."""
    cfg = dict(TINY, hybrid_override_pattern="MEME*E")
    flips = {"bfloat16": 0.0, "fp8": 0.0}
    for seed in (31, 32, 33):
        params = ref.init_params(cfg, seed)
        ids, labels = _ids(seed)
        exact = ref.token_logprobs(params, cfg, ids[0], labels[0])[1]
        for precision in flips:
            flips[precision] += runner.compare_routing(ref.token_logprobs(
                params, cfg, ids[0], labels[0], precision)[1], exact)
    assert flips["fp8"] > 0.0 and flips["fp8"] > 2 * flips["bfloat16"]


def test_compare_routing_counts_pairs_whose_sets_differ():
    want = np.zeros((2, 5, 4), bool)
    want[:, :, 0] = True
    got = want.copy()
    got[0, 1, 0], got[0, 1, 2] = False, True        # one pair, two flips
    got[1, 4, 3] = True                             # one pair, one more
    assert runner.compare_routing(got, want) == pytest.approx(2 / 10)
    assert runner.compare_routing(np.zeros((0, 5, 4), bool),
                                  np.zeros((0, 5, 4), bool)) == 0.0


def _first_step_numbers(seed, precision="float32", master_dtype="float32"):
    """A control in the program's place, against the float32 reference."""
    params = ref.init_params(TINY, seed)
    ids, labels = _ids(seed)
    grads = ref.loss_and_grads(params, TINY, ids, labels)[1]
    want = runner.plain_first_step(TINY, TRAFFIC, params, grads)
    low = ref.loss_and_grads(params, TINY, ids, labels, precision)[1]
    got = runner.plain_first_step(TINY, TRAFFIC, params, low, master_dtype)
    init = runner.train.sample_leaves(params, runner.sampled_tops(TINY))
    return runner.train.compare_first_step(got, want, init)


@pytest.mark.parametrize("seed", [41, 42, 43])
def test_control_fails_the_gradient_comparison(seed):
    same = _first_step_numbers(seed)
    assert same["grad_rel_err"] == 0 and same["adam_update_rel_err"] == 0
    bf16 = _first_step_numbers(seed, "bfloat16")
    fp8 = _first_step_numbers(seed, "fp8")
    assert 0 < bf16["grad_rel_err"] < 0.03
    assert fp8["grad_rel_err"] > 3 * bf16["grad_rel_err"]
    limits = core.load_json(runner.REHEARSAL_LIMITS)
    assert fp8["grad_rel_err"] > limits["grad_rel_err"]["limit"]
    assert bf16["adam_update_rel_err"] < limits["adam_update_rel_err"]["limit"]


@pytest.mark.parametrize("seed", [51, 52, 53])
def test_control_fails_the_adam_comparison(seed):
    got = _first_step_numbers(seed, master_dtype="bfloat16")
    limits = core.load_json(runner.REHEARSAL_LIMITS)
    assert got["grad_rel_err"] == 0
    assert got["adam_update_rel_err"] > 10 * limits[
        "adam_update_rel_err"]["limit"]


def test_rehearsal_limits_stand_clear_of_their_controls():
    limits = core.load_json(runner.REHEARSAL_LIMITS)
    assert limits["device"]["platform"] == "cpu"
    numbers = {k: v for k, v in limits.items() if k != "device"}
    assert set(numbers) == {"grad_rel_err", "adam_update_rel_err"}
    for v in numbers.values():
        assert v["sound_largest"] < v["limit"] < v["control_smallest"]
        assert v["control_smallest"] >= 3 * v["sound_largest"]
        assert v["control_seeds"] >= 3 and v["sound_seeds"] >= 5


# ------------------------------------------------------- the runner's rules
def _reading(grad, adam, fp8=None, low=None, lp=0.004, flips=0.002,
             slots=0.0005):
    r = {"program": {"grad_rel_err": grad, "adam_update_rel_err": adam,
                     "logprob_rms": lp, "routed_set_mismatch_share": flips,
                     "slots_held_rel_diff": slots,
                     "first_loss_abs_diff": 0.0001}}
    if fp8 is not None:
        r["control_fp8"] = {"grad_rel_err": fp8, "logprob_rms": 0.3,
                            "routed_set_mismatch_share": 0.3,
                            "slots_held_rel_diff": 0.02}
        r["control_bf16_masters"] = {"adam_update_rel_err": low}
    return r


def test_limits_are_the_geometric_mean_and_need_three_times_clearance():
    sound = [_reading(0.01, 1e-5, 0.09, 0.4), _reading(0.008, 1e-5, 0.16, 0.4),
             _reading(0.009, 4e-5, 0.1, 0.9), _reading(0.004, 1e-5)]
    got = runner.limits_from(sound)
    assert set(got) == {"grad_rel_err", "adam_update_rel_err"}
    assert got["grad_rel_err"]["limit"] == pytest.approx(0.03)
    assert got["adam_update_rel_err"]["limit"] == pytest.approx(0.004)
    with pytest.raises(SystemExit):                       # 0.025 < 3 x 0.01
        runner.limits_from(sound + [_reading(0.01, 1e-5, 0.025, 0.4)])
    with pytest.raises(SystemExit):                       # two control seeds
        runner.limits_from(sound[1:])
    for number in ("lp", "flips", "slots"):               # a kept limit
        with pytest.raises(SystemExit):
            runner.limits_from(sound + [_reading(0.01, 1e-5, **{number: 0.5})])
    bad = _reading(0.01, 1e-5)
    bad["program"]["first_loss_abs_diff"] = 0.1
    with pytest.raises(SystemExit):
        runner.limits_from(sound + [bad])


def test_sampled_leaves_cover_tables_norm_and_a_layer_of_each_kind():
    assert runner.sampled_tops(CELL) == {
        "embed_tokens", "lm_head_kernel", "final_norm_scale", "layers_0",
        "layers_1", "layers_10"}
    got = runner.train.sample_leaves(ref.init_params(TINY, 1),
                                     runner.sampled_tops(TINY))
    assert ("lm_head_kernel",) in got and ("final_norm_scale",) in got
    assert ("layers_0", "mixer", "router_kernel") in got
    assert ("layers_0", "mixer", "experts_up_proj") in got
    assert ("layers_1", "mixer", "A_log") in got
    assert ("layers_2", "mixer", "q_proj", "kernel") in got
    assert not any(path[0] == "layers_3" for path in got)


def test_cast_for_compute_keeps_what_the_model_keeps_float32():
    model = runner.program_model(TINY, dict(TRAFFIC, dtype="bfloat16"))
    cast = runner.cast_for_compute(model, ref.init_params(TINY, 2),
                                   {"dtype": "bfloat16"})
    assert cast["lm_head_kernel"].dtype == jnp.bfloat16
    expert, mamba = cast["layers_0"]["mixer"], cast["layers_1"]["mixer"]
    assert expert["experts_up_proj"].dtype == jnp.bfloat16
    assert expert["router_kernel"].dtype == jnp.float32
    assert mamba["in_proj"]["kernel"].dtype == jnp.bfloat16
    for name in ("A_log", "dt_bias", "D"):
        assert mamba[name].dtype == jnp.float32
    assert cast["embed_tokens"]["embedding"].dtype == jnp.float32


# -------------------------------------------------------------- the readers
COUNTERS = {"ssm_layer_applications": 5.0, "moe_layer_applications": 5.0,
            "attention_layer_applications": 1.0, "moe_slots_held": 2816.0,
            "moe_load_max_over_mean": 1.7, "moe_slots_dropped": 0.0}


def _record(step_s=0.3, steps=5, **more):
    return dict({"step_ready_at": [step_s * i for i in range(steps)],
                 "model_config": CELL, "seq_len": 8192, "micro_batch": 1,
                 "tokens": 8192 * steps, "attempted": steps, "chips": 1,
                 "device_kind": "TPU v5 lite", "losses": [1.0] * steps,
                 "step_counters": dict(COUNTERS)}, **more)


def test_hybrid_mfu_by_hand_and_against_the_programs_counters():
    reader = core.layer_metric_reader("train.hybrid_mfu_pct")
    got = reader.compute(_record(), None)
    per_token = ref.flops_per_token(CELL, 8192, 2816 / 8192)
    assert got == pytest.approx(100 * per_token * 8192 / 0.3 / 197e12)
    assert 40 < got < 45
    # more slots routed here is more work for the same step time
    busy = dict(COUNTERS, moe_slots_held=8192.0)
    assert reader.compute(_record(step_counters=busy), None) > got
    # counters that disagree with the pattern, or a dropped slot: no number
    for wrong in ({"ssm_layer_applications": 4.0},
                  {"moe_layer_applications": 6.0},
                  {"attention_layer_applications": 0.0},
                  {"moe_slots_dropped": 3.0}):
        assert reader.compute(_record(
            step_counters=dict(COUNTERS, **wrong)), None) is None
    # no steps, another model, no counters, nothing at all
    assert reader.compute(_record(step_ready_at=[0.0]), None) is None
    pythia = core.load_json(core.BENCH_DIR + "/configs/pythia-160m.json")
    assert reader.compute(_record(model_config=pythia), None) is None
    assert reader.compute(_record(step_counters=None), None) is None
    assert reader.compute({}, None) is None


def test_load_reader_reads_the_programs_counter():
    reader = core.layer_metric_reader("train.moe_load_max_over_mean")
    assert reader.compute(_record(), object()) == 1.7
    assert reader.compute(_record(), None) is None
    assert reader.compute(_record(step_counters=None), object()) is None
    assert reader.compute({}, None) is None


def _rows():
    """Two steps of a hand-made trace of a hybrid step."""
    ops, host = [], []
    top = "jit(train_step)/jvp(NemotronH)/"
    names = {
        "proj.1": top + "layers_1/ssm/mixer/in_proj/dot_general",
        "scan.2": top + "layers_1/ssm/mixer/checkpoint/ssm_scan/dot_general",
        "scan.3": "jit(train_step)/transpose(jvp(NemotronH))/layers_1/ssm/"
                  "mixer/checkpoint/ssm_scan/exp",
        "route.4": top + "layers_0/mlp/mixer/moe_route/top_k",
        "act.5": top + "layers_0/mlp/mixer/while/body/moe_experts/mul",
        "shared.6": top + "layers_0/mlp/mixer/moe_shared/dot_general",
        "dot.7": top + "layers_0/mlp/mixer/while/body/moe_experts/"
                 "dot_general",
        "dot.8": "jit(train_step)/transpose(jvp(NemotronH))/layers_0/mlp/"
                 "mixer/while/body/transpose(jvp(moe_experts))/dot_general",
        "attn.9": top + "layers_10/attention/mixer/q_proj/dot_general",
        "lost.10": "params['layers_0']['mixer']['router_kernel']"}
    durations = {"proj.1": 40_000, "scan.2": 7_000, "scan.3": 13_000,
                 "route.4": 9_000, "act.5": 1_000, "shared.6": 50_000,
                 "dot.7": 5_000, "dot.8": 500,
                 "attn.9": 20_000, "lost.10": 4_500}
    for step in range(2):
        at = step * 400_000
        host.append(["dst:train/step", at, 300_000, {"step_num": str(step)}])
        for name, dur in durations.items():
            ops.append([name, at, dur, "jit_train_step"])
            at += dur
    return {"ops": ops, "host": host, "scopes": {"jit_train_step": names}}


def test_hybrid_scope_readers_on_a_fixture(monkeypatch):
    found = program_trace.ProgramTrace(_rows())
    monkeypatch.setattr(program_trace, "of_run", lambda: found)
    record = {"losses": [1.0]}
    read = {s: core.layer_metric_reader("train.scope_ms." + s).compute(
        record, object()) for s in ("ssm", "ssm_scan", "moe_route",
                                    "moe_experts")}
    assert read["ssm"] == pytest.approx(0.060)
    assert read["ssm_scan"] == pytest.approx(0.020)
    assert read["moe_route"] == pytest.approx(0.009)
    # a chunk's matmuls, forward and in the backward walk's body
    assert read["moe_experts"] == pytest.approx(0.0065)
    # the accepted reader of ``mlp`` sees the whole expert layer
    assert found.scope_ms_per_step("mlp") == pytest.approx(0.0655)
    lost = core.layer_metric_reader("train.hybrid_unattributed_pct").compute(
        record, object())
    assert lost == pytest.approx(100 * 4_500 / 150_000)
    # the accepted share would call the state-space layer unattributed too
    assert found.unattributed_pct() == pytest.approx(
        100 * (60_000 + 4_500) / 150_000)
    assert hybrid_trace.scopes_on(
        "jit(f)/transpose(jvp(ssm))/checkpoint/mul") >= {"ssm"}
    # a program that published no scope, no trace, no record
    bare = program_trace.ProgramTrace(dict(_rows(), scopes={}))
    for state in (bare, None):
        monkeypatch.setattr(program_trace, "of_run", lambda: state)
        for name in ("train.scope_ms.ssm", "train.scope_ms.moe_experts",
                     "train.hybrid_unattributed_pct"):
            assert core.layer_metric_reader(name).compute(
                record, object()) is None


class _Trace:
    """What ``flash_attention_roofline_held`` asks of a reduced trace."""

    def __init__(self, durations_ns):
        self.events = [(i * 10 ** 7, d) for i, d in enumerate(durations_ns)]

    def scope_events(self, scope):
        return self.events if scope == "flash_attention" else []


def test_flash_roofline_at_the_heads_held(monkeypatch):
    reader = core.layer_metric_reader("flash_attention_roofline_held")
    cost = core.load_kernel_cost("flash_attention")
    f, b = cost.forward(1, 8, 8192, 128), cost.backward(1, 8, 8192, 128)
    passes = {"forward": 1, "recomputed": 0, "backward": 1}
    assert reader.step_work(passes, 1, 8, 8192, 128) == {
        "flops": f["flops"] + b["flops"], "bytes": f["bytes"] + b["bytes"]}
    # a recomputed forward is a forward's work again; a backward in two
    # kernels is one backward's work
    again = reader.step_work({"forward": 1, "recomputed": 1, "backward": 2},
                             1, 8, 8192, 128)
    assert again["flops"] == 2 * f["flops"] + b["flops"]
    monkeypatch.setattr(reader, "kernel_passes", lambda: passes)
    # three steps: a forward of 1.0 ms and a backward of 2.5 ms each
    trace = _Trace([1_000_000, 2_500_000] * 3)
    got = reader.compute(_record(), trace)
    least = (f["flops"] + b["flops"]) / 197e12
    assert got == pytest.approx(100 * least / 3.5e-3)
    assert 0 < got < 100
    # the accepted reader would credit all 32 published heads: four times
    assert 4 * got > 100
    monkeypatch.setattr(reader, "kernel_passes", lambda: None)
    assert reader.compute(_record(), trace) is None
    monkeypatch.setattr(reader, "kernel_passes", lambda: passes)
    assert reader.compute(_record(), _Trace([])) is None
    pythia = core.load_json(core.BENCH_DIR + "/configs/pythia-160m.json")
    assert reader.compute(_record(model_config=pythia), trace) is None
    assert reader.compute({}, None) is None


def test_the_cell_lists_the_readers_that_serve_it(listed):
    manifest, _ = listed
    names = {m["name"] for m in core.metrics_for(manifest, NAME, "per_layer")}
    assert names >= {
        "train.hybrid_mfu_pct", "train.scope_ms.ssm",
        "train.scope_ms.ssm_scan", "train.scope_ms.moe_route",
        "train.scope_ms.moe_experts", "train.hybrid_unattributed_pct",
        "train.moe_load_max_over_mean", "flash_attention_roofline_held",
        "train.step_ms", "device.idle_pct.train", "train.scope_ms.mlp",
        "train.scope_ms.attention", "train.scope_ms.head_ce",
        "train.scope_ms.optimizer", "train.idle_ms.fence"}
    # readers that would print a wrong number here are not asked
    assert not names & {"train.mfu_pct", "train.looped_mfu_pct",
                        "flash_attention_roofline",
                        "train.scope_unattributed_pct"}
    for cell in ("train-410m", "train-160m", "train-ouro-2.6b-loop4"):
        old = {m["name"] for m in core.metrics_for(manifest, cell,
                                                   "per_layer")}
        assert not any("hybrid" in n or "moe" in n or "ssm" in n
                       or n.endswith("_held") for n in old)
    assert {m["name"] for m in core.metrics_for(
        manifest, NAME, "end_to_end")} == {"train_tokens_per_s_chip",
                                           "setup_s"}


# ------------------------------------------------------------ the rehearsal
def test_rehearsal_prints_counts_only():
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", NAME, "--seed",
         str(2**31 + 77), "--seconds", "2", "--trace", "0", "--rehearse"],
        cwd=core.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=400)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["metrics"] == {}
    assert last["rehearsal"] is True and last["device"]["platform"] == "cpu"
    assert last["counts"]["steps"] > 0
    checks = {c["check"]: c for c in lines if "check" in c}
    assert set(checks) >= {
        "grad_rel_err_vs_reference", "adam_update_rel_err_vs_reference",
        "logprob_rms_vs_reference", "routed_set_mismatch_share_vs_reference",
        "slots_held_rel_diff_vs_reference", "moe_slots_dropped",
        "layers_of_every_kind_counted", "first_loss_abs_diff_vs_reference",
        "compiles_in_window"}
    assert checks["compiles_in_window"]["value"] == 0
    assert checks["moe_slots_dropped"]["value"] == 0
    # the window's steps counted themselves, every one of them
    told = next(x for x in lines if x.get("progress") == "window_counters")
    assert told["moe_slots_held_min"] <= told["moe_slots_held"] \
        <= told["moe_slots_held_max"]
    assert told["moe_slots_held_first"] > 0 and told["moe_slots_dropped"] == 0
    assert told["moe_layer_applications"] == 1
    assert 0 < checks["logprob_rms_vs_reference"]["value"]

"""The gated window + mixture-of-experts cell's part of the benchmark on the
CPU: the configuration file against the published row key for key, its
``sizing`` against ``num_params()``, the controls of the output check (fp8,
bfloat16 masters, a state left unchanged, each of the five mechanisms left
out: each must come out as not correct), the runner's limits rule, the FLOP
count by hand, the six new readers on hand-made fixtures and on nothing, and
the cell's rehearsal."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core, program_trace
from benchmarks.reference import laguna_ref as ref

runner = core.load_runner("train_laguna")
TINY = core.load_json(core.BENCH_DIR + "/configs/tiny-laguna-rehearsal.json")
CELL = core.load_json(core.BENCH_DIR + "/configs/laguna-s-2.1.json")
NAME = "train-laguna-s-ep32-8k"
TRAFFIC = {"seq_len": 40, "micro_batch": 2, "dtype": "float32",
           "ce_chunk_tokens": 48, "clip": 1.0,
           "optimizer": {"type": "Adam", "lr": 1e-4, "betas": [0.9, 0.999],
                         "eps": 1e-8},
           "scheduler": {"type": "WarmupLR", "params": {
               "warmup_min_lr": 1e-6, "warmup_max_lr": 1e-4,
               "warmup_num_steps": 2000, "warmup_type": "linear"}}}
SLIDING, FULL = "sliding_attention", "full_attention"
PERIOD = [FULL, SLIDING, SLIDING, SLIDING]
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
ROW = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": PERIOD * 12, "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
    "moe_router_logit_softcapping": 0}
HELD = {"layers_held": 5, "routed_experts_held": 8, "vocab_rows_held": 12544,
        "full_attention_heads_held": 24, "sliding_attention_heads_held": 36,
        "key_value_heads_held": 4}
COUNTERS = {"window_layer_applications": 3.0, "full_layer_applications": 2.0,
            "dense_mlp_layer_applications": 1.0,
            "moe_layer_applications": 4.0,
            "shared_expert_layer_applications": 4.0, "moe_slots_held": 5120.0,
            "moe_load_max_over_mean": 3.1, "moe_slots_dropped": 0.0}


def _ids(seed, b=2, s=40):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, TINY["vocab_size"], size=(b, s + 1), dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


# ------------------------------------------------------- the configuration
def test_the_configuration_is_the_published_row_key_for_key():
    for key, value in ROW.items():
        assert CELL[key] == value, key
    manifest = core.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == "laguna-s-2.1")
    assert entry["source"] == CELL["source"] == (
        "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json")
    assert entry["file"] == "benchmarks/configs/laguna-s-2.1.json"
    assert entry["reduced"] == CELL["reduced"] == list(HELD)
    assert {k: CELL[k] for k in HELD} == HELD
    # everything beside the published keys is the cut or says what was done
    assert set(CELL) - set(ROW) - set(HELD) == {
        "first_layer_held", "first_expert_held", "first_key_value_head_held",
        "initializer_range", "source", "reduced", "assumed",
        "program_preset", "deployment", "sizing", "distorts"}
    assert {"block", "gate", "qk_norm", "rotary", "router", "shared_expert",
            "initializer_range"} <= set(CELL["assumed"])
    assert "LEFT OUT" in CELL["assumed"]["left_out"]
    for said in ("a fifth of the depth", "two of the five layers",
                 "0.31 slots", "12,544 rows"):
        assert said in CELL["distorts"], said
    assert "32 chips share each layer" in CELL["deployment"]


def test_the_cut_is_the_dense_layer_a_period_and_the_shares():
    assert ref.layer_kinds(CELL) == [
        (FULL, "dense"), (SLIDING, "sparse"), (SLIDING, "sparse"),
        (SLIDING, "sparse"), (FULL, "sparse")]
    assert ref.share(CELL) == {
        "first_expert": 0, "experts": 8, "vocab": 12544, "kv_heads": 4,
        "first_kv_head": 0, "heads": {FULL: 24, SLIDING: 36}}
    assert ref.whole_heads(CELL) == {FULL: 48, SLIDING: 72}
    sh = ref.share(CELL)
    assert ref.attention_params(CELL, sh, FULL) == 22_093_824
    assert ref.attention_params(CELL, sh, SLIDING) == 31_567_872
    assert ref.gated_mlp_params(CELL, 12288) == 113_246_208
    assert ref.routed_expert_params(CELL) == 9_437_184
    assert ref.num_params(CELL) == 672_125_952
    # the file's sizing says the same, to the parameter
    sizing = CELL["sizing"]
    assert "= 672,125,952" in sizing["held_params"]
    for number in ("135,346,176", "117,295,104", "107,821,056"):
        assert number in sizing["layers_held"]
        assert number in sizing["held_params"]
    assert "77,070,336" in sizing["tables"]
    assert 135_346_176 + 3 * 117_295_104 + 107_821_056 + 77_070_336 \
        + 3_072 == 672_125_952
    with pytest.raises(ValueError):
        ref.layer_kinds(dict(CELL, mlp_layer_types=["gated"] * 48))


def test_flops_by_hand():
    # 6 x (two full and three sliding layers' attention with its gate, the
    # dense MLP, four routers and shared experts, 0.3125 slots a sparse
    # layer of a routed expert, the head) + 12 D S x (2 x 24 heads of a full
    # layer + 3 x 36 of a windowed one x the band's share)
    matmul = (2 * 22_093_824 + 3 * 31_567_872 + 113_246_208
              + 4 * (786_432 + 9_437_184) + 4 * 0.3125 * 9_437_184
              + 3072 * 12544)
    assert ref.band_pairs(8192, 512) == 512 * 8192 - 512 * 511 // 2
    band = ref.band_pairs(8192, 512) / ref.band_pairs(8192)
    assert 0.12 < band < 0.125
    assert ref.flops_per_token(CELL, 8192, 0.3125) == pytest.approx(
        6 * matmul + 12 * 128 * 8192 * (2 * 24 + 3 * 36 * band))
    assert 2.8e9 < ref.flops_per_token(CELL, 8192, 0.3125) < 2.9e9
    # a routed expert counts by the slot
    assert (ref.flops_per_token(CELL, 8192, 1.3125)
            - ref.flops_per_token(CELL, 8192, 0.3125)) == pytest.approx(
                6 * 4 * 9_437_184)


# ------------------------------------------------------------- the controls
@pytest.mark.parametrize("seed", [21, 22, 23])
def test_controls_fail_the_forward_comparisons(seed):
    """bf16 (the stated precision) against the float32 reference reads a
    number for the log-probabilities; fp8 (the next step down) at least
    three times that; each mechanism left out reads more than bf16 too."""
    params = ref.init_params(TINY, seed)
    ids, labels = _ids(seed)
    exact_lp, exact_set = ref.token_logprobs(params, TINY, ids[0], labels[0])
    read = {}
    for name, changed in [("bf16", dict(precision="bfloat16")),
                          ("fp8", dict(precision="fp8"))] + [
                              (m, dict(without=(m,)))
                              for m in ref.MECHANISMS]:
        lp, chosen = ref.token_logprobs(params, TINY, ids[0], labels[0],
                                        **changed)
        read[name] = (runner.train.compare_logprobs(lp, exact_lp),
                      runner.hybrid.compare_routing(chosen, exact_set))
    assert 0 < read["bf16"][0] < 0.01 and read["fp8"][0] > 3 * read["bf16"][0]
    assert read["fp8"][1] >= read["bf16"][1]
    for mechanism in ("window", "gate", "shared_expert", "routed_scale"):
        assert read[mechanism][0] > 3 * read["bf16"][0], mechanism
    # forty positions hardly turn the 64 dims a full layer's head gains
    assert read["partial_rotary"][0] > 1e-4


def _first_step_numbers(seed, master_dtype="float32", **changed):
    """A control in the program's place, against the float32 reference."""
    params = ref.init_params(TINY, seed)
    ids, labels = _ids(seed)
    grads = ref.loss_and_grads(params, TINY, ids, labels)[1]
    want = runner.plain_first_step(TINY, TRAFFIC, params, grads)
    low = ref.loss_and_grads(params, TINY, ids, labels, **changed)[1]
    got = runner.plain_first_step(TINY, TRAFFIC, params, low, master_dtype)
    init = runner.train.sample_leaves(params, runner.sampled_tops(TINY))
    return runner.train.compare_first_step(got, want, init)


@pytest.mark.parametrize("seed", [42, 43])
def test_controls_fail_the_gradient_comparison(seed):
    limits = core.load_json(runner.REHEARSAL_LIMITS)
    same = _first_step_numbers(seed)
    assert same["grad_rel_err"] == 0 and same["adam_update_rel_err"] == 0
    bf16 = _first_step_numbers(seed, precision="bfloat16")
    fp8 = _first_step_numbers(seed, precision="fp8")
    assert 0 < bf16["grad_rel_err"] < 0.03
    assert fp8["grad_rel_err"] > 3 * bf16["grad_rel_err"]
    assert fp8["grad_rel_err"] > limits["grad_rel_err"]["limit"]
    # the gradient sees a mechanism left out as well
    for mechanism in ("gate", "routed_scale"):
        out = _first_step_numbers(seed, without=(mechanism,))
        assert runner.refused({"grad_rel_err": out["grad_rel_err"]},
                              limits) == ["grad_rel_err"], mechanism


def test_control_fails_the_adam_comparison():
    """The first step moves a weight by the schedule's FIRST rate, 1e-6: a
    bfloat16 master cannot hold such a step at all; and a step that never
    happened reads 1."""
    seed = 51
    got = _first_step_numbers(seed, master_dtype="bfloat16")
    limits = core.load_json(runner.REHEARSAL_LIMITS)
    assert got["grad_rel_err"] == 0
    assert got["adam_update_rel_err"] > 10 * limits[
        "adam_update_rel_err"]["limit"]
    params = ref.init_params(TINY, seed)
    ids, labels = _ids(seed)
    want = runner.plain_first_step(
        TINY, TRAFFIC, params, ref.loss_and_grads(params, TINY, ids,
                                                  labels)[1])
    init = runner.train.sample_leaves(params, runner.sampled_tops(TINY))
    still = runner.train.compare_first_step(dict(want, master=init), want,
                                            init)["adam_update_rel_err"]
    assert still == pytest.approx(1.0)
    assert still > 10 * limits["adam_update_rel_err"]["limit"]
    assert runner.first_rate(TRAFFIC) == 1e-6
    assert runner.engine_config(TRAFFIC, 7)["scheduler"] == TRAFFIC[
        "scheduler"]


def test_rehearsal_limits_stand_clear_of_their_controls():
    limits = core.load_json(runner.REHEARSAL_LIMITS)
    assert limits["device"]["platform"] == "cpu"
    for v in (limits["grad_rel_err"], limits["adam_update_rel_err"]):
        assert v["sound_largest"] < v["limit"] < v["control_smallest"]
        assert v["control_smallest"] >= 3 * v["sound_largest"]
    assert limits["adam_update_rel_err"]["control"] == runner.UNCHANGED


def _reading(grad, adam, fp8=None, low=None, lp=0.004, flips=0.002,
             slots=0.0002, **left_out):
    r = {"program": {"grad_rel_err": grad, "adam_update_rel_err": adam,
                     "routed_grad_rel_err": 0.02,
                     "logprob_rms": lp, "routed_set_mismatch_share": flips,
                     "slots_held_rel_diff": slots,
                     "first_loss_abs_diff": 0.0002}}
    if fp8 is not None:
        r["control_fp8"] = {"grad_rel_err": fp8, "logprob_rms": 0.3,
                            "routed_set_mismatch_share": 0.4,
                            "slots_held_rel_diff": 0.05}
        r["control_bf16_masters"] = {"adam_update_rel_err": low}
        r["control_state_unchanged"] = {"adam_update_rel_err": 1.0}
        for control in runner.LEFT_OUT:
            r[control] = left_out.get(control, {
                "logprob_rms": 0.2, "routed_set_mismatch_share": 0.3,
                "routed_grad_rel_err": 0.6})
    return r


def test_limits_rule_and_the_mechanism_controls(monkeypatch):
    readings = [_reading(0.006, 0.001, 0.07, 30.0),
                _reading(0.005, 0.0009, 0.08, 31.0),
                _reading(0.0055, 0.0008, 0.09, 32.0), _reading(0.004, 0.0005)]
    got = runner.limits_from(readings)
    assert got["grad_rel_err"]["limit"] == pytest.approx(
        (0.006 * 0.07) ** 0.5)
    assert got["grad_rel_err"]["sound_seeds"] == 4
    assert got["adam_update_rel_err"]["control"] == "control_state_unchanged"
    assert got["adam_update_rel_err"]["limit"] == pytest.approx(0.001 ** 0.5)
    with pytest.raises(SystemExit, match="control_bf16_masters would pass"):
        runner.limits_from(readings[:3] + [
            _reading(0.004, 0.0005, 0.08, 0.02)])
    # a control under three times the sound runs refuses the limits
    with pytest.raises(SystemExit):
        runner.limits_from(readings[:2] + [
            _reading(0.03, 0.0008, 0.07, 30.0)])
    # a kept limit that a sound run breaks
    with pytest.raises(SystemExit):
        runner.limits_from(readings + [_reading(0.004, 0.0005, lp=1.0)])
    # at the cell's size (on the chip) a mechanism left out that would pass
    # refuses them too, each of the five
    passes = {"logprob_rms": 0.001, "routed_set_mismatch_share": 0.0,
              "routed_grad_rel_err": 0.01}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert runner.limits_from(readings) == got
    for control in runner.LEFT_OUT:
        # the routed experts' gradient is the unscaled weights' own number:
        # where that control reads like a sound run, no limit stands
        said = ("routed_grad_rel_err: the kept limit"
                if control in runner.BY_GRADIENT else control + " would pass")
        with pytest.raises(SystemExit, match=said):
            runner.limits_from(readings[:3] + [_reading(
                0.004, 0.0005, 0.08, 30.0, **{control: passes})])
    assert sorted(runner.LEFT_OUT.values()) == sorted(ref.MECHANISMS)
    # a limit no control bounds leaves the sound readings three times of room
    with pytest.raises(SystemExit, match="slots_held_rel_diff: the guard"):
        runner.limits_from(readings + [_reading(0.004, 0.0005, slots=0.004)])
    assert set(runner.GUARDS) == {"slots_held_rel_diff",
                                  "first_loss_abs_diff"}
    assert set(runner.BY_GRADIENT) <= set(runner.LEFT_OUT)


def test_sampled_leaves_cover_tables_norm_and_a_layer_of_each_kind():
    assert runner.sampled_tops(CELL) == {
        "embed_tokens", "lm_head_kernel", "final_norm_scale", "layers_0",
        "layers_1", "layers_4"}
    assert runner.layers_counted(CELL, COUNTERS, dict(COUNTERS))
    for name in runner.COUNTED:
        assert not runner.layers_counted(
            CELL, COUNTERS, dict(COUNTERS, **{name: COUNTERS[name] + 1})), name


# --------------------------------------------------------------- the readers
def _record(step_s=0.6, steps=5, **more):
    return dict({"step_ready_at": [step_s * i for i in range(steps)],
                 "model_config": CELL, "seq_len": 8192, "micro_batch": 2,
                 "tokens": 2 * 8192 * steps, "attempted": steps, "chips": 1,
                 "device_kind": "TPU v5 lite", "losses": [1.0] * steps,
                 "step_counters": dict(COUNTERS)}, **more)


def test_gated_swa_moe_mfu_by_hand_and_against_the_programs_counters():
    reader = core.layer_metric_reader("train.gated_swa_moe_mfu_pct")
    got = reader.compute(_record(), None)
    per_token = ref.flops_per_token(CELL, 8192, 5120 / 16384)
    assert got == pytest.approx(100 * per_token * 16384 / 0.6 / 197e12)
    assert 35 < got < 45
    # more slots routed here is more work for the same step time
    busy = dict(COUNTERS, moe_slots_held=20480.0)
    assert reader.compute(_record(step_counters=busy), None) > got
    # counters that disagree with the layers, or a dropped slot: no number
    for wrong in ({"window_layer_applications": 4.0},
                  {"full_layer_applications": 1.0},
                  {"dense_mlp_layer_applications": 0.0},
                  {"moe_layer_applications": 5.0},
                  {"shared_expert_layer_applications": 3.0},
                  {"moe_slots_dropped": 3.0}):
        assert reader.compute(_record(
            step_counters=dict(COUNTERS, **wrong)), None) is None
    # no steps, another model, no counters, nothing at all
    assert reader.compute(_record(step_ready_at=[0.0]), None) is None
    for other in ("pythia-160m", "mellum2-12b-a2.5b"):
        config = core.load_json(f"{core.BENCH_DIR}/configs/{other}.json")
        assert reader.compute(_record(model_config=config), None) is None
    assert reader.compute(_record(step_counters=None), None) is None
    assert reader.compute({}, None) is None


class _Trace:
    """What the roofline readers ask of a reduced trace."""

    def __init__(self, window_ns=(), full_ns=()):
        self.by_scope = {
            "flash_attention_window": [(i * 10 ** 7, d)
                                       for i, d in enumerate(window_ns)],
            "flash_attention": [(i * 10 ** 7 + 5, d)
                                for i, d in enumerate(full_ns)]}

    def scope_events(self, scope):
        return self.by_scope.get(scope, [])


def test_rooflines_take_each_kinds_heads_held(monkeypatch):
    window = core.layer_metric_reader(
        "flash_attention_window_roofline_kind_heads")
    full = core.layer_metric_reader("flash_attention_full_roofline_kind_heads")
    cost_w = core.load_kernel_cost("flash_attention_window")
    cost_f = core.load_kernel_cost("flash_attention")
    # two steps: three windowed layers' forwards of 3 ms and backwards of
    # 8 ms, two full layers' forwards of 10 ms and backwards of 24 ms
    trace = _Trace([3_000_000] * 3 + [8_000_000] * 3 + [3_000_000] * 3
                   + [8_000_000] * 3, [10_000_000, 24_000_000] * 4)
    monkeypatch.setattr(window.window, "kernel_passes", lambda: {
        "forward": 3, "recomputed": 0, "backward": 3})
    monkeypatch.setattr(full.held, "kernel_passes", lambda: {
        "forward": 2, "recomputed": 0, "backward": 2})
    # 36 heads under the window, the band's pairs only
    f = cost_w.forward(2, 36, 8192, 128, 512)
    b = cost_w.backward(2, 36, 8192, 128, 512)
    got = window.compute(_record(), trace)
    assert got == pytest.approx(
        100 * 3 * (f["flops"] + b["flops"]) / 197e12 / 33e-3)
    assert 0 < got < 100
    # 24 heads on the full layers: the accepted reader's one count (48, and
    # not held) would read twice as much
    f, b = cost_f.forward(2, 24, 8192, 128), cost_f.backward(2, 24, 8192, 128)
    got = full.compute(_record(), trace)
    assert got == pytest.approx(
        100 * 2 * (f["flops"] + b["flops"]) / 197e12 / 68e-3)
    assert 0 < got < 100
    accepted = core.layer_metric_reader("flash_attention_full_roofline")
    monkeypatch.setattr(accepted.held, "kernel_passes", lambda: {
        "forward": 2, "recomputed": 0, "backward": 2})
    assert accepted.compute(_record(), trace) == pytest.approx(2 * got)
    # nothing to read: no events, no passes, another model, nothing at all
    mellum = core.load_json(core.BENCH_DIR + "/configs/mellum2-12b-a2.5b.json")
    for reader in (window, full):
        assert reader.compute(_record(), _Trace()) is None
        assert reader.compute(_record(model_config=mellum), trace) is None
        assert reader.compute(_record(), None) is None
        assert reader.compute({}, None) is None
    monkeypatch.setattr(window.window, "kernel_passes", lambda: None)
    monkeypatch.setattr(full.held, "kernel_passes", lambda: None)
    assert window.compute(_record(), trace) is None
    assert full.compute(_record(), trace) is None
    # a stack with no layer of the kind has no heads of it
    helper = window._kind_heads
    assert helper.heads_held(dict(CELL, layers_held=1), SLIDING) is None
    assert helper.heads_held(dict(CELL, layers_held=1), FULL) == 24
    assert helper.heads_held(mellum, FULL) is None


def _rows():
    """Two steps of a hand-made trace of this model's step."""
    ops, host = [], []
    top = "jit(train_step)/jvp(Laguna)/"
    back = "jit(train_step)/transpose(jvp(Laguna))/"
    names = {
        "qkv.1": top + "layers_1/attention/attention_window/attn/q_proj/"
                 "dot_general",
        "kernel.2": top + "layers_1/attention/attention_window/attn/"
                    "flash_attention_window/pallas_call",
        "gate.3": top + "layers_1/attention/attention_window/attn/"
                  "attention_gate/g_proj/dot_general",
        "gate.4": back + "layers_1/attention/attention_window/attn/"
                  "attention_gate/mul",
        "gate.5": top + "layers_0/attention/attention_full/attn/"
                  "attention_gate/logistic",
        "dense.6": top + "layers_0/mlp/mlp_dense/mlp/gate_proj/dot_general",
        "route.7": top + "layers_1/mlp/moe/moe_route/top_k",
        "dot.8": top + "layers_1/mlp/moe/while/body/moe_experts/dot_general",
        "shared.9": top + "layers_1/mlp/moe_shared/shared_expert/up_proj/"
                    "dot_general",
        "shared.10": back + "layers_1/mlp/moe_shared/shared_expert/"
                     "down_proj/dot_general",
        "lost.11": "params['layers_1']['moe']['router_kernel']"}
    durations = {"qkv.1": 30_000, "kernel.2": 6_000, "gate.3": 1_000,
                 "gate.4": 2_000, "gate.5": 500, "dense.6": 40_000,
                 "route.7": 9_000, "dot.8": 5_000, "shared.9": 7_000,
                 "shared.10": 8_000, "lost.11": 2_500}
    for step in range(2):
        at = step * 400_000
        host.append(["dst:train/step", at, 300_000, {"step_num": str(step)}])
        for name, dur in durations.items():
            ops.append([name, at, dur, "jit_train_step"])
            at += dur
    return {"ops": ops, "host": host, "scopes": {"jit_train_step": names}}


def test_scope_readers_on_a_fixture(monkeypatch):
    found = program_trace.ProgramTrace(_rows())
    monkeypatch.setattr(program_trace, "of_run", lambda: found)
    record = {"losses": [1.0]}
    new = ("attention_gate", "moe_shared", "mlp_dense")
    read = {s: core.layer_metric_reader("train.scope_ms." + s).compute(
        record, object()) for s in new + (
            "attention_window", "attention_full", "moe_route", "moe_experts")}
    assert read["attention_gate"] == pytest.approx(0.0035)
    assert read["moe_shared"] == pytest.approx(0.015)
    assert read["mlp_dense"] == pytest.approx(0.040)
    # the gate lies inside its kind's sublayer, the new MLP parts inside mlp
    assert read["attention_window"] == pytest.approx(0.039)
    assert read["attention_full"] == pytest.approx(0.0005)
    assert read["moe_route"] == pytest.approx(0.009)
    assert read["moe_experts"] == pytest.approx(0.005)
    assert found.scope_ms_per_step("attention") == pytest.approx(0.0395)
    assert found.scope_ms_per_step("mlp") == pytest.approx(0.069)
    lost = core.layer_metric_reader("train.hybrid_unattributed_pct").compute(
        record, object())
    assert lost == pytest.approx(100 * 2_500 / 111_000)
    # a program that published no scope, no trace, no record
    bare = program_trace.ProgramTrace(dict(_rows(), scopes={}))
    for state in (bare, None):
        monkeypatch.setattr(program_trace, "of_run", lambda: state)
        for name in new:
            assert core.layer_metric_reader(
                "train.scope_ms." + name).compute(record, object()) is None
    for name in new:
        assert core.layer_metric_reader("train.scope_ms." + name).compute(
            {}, None) is None


#: this cell's own readers
OWN = ["train.gated_swa_moe_mfu_pct",
       "flash_attention_window_roofline_kind_heads",
       "flash_attention_full_roofline_kind_heads",
       "train.scope_ms.attention_gate", "train.scope_ms.moe_shared",
       "train.scope_ms.mlp_dense"]


def test_the_cell_lists_the_readers_that_serve_it(listed):
    manifest, bench_dir = listed
    names = {m["name"] for m in core.metrics_for(manifest, NAME, "per_layer")}
    assert names >= set(OWN) | {
        "train.scope_ms.moe_route", "train.scope_ms.moe_experts",
        "train.hybrid_unattributed_pct", "train.moe_load_max_over_mean",
        "train.step_ms", "device.idle_pct.train", "train.scope_ms.mlp",
        "train.scope_ms.attention", "train.scope_ms.attention_layout",
        "train.scope_ms.head_ce", "train.scope_ms.optimizer",
        "train.idle_ms.fence", "train.idle_ms.input",
        "train.idle_ms.dispatch", "train.idle_ms.outside",
        "train.host_cpu_ms.step", "train.host_cpu_ms.outside",
        "train.host_ms.input", "train.host_ms.dispatch",
        "train.host_ms.report", "train.host_ms.outside",
        "train.step_ms.unprofiled_less_profiled"}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    layers = {m["layer"] for m in manifest["per_layer"]
              if m["name"] not in OWN}
    for name in OWN:
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["workloads"] == [NAME]
        assert m["moves"] == "train_tokens_per_s_chip" and m["moves"] in e2e
        assert m["layer"] in layers
        assert callable(core.layer_metric_reader(name, bench_dir).compute)
    # ``train.scope_ms.attention_window`` / ``.attention_full`` would hold
    # here unchanged, but ``test_bench_mellum.py`` holds their lists to the
    # Mellum cell alone and only a ``benchmark`` PR may edit that file
    # (PERF.md section 7)
    # readers that would print a wrong number here are not asked: those
    # that take one head count for every layer, the walk's grouped kernels
    # (this walk is by slots), the other models' shares of the peak
    assert not names & {
        "train.mfu_pct", "train.looped_mfu_pct", "train.hybrid_mfu_pct",
        "train.swa_moe_mfu_pct", "flash_attention_roofline",
        "flash_attention_roofline_held", "flash_attention_window_roofline",
        "flash_attention_full_roofline", "grouped_matmul_roofline",
        "ssd_scan_roofline", "train.scope_unattributed_pct",
        "train.scope_ms.ssm"}
    # and no other cell is asked for this cell's
    for w in manifest["workloads"]:
        if w["name"] != NAME:
            assert not set(OWN) & {m["name"] for m in core.metrics_for(
                manifest, w["name"], "per_layer")}
    assert {m["name"] for m in core.metrics_for(
        manifest, NAME, "end_to_end")} == {"train_tokens_per_s_chip",
                                           "setup_s"}
    cell, config, traffic = core.find_cell(manifest, NAME,
                                           os.path.dirname(bench_dir))
    assert cell["chips"] == 1 and cell["config"] == "laguna-s-2.1"
    assert cell["traffic"] == "pretrain-8192-gated-swa-moe-remat"
    assert traffic["runner"] == "train_laguna"
    assert (traffic["seq_len"], traffic["micro_batch"], traffic[
        "ce_chunk_tokens"], traffic["remat"]) == (8192, 2, 2048, True)
    assert traffic["optimizer"] == {"type": "Adam", "lr": 1e-4,
                                    "betas": [0.9, 0.999], "eps": 1e-8}
    assert traffic["scheduler"]["params"] == {
        "warmup_min_lr": 1e-6, "warmup_max_lr": 1e-4,
        "warmup_num_steps": 2000, "warmup_type": "linear"}
    assert traffic["token_dist"] == {"kind": "zipf", "exponent": 1.1}
    assert (traffic["clip"], traffic["zero_stage"], traffic["grad_accum"],
            traffic["dtype"]) == (1.0, 0, 1, "bfloat16")
    # one world from the start: the seed among the nine readings it names
    assert 0 <= traffic["world"]["seed"] <= 8
    assert str(traffic["world"]["seed"]) + ":" in traffic["world"]["why"]
    assert traffic["sizing"]["chosen"] == traffic["micro_batch"]


def test_the_world_renames_the_tables_of_this_model():
    """Under a world the run's weights are the world's with both tables
    moved to the run's names: the first batch's loss is the world's."""
    from benchmarks import traffic_gen

    traffic = dict(TRAFFIC, token_dist={"kind": "zipf", "exponent": 1.1},
                   world={"seed": 3})
    vocab = runner.vocab(TINY)
    losses = []
    for seed in (3, 2**31 + 5):
        batches = traffic_gen.TokenBatches(traffic, vocab, seed)
        assert batches.world_seed == 3
        params = runner.seeded_params(TINY, batches)
        first = batches.batch(0)
        losses.append(float(ref.loss_and_grads(
            params, TINY, jnp.asarray(first["input_ids"]),
            jnp.asarray(first["labels"]))[0]))
    assert losses[0] == pytest.approx(losses[1], rel=1e-5)


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
        "routed_grad_rel_err_vs_reference", "first_loss_abs_diff_vs_reference",
        "slots_held_rel_diff_vs_reference", "moe_slots_dropped",
        "layers_of_every_kind_counted", "loss_fall_over_window",
        "compiles_in_window"}
    assert "first_loss_abs_diff" in next(
        x for x in lines if x.get("progress") == "reference")
    assert checks["compiles_in_window"]["value"] == 0
    assert checks["moe_slots_dropped"]["value"] == 0
    assert checks["layers_of_every_kind_counted"]["value"] == 1
    # the window's steps counted themselves, every one of them
    told = next(x for x in lines if x.get("progress") == "window_counters")
    assert told["moe_slots_held_min"] <= told["moe_slots_held"] \
        <= told["moe_slots_held_max"]
    assert told["moe_slots_held_first"] > 0 and told["moe_slots_dropped"] == 0
    assert (told["window_layer_applications"], told[
        "full_layer_applications"], told["dense_mlp_layer_applications"],
        told["moe_layer_applications"], told[
            "shared_expert_layer_applications"]) == (2, 1, 1, 2, 2)

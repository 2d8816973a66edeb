"""The window + mixture-of-experts cell's part of the benchmark on the CPU:
the configuration file against the published row key for key, the three
controls of the output check (fp8, bfloat16 masters, every layer full: each
must come out as not correct), the runner's limits rule, the FLOP count and
the windowed kernel's cost by hand, the new readers on hand-made fixtures
and on nothing, and the cell's rehearsal."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core, program_trace
from benchmarks.reference import mellum_ref as ref

runner = core.load_runner("train_swa_moe")
TINY = core.load_json(core.BENCH_DIR + "/configs/tiny-mellum-rehearsal.json")
CELL = core.load_json(core.BENCH_DIR + "/configs/mellum2-12b-a2.5b.json")
NAME = "train-mellum2-ep4-8k"
TRAFFIC = {"seq_len": 40, "micro_batch": 2, "dtype": "float32",
           "ce_chunk_tokens": 48, "clip": 1.0,
           "optimizer": {"type": "Adam", "lr": 1e-4, "betas": [0.9, 0.999],
                         "eps": 1e-8},
           "scheduler": {"type": "WarmupLR", "params": {
               "warmup_min_lr": 1e-6, "warmup_max_lr": 1e-4,
               "warmup_num_steps": 2000, "warmup_type": "linear"}}}
SLIDING, FULL = "sliding_attention", "full_attention"
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
ROW = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
HELD = {"layers_held": 4, "routed_experts_held": 16, "vocab_rows_held": 24576}
COUNTERS = {"window_layer_applications": 3.0, "full_layer_applications": 1.0,
            "moe_layer_applications": 4.0, "moe_slots_held": 65536.0,
            "moe_load_max_over_mean": 1.3, "moe_slots_dropped": 0.0}


def _ids(seed, b=2, s=40):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, TINY["vocab_size"], size=(b, s + 1), dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


# ------------------------------------------------------- the configuration
def test_the_configuration_is_the_published_row_key_for_key():
    for key, value in ROW.items():
        assert CELL[key] == value, key
    manifest = core.load_manifest()
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "mellum2-12b-a2.5b")
    assert entry["source"] == CELL["source"]
    assert entry["reduced"] == CELL["reduced"] == list(HELD)
    assert {k: CELL[k] for k in HELD} == HELD
    # everything beside the published keys is the cut or says what was done
    assert set(CELL) - set(ROW) - set(HELD) == {
        "first_layer_held", "first_expert_held", "initializer_range",
        "source", "reduced", "assumed", "program_preset", "deployment",
        "sizing", "distorts"}
    assert "MTP" in CELL["assumed"]["multi_token_prediction"]


def test_the_cut_is_one_period_a_quarter_of_the_experts_and_of_the_rows():
    assert ref.layer_kinds(CELL) == [SLIDING, SLIDING, SLIDING, FULL]
    assert ref.share(CELL) == {"first_expert": 0, "experts": 16,
                               "vocab": 24576}
    assert ref.num_params(CELL) == 595_153_152
    assert "595,153,152" in CELL["sizing"]["held_params"]
    assert ref.routed_expert_params(CELL) == 6_193_152
    assert ref.layer_matmul_params(CELL) == 21_233_664 + 147_456
    with pytest.raises(ValueError):
        ref.layer_kinds(dict(CELL, mlp_layer_types=["dense"] * 28))


def test_flops_by_hand():
    # 6 x (four layers' attention + router, two slots a layer of a routed
    # expert, the head) + a full layer's 12 heads D S and three windowed
    # layers' band share of that
    matmul = 4 * (21_233_664 + 147_456 + 2 * 6_193_152) + 2304 * 24576
    square = 12 * 32 * 128 * 8192
    band = 7_864_832 / 33_558_528
    assert ref.band_pairs(8192, 1024) == 7_864_832
    assert ref.band_pairs(8192) == 33_558_528
    assert ref.flops_per_token(CELL, 8192, 2.0) == pytest.approx(
        6 * matmul + square * (1 + 3 * band))
    assert 1.8e9 < ref.flops_per_token(CELL, 8192, 2.0) < 1.9e9


# ------------------------------------------------------------- the controls
@pytest.mark.parametrize("seed", [21, 22, 23])
def test_controls_fail_the_forward_comparisons(seed):
    """bf16 (the stated precision) against the float32 reference reads a
    number for the log-probabilities; fp8 (the next step down) at least
    three times that; the reference with every layer full reads more than
    fp8 and flips routed sets."""
    params = ref.init_params(TINY, seed)
    ids, labels = _ids(seed)
    exact_lp, exact_set = ref.token_logprobs(params, TINY, ids[0], labels[0])
    read = {}
    for name, changed in (("bf16", dict(precision="bfloat16")),
                          ("fp8", dict(precision="fp8")),
                          ("full", dict(every_layer_full=True))):
        lp, chosen = ref.token_logprobs(params, TINY, ids[0], labels[0],
                                        **changed)
        read[name] = (runner.train.compare_logprobs(lp, exact_lp),
                      runner.hybrid.compare_routing(chosen, exact_set))
    assert 0 < read["bf16"][0] < 0.01 and read["fp8"][0] > 3 * read["bf16"][0]
    assert read["full"][0] > runner.LOGPROB_RMS_LIMIT
    assert read["full"][1] > runner.ROUTED_SET_MISMATCH_LIMIT
    assert read["fp8"][1] >= read["bf16"][1]


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


@pytest.mark.parametrize("seed", [41, 42, 43])
def test_controls_fail_the_gradient_comparison(seed):
    limits = core.load_json(runner.REHEARSAL_LIMITS)
    same = _first_step_numbers(seed)
    assert same["grad_rel_err"] == 0 and same["adam_update_rel_err"] == 0
    bf16 = _first_step_numbers(seed, precision="bfloat16")
    fp8 = _first_step_numbers(seed, precision="fp8")
    full = _first_step_numbers(seed, every_layer_full=True)
    assert 0 < bf16["grad_rel_err"] < 0.03
    assert fp8["grad_rel_err"] > 3 * bf16["grad_rel_err"]
    assert fp8["grad_rel_err"] > limits["grad_rel_err"]["limit"]
    # a program that ignored the window: far beyond what fp8 reads
    assert full["grad_rel_err"] > fp8["grad_rel_err"]
    assert runner.refused({"grad_rel_err": full["grad_rel_err"]}, limits) \
        == ["grad_rel_err"]


@pytest.mark.parametrize("seed", [51, 52, 53])
def test_control_fails_the_adam_comparison(seed):
    """The first step moves a weight by the schedule's FIRST rate, 1e-6: a
    bfloat16 master cannot hold such a step at all."""
    got = _first_step_numbers(seed, master_dtype="bfloat16")
    limits = core.load_json(runner.REHEARSAL_LIMITS)
    assert got["grad_rel_err"] == 0
    assert got["adam_update_rel_err"] > 10 * limits[
        "adam_update_rel_err"]["limit"]
    # and so does a step that never happened, which reads 1
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


def test_the_first_step_runs_at_the_schedules_first_rate():
    assert runner.first_rate(TRAFFIC) == 1e-6
    assert runner.first_rate({"optimizer": {"lr": 3e-4}}) == 3e-4
    with pytest.raises(ValueError):
        runner.first_rate(dict(TRAFFIC, scheduler={"type": "OneCycle"}))
    config = runner.engine_config(TRAFFIC, 7)
    assert config["scheduler"] == TRAFFIC["scheduler"]
    assert "scheduler" not in runner.engine_config(
        {k: v for k, v in TRAFFIC.items() if k != "scheduler"}, 7)
    # the masters move by that rate, not by the optimizer's
    params = ref.init_params(TINY, 61)
    ids, labels = _ids(61)
    grads = ref.loss_and_grads(params, TINY, ids, labels)[1]
    step = runner.plain_first_step(TINY, TRAFFIC, params, grads)
    init = runner.train.sample_leaves(params, runner.sampled_tops(TINY))
    moved = max(float(np.max(np.abs(step["master"][k] - init[k])))
                for k in init)
    assert 0.5e-6 < moved <= 1.05e-6      # float32 steps of 1e-9 at 0.02


def test_rehearsal_limits_stand_clear_of_their_controls():
    limits = core.load_json(runner.REHEARSAL_LIMITS)
    assert limits["device"]["platform"] == "cpu"
    for v in (limits["grad_rel_err"], limits["adam_update_rel_err"]):
        assert v["sound_largest"] < v["limit"] < v["control_smallest"]
        assert v["control_smallest"] >= 3 * v["sound_largest"]


def _reading(grad, adam, fp8=None, low=None, full=None, lp=0.004, flips=0.002,
             slots=0.0002):
    r = {"program": {"grad_rel_err": grad, "adam_update_rel_err": adam,
                     "logprob_rms": lp, "routed_set_mismatch_share": flips,
                     "slots_held_rel_diff": slots,
                     "first_loss_abs_diff": 0.0002}}
    if fp8 is not None:
        r["control_fp8"] = {"grad_rel_err": fp8, "logprob_rms": 0.3,
                            "routed_set_mismatch_share": 0.4,
                            "slots_held_rel_diff": 0.05}
        r["control_bf16_masters"] = {"adam_update_rel_err": low}
        r["control_state_unchanged"] = {"adam_update_rel_err": 1.0}
        r["control_every_layer_full"] = full or {
            "grad_rel_err": 0.5, "logprob_rms": 0.2,
            "routed_set_mismatch_share": 0.3, "slots_held_rel_diff": 0.01,
            "first_loss_abs_diff": 0.0}
    return r


def test_limits_rule_and_the_third_control():
    readings = [_reading(0.006, 0.001, 0.07, 30.0),
                _reading(0.005, 0.0009, 0.08, 31.0),
                _reading(0.0055, 0.0008, 0.09, 32.0), _reading(0.004, 0.0005)]
    got = runner.limits_from(readings)
    assert got["grad_rel_err"]["limit"] == pytest.approx(
        (0.006 * 0.07) ** 0.5)
    assert got["grad_rel_err"]["sound_seeds"] == 4
    # the update's limit stands between the sound runs and 1, what a state
    # left unchanged reads, and bfloat16 masters must break it as well
    assert got["adam_update_rel_err"]["control"] == "control_state_unchanged"
    assert got["adam_update_rel_err"]["limit"] == pytest.approx(0.001 ** 0.5)
    with pytest.raises(SystemExit, match="bfloat16 masters would pass"):
        runner.limits_from(readings[:3] + [
            _reading(0.004, 0.0005, 0.08, 0.02)])
    # a control under three times the sound runs refuses the limits
    with pytest.raises(SystemExit):
        runner.limits_from(readings[:2] + [
            _reading(0.03, 0.0008, 0.07, 30.0)])
    # a kept limit that a sound run breaks
    with pytest.raises(SystemExit):
        runner.limits_from(readings + [_reading(0.004, 0.0005, lp=1.0)])
    # a program that ignored the window and would pass refuses them too
    with pytest.raises(SystemExit, match="ignored the window"):
        runner.limits_from(readings[:3] + [_reading(
            0.004, 0.0005, 0.08, 30.0, full={
                "grad_rel_err": 0.001, "logprob_rms": 0.001,
                "routed_set_mismatch_share": 0.0, "slots_held_rel_diff": 0.0,
                "first_loss_abs_diff": 0.0})])


def test_sampled_leaves_cover_tables_norm_and_a_layer_of_each_kind():
    assert runner.sampled_tops(CELL) == {
        "embed_tokens", "lm_head_kernel", "final_norm_scale", "layers_0",
        "layers_3"}
    assert runner.layers_counted(CELL, COUNTERS, dict(COUNTERS))
    assert not runner.layers_counted(
        CELL, dict(COUNTERS, window_layer_applications=4.0))
    assert not runner.layers_counted(
        CELL, COUNTERS, dict(COUNTERS, moe_layer_applications=3.0))


# --------------------------------------------------------------- the readers
def _record(step_s=1.2, steps=5, **more):
    return dict({"step_ready_at": [step_s * i for i in range(steps)],
                 "model_config": CELL, "seq_len": 8192, "micro_batch": 4,
                 "tokens": 4 * 8192 * steps, "attempted": steps, "chips": 1,
                 "device_kind": "TPU v5 lite", "losses": [1.0] * steps,
                 "step_counters": dict(COUNTERS)}, **more)


def test_swa_moe_mfu_by_hand_and_against_the_programs_counters():
    reader = core.layer_metric_reader("train.swa_moe_mfu_pct")
    got = reader.compute(_record(), None)
    per_token = ref.flops_per_token(CELL, 8192, 65536 / 32768)
    assert got == pytest.approx(100 * per_token * 32768 / 1.2 / 197e12)
    assert 20 < got < 30
    # more slots routed here is more work for the same step time
    busy = dict(COUNTERS, moe_slots_held=131072.0)
    assert reader.compute(_record(step_counters=busy), None) > got
    # counters that disagree with the layers, or a dropped slot: no number
    for wrong in ({"window_layer_applications": 4.0},
                  {"full_layer_applications": 0.0},
                  {"moe_layer_applications": 3.0},
                  {"moe_slots_dropped": 3.0}):
        assert reader.compute(_record(
            step_counters=dict(COUNTERS, **wrong)), None) is None
    # no steps, another model, no counters, nothing at all
    assert reader.compute(_record(step_ready_at=[0.0]), None) is None
    pythia = core.load_json(core.BENCH_DIR + "/configs/pythia-160m.json")
    assert reader.compute(_record(model_config=pythia), None) is None
    assert reader.compute(_record(step_counters=None), None) is None
    assert reader.compute({}, None) is None


def test_window_cost_by_hand():
    cost = core.load_kernel_cost("flash_attention_window")
    full = core.load_kernel_cost("flash_attention")
    assert cost.pairs(8192, 1024) == 7_864_832
    f = cost.forward(4, 32, 8192, 128, 1024)
    assert f["flops"] == 4.0 * 4 * 32 * 7_864_832 * 128
    assert f["bytes"] == full.forward(4, 32, 8192, 128)["bytes"]
    b = cost.backward(4, 32, 8192, 128, 1024)
    assert b["flops"] == 2.5 * f["flops"]
    assert b["bytes"] == full.backward(4, 32, 8192, 128)["bytes"]
    # the band's pairs only: under a quarter of the triangle's at this size,
    # and the whole triangle's where the window reaches the whole length
    assert 0.23 < f["flops"] / full.forward(4, 32, 8192, 128)["flops"] < 0.24
    whole = cost.forward(1, 1, 512, 128, 512)["flops"]
    assert whole == 4.0 * (512 * 513 // 2) * 128
    assert cost.forward(1, 1, 512, 128, 9999)["flops"] == whole


class _Trace:
    """What ``flash_attention_window_roofline`` asks of a reduced trace."""

    def __init__(self, durations_ns, full_ns=()):
        self.events = [(i * 10 ** 7, d) for i, d in enumerate(durations_ns)]
        self.full = [(i * 10 ** 7 + 5, d) for i, d in enumerate(full_ns)]

    def scope_events(self, scope):
        return {"flash_attention_window": self.events,
                "flash_attention": self.full}.get(scope, [])


def test_window_roofline_on_a_fixture(monkeypatch):
    reader = core.layer_metric_reader("flash_attention_window_roofline")
    cost = core.load_kernel_cost("flash_attention_window")
    f = cost.forward(4, 32, 8192, 128, 1024)
    b = cost.backward(4, 32, 8192, 128, 1024)
    passes = {"forward": 3, "recomputed": 0, "backward": 3}
    assert reader.step_work(passes, 4, 32, 8192, 128, 1024) == {
        "flops": 3 * (f["flops"] + b["flops"]),
        "bytes": 3 * (f["bytes"] + b["bytes"])}
    monkeypatch.setattr(reader, "kernel_passes", lambda: passes)
    # two steps: three forwards of 6 ms and three backwards of 15 ms each;
    # the full layer's events (50 and 120 ms) are another scope's
    trace = _Trace([6_000_000] * 3 + [15_000_000] * 3 + [6_000_000] * 3
                   + [15_000_000] * 3, full_ns=[50_000_000, 120_000_000] * 2)
    got = reader.compute(_record(), trace)
    least = 3 * (f["flops"] + b["flops"]) / 197e12
    assert got == pytest.approx(100 * least / 63e-3)
    assert 0 < got < 100
    # counting the triangle's pairs would read four times as much
    monkeypatch.setattr(reader, "kernel_passes", lambda: None)
    assert reader.compute(_record(), trace) is None
    monkeypatch.setattr(reader, "kernel_passes", lambda: passes)
    assert reader.compute(_record(), _Trace([])) is None
    pythia = core.load_json(core.BENCH_DIR + "/configs/pythia-160m.json")
    assert reader.compute(_record(model_config=pythia), trace) is None
    hybrid = core.load_json(core.BENCH_DIR
                            + "/configs/nemotron-3-super-120b-a12b.json")
    assert reader.compute(_record(model_config=hybrid), trace) is None
    assert reader.compute({}, None) is None


def test_full_roofline_reads_the_full_layers_events_alone(monkeypatch):
    reader = core.layer_metric_reader("flash_attention_full_roofline")
    cost = core.load_kernel_cost("flash_attention")
    f, b = cost.forward(4, 32, 8192, 128), cost.backward(4, 32, 8192, 128)
    passes = {"forward": 1, "recomputed": 0, "backward": 1}
    monkeypatch.setattr(reader.held, "kernel_passes", lambda: passes)
    # two steps: the one full layer's forward of 14 ms and backward of
    # 32 ms; the windowed layers' events are another scope's
    trace = _Trace([6_000_000] * 12, full_ns=[14_000_000, 32_000_000] * 2)
    got = reader.compute(_record(), trace)
    assert got == pytest.approx(
        100 * (f["flops"] + b["flops"]) / 197e12 / 46e-3)
    assert 0 < got < 100
    assert reader.compute(_record(), _Trace([6_000_000])) is None
    monkeypatch.setattr(reader.held, "kernel_passes", lambda: None)
    assert reader.compute(_record(), trace) is None
    # a model without windowed layers is the accepted readers'
    hybrid = core.load_json(core.BENCH_DIR
                            + "/configs/nemotron-3-super-120b-a12b.json")
    monkeypatch.setattr(reader.held, "kernel_passes", lambda: passes)
    assert reader.compute(_record(model_config=hybrid), trace) is None
    assert reader.compute({}, None) is None


def _rows():
    """Two steps of a hand-made trace of this model's step."""
    ops, host = [], []
    top = "jit(train_step)/jvp(Mellum)/"
    back = "jit(train_step)/transpose(jvp(Mellum))/"
    names = {
        "qkv.1": top + "layers_0/attention/attention_window/attn/q_proj/"
                 "dot_general",
        "kernel.2": top + "layers_0/attention/attention_window/attn/"
                    "flash_attention_window/pallas_call",
        "kernel.3": back + "layers_0/attention/attention_window/attn/"
                    "flash_attention_window/pallas_call",
        "qkv.4": top + "layers_3/attention/attention_full/attn/q_proj/"
                 "dot_general",
        "kernel.5": top + "layers_3/attention/attention_full/attn/"
                    "flash_attention/pallas_call",
        "route.6": top + "layers_0/mlp/moe/moe_route/top_k",
        "dot.7": top + "layers_0/mlp/moe/while/body/moe_experts/dot_general",
        "lost.8": "params['layers_0']['moe']['router_kernel']"}
    durations = {"qkv.1": 30_000, "kernel.2": 6_000, "kernel.3": 15_000,
                 "qkv.4": 10_000, "kernel.5": 50_000, "route.6": 9_000,
                 "dot.7": 5_000, "lost.8": 2_500}
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
    read = {s: core.layer_metric_reader("train.scope_ms." + s).compute(
        record, object()) for s in ("attention_window", "attention_full",
                                    "moe_route", "moe_experts")}
    assert read["attention_window"] == pytest.approx(0.051)
    assert read["attention_full"] == pytest.approx(0.060)
    assert read["moe_route"] == pytest.approx(0.009)
    assert read["moe_experts"] == pytest.approx(0.005)
    # the accepted readers see the whole sublayers
    assert found.scope_ms_per_step("attention") == pytest.approx(0.111)
    assert found.scope_ms_per_step("mlp") == pytest.approx(0.014)
    lost = core.layer_metric_reader("train.hybrid_unattributed_pct").compute(
        record, object())
    assert lost == pytest.approx(100 * 2_500 / 127_500)
    # a program that published no scope, no trace, no record
    bare = program_trace.ProgramTrace(dict(_rows(), scopes={}))
    for state in (bare, None):
        monkeypatch.setattr(program_trace, "of_run", lambda: state)
        for name in ("train.scope_ms.attention_window",
                     "train.scope_ms.attention_full"):
            assert core.layer_metric_reader(name).compute(
                record, object()) is None


#: this cell's own readers
OWN = ["train.swa_moe_mfu_pct", "train.scope_ms.attention_window",
       "train.scope_ms.attention_full", "flash_attention_window_roofline",
       "flash_attention_full_roofline"]


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
        "train.idle_ms.dispatch", "train.idle_ms.outside"}
    # each of its own entries is whole, names this cell alone, moves what the
    # cell reports, sits in a layer the manifest has and has its reader
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    layers = {m["layer"] for m in manifest["per_layer"]
              if m["name"] not in OWN}
    for name in OWN:
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["workloads"] == [NAME]
        assert m["moves"] in e2e and m["layer"] in layers
        assert callable(core.layer_metric_reader(name, bench_dir).compute)
    # readers that would print a wrong number here are not asked: the full
    # kernel's take every ``flash_attention`` event at one cost
    assert not names & {"train.mfu_pct", "train.looped_mfu_pct",
                        "train.hybrid_mfu_pct", "flash_attention_roofline",
                        "flash_attention_roofline_held", "ssd_scan_roofline",
                        "train.scope_unattributed_pct", "train.scope_ms.ssm"}
    for cell in ("train-410m", "train-160m", "train-ouro-2.6b-loop4",
                 "train-nemotron3-super-ep64-8k"):
        old = {m["name"] for m in core.metrics_for(manifest, cell,
                                                   "per_layer")}
        assert not any("swa" in n or "window" in n or n.endswith("_full")
                       for n in old)
    assert {m["name"] for m in core.metrics_for(
        manifest, NAME, "end_to_end")} == {"train_tokens_per_s_chip",
                                           "setup_s"}
    cell, config, traffic = core.find_cell(manifest, NAME,
                                           os.path.dirname(bench_dir))
    assert cell["chips"] == 1 and traffic["runner"] == "train_swa_moe"
    assert traffic["seq_len"] == 8192 and traffic["remat"] is True
    assert traffic["scheduler"]["type"] == "WarmupLR"
    assert runner.first_rate(traffic) == traffic["scheduler"]["params"][
        "warmup_min_lr"] < traffic["optimizer"]["lr"]


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
        "layers_of_every_kind_counted", "loss_fall_over_window",
        "compiles_in_window"}
    # read and printed, but no limit stands between its readings here
    assert "first_loss_abs_diff_vs_reference" not in checks
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
        "full_layer_applications"], told["moe_layer_applications"]) == (2, 1, 3)

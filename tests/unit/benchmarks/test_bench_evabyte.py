"""The EVA cell's part of the benchmark on the CPU: the configuration file
against the published row key for key, its ``sizing`` against
``num_params()`` and the reference's count, what is *assumed* in the file
and in the reference's docstring alike, the controls of the output check
(fp8, bfloat16 masters, a state left unchanged, the float32 islands lost,
the summaries left out), the runner's limits rule, the FLOP count by hand,
the five new readers on hand-made fixtures and on nothing, the cell's
entries in the manifest, and the cell's rehearsal."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core, program_trace
from benchmarks.reference import evabyte_ref as ref

runner = core.load_runner("train_evabyte")
TINY = core.load_json(core.BENCH_DIR + "/configs/tiny-evabyte-rehearsal.json")
CELL = core.load_json(core.BENCH_DIR + "/configs/evabyte-6.5b.json")
NAME = "train-evabyte-tp2-16k"
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
ROW = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048}
HELD = {"layers_held": 4, "attention_heads_held": 16}
COUNTERS = {"layer_applications": 4.0, "head_chunks": 8.0,
            "eva_pairs_needed": 64 * 24_125_440.0,
            "eva_pairs_visited": 64 * 28_311_552.0}
S = 192


def _ids(seed, b=2, s=S):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, TINY["vocab_size"], size=(b, s + 1), dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


# ------------------------------------------------------- the configuration
def test_the_configuration_is_the_published_row_key_for_key():
    for key, value in ROW.items():
        assert key in CELL and CELL[key] == value, key
    manifest = core.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == "evabyte-6.5b")
    assert entry["source"] == CELL["source"] == (
        "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json")
    assert entry["file"] == "benchmarks/configs/evabyte-6.5b.json"
    assert entry["reduced"] == CELL["reduced"] == list(HELD)
    assert {k: CELL[k] for k in HELD} == HELD
    assert (CELL["first_layer_held"], CELL["first_head_held"]) == (12, 0)
    # everything beside the published keys is the cut or says what was done
    assert set(CELL) - set(ROW) - set(HELD) == {
        "first_layer_held", "first_head_held", "source", "reduced", "assumed",
        "program_preset", "deployment", "sizing", "distorts"}
    assert "two chips share each layer" in CELL["deployment"]
    for said in ("about 3 %", "about 5 % with whole heads", "1.5 %",
                 "four layers"):
        assert said in CELL["distorts"], said


def test_what_is_assumed_is_in_the_file_and_in_the_references_docstring():
    assumed = CELL["assumed"]
    assert {"pooling_logits", "summaries_seen", "no_random_features", "init",
            "head", "first_window"} <= set(assumed)
    doc = " ".join(ref.__doc__.split())
    for words in ("unscaled and read the ROTATED keys",
                  "seen only by LATER windows",
                  "no random features, no dropout",
                  "clipped to [-1, 1] times ``D^-1/2``",
                  "ONE matrix and weigh alike",
                  "the first window has ``R_t`` empty"):
        assert words in doc, words
    assert doc.lower().count("*assumed*") >= 4
    for key, words in (("pooling_logits", "unscaled"),
                       ("summaries_seen", "later windows"),
                       ("no_random_features", "no dropout"),
                       ("init", "clipped to [-1, 1]"),
                       ("head", "weigh alike"),
                       ("first_window", "R_t empty")):
        assert words in assumed[key], key
    # a departure is written beside them, never made silently
    assert "departure" in assumed["fp32_logits"]
    # the reference imports nothing from the program under test
    source = open(ref.__file__).read()
    assert "deeperspeed_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source


def test_the_cut_and_its_sizing_to_the_parameter():
    assert (ref.depth(CELL), ref.heads(CELL), ref.head_dim(CELL),
            ref.slices(CELL)) == (4, 16, 128, 8)
    assert ref.layer_matmul_params(CELL) == 33_554_432 + 135_266_304
    assert ref.num_params(CELL) == 687_132_672
    assert ref.num_params(CELL, with_input_embedding=False) == 685_821_952
    sizing = CELL["sizing"]
    for part, number in (("attention", "33,558,528"),
                         ("mlp", "135,266,304"), ("norms", "8,192"),
                         ("layer", "168,833,024"),
                         ("tables", "10,485,760"),
                         ("held", "= 687,132,672 parameters"),
                         ("rule", "620,015,616")):
        assert number in sizing[part], part
    assert 4 * 168_833_024 + 1_310_720 + 10_485_760 + 4_096 == 687_132_672
    program = runner.program_model(CELL, {"seq_len": 16384,
                                          "ce_chunk_tokens": 2048})
    assert program.num_params() == 687_132_672
    assert ref.num_params(dict(CELL, attention_heads_held=8)) == 620_015_616


def test_flops_by_hand():
    # 6 x (four layers' projections at 16 heads and SwiGLU, the head's eight
    # slices) + 12 x 16 heads x 128 x 4 layers x 1472.5 pairs a row + the
    # pooling, 24 x 16 x 128 x 4
    assert ref.pairs_needed(CELL, 16384) == 16384 * 1472.5 == 24_125_440
    matmul = 4 * (33_554_432 + 135_266_304) + 4096 * 2560
    want = 6 * matmul + 12 * 4 * 16 * 128 * 1472.5 + 24 * 4 * 16 * 128
    assert ref.flops_per_token(CELL, 16384) == pytest.approx(want)
    # a step: 67.4 TFLOP of matmuls among 69.8
    assert 16384 * 6 * matmul == pytest.approx(67.4e12, rel=0.01)
    assert 16384 * want == pytest.approx(69.8e12, rel=0.01)
    # the runner's count of the pairs a step needs, every head and layer
    traffic = core.find_cell(core.load_manifest(), NAME)[2]
    assert runner.pairs_needed(CELL, traffic) == 64 * 24_125_440


# ---------------------------------------------------------------- controls
@pytest.mark.parametrize("seed", [21, 22])
def test_controls_fail_the_comparisons(seed):
    """At the tiny preset: the fp8 control's gradient and log-probabilities,
    the islands lost, the summaries left out are each far from the float32
    reference; a state left unchanged reads 1 and bfloat16 masters lose the
    warm-up's first step."""
    params = ref.init_params(TINY, seed)
    ids, labels = _ids(seed)
    _, grads, lp = ref.loss_and_grads(params, TINY, ids, labels)

    def rel(other):
        a, b = (jax.tree_util.tree_leaves(t) for t in (other, grads))
        return (sum(float(jnp.sum(jnp.square(x - y))) for x, y in zip(a, b))
                / sum(float(jnp.sum(jnp.square(y))) for y in b)) ** 0.5

    inside = np.asarray(ref.targets(labels[0], 8)[1])
    for changed, least_grad, least_lp in (
            (dict(precision="fp8"), 0.05, 0.01),
            (dict(precision="bfloat16", islands="bfloat16"), 0.002, 0.001),
            (dict(without=("summaries",)), 0.01, 0.001)):
        _, low, low_lp = ref.loss_and_grads(params, TINY, ids, labels,
                                            **changed)
        assert rel(low) > least_grad, changed
        assert runner.compare_logprobs(low_lp, lp, inside) > least_lp, changed
    # bfloat16 matmuls with the islands kept are nearer than with them lost
    _, kept, _ = ref.loss_and_grads(params, TINY, ids, labels,
                                    precision="bfloat16")
    _, lost, _ = ref.loss_and_grads(params, TINY, ids, labels,
                                    precision="bfloat16", islands="bfloat16")
    assert rel(kept) < rel(lost)


def test_rehearsal_limits_stand_clear_of_their_controls():
    limits = core.load_json(runner.REHEARSAL_LIMITS)
    assert limits["device"]["platform"] == "cpu"
    assert set(limits) == {"grad_rel_err", "adam_update_rel_err", "device"}
    for v in (limits["grad_rel_err"], limits["adam_update_rel_err"]):
        assert v["sound_largest"] < v["limit"] < v["control_smallest"]
        assert v["control_smallest"] >= 3 * v["sound_largest"]
    assert limits["adam_update_rel_err"]["control"] == runner.UNCHANGED
    # the cell's own: set on the chip, the same two numbers and no other
    cell = core.load_limits(NAME)
    assert set(cell) == {"grad_rel_err", "adam_update_rel_err", "device"}
    assert cell["device"]["platform"] == "tpu"
    assert cell["grad_rel_err"]["control"] == "control_fp8"
    assert cell["adam_update_rel_err"]["control"] == runner.UNCHANGED


def _reading(grad, adam, fp8=None, low=None, lp=0.002, loss=0.0002,
             summaries=None):
    r = {"program": {"grad_rel_err": grad, "adam_update_rel_err": adam,
                     "logprob_rms": lp, "first_loss_abs_diff": loss}}
    if fp8 is not None:
        r["control_fp8"] = {"grad_rel_err": fp8, "logprob_rms": 0.04}
        r["control_bf16_masters"] = {"adam_update_rel_err": low}
        r[runner.UNCHANGED] = {"adam_update_rel_err": 1.0}
        r[runner.ISLANDS] = {"grad_rel_err": 1.5 * grad,
                             "logprob_rms": 1.5 * lp}
        r[runner.NO_SUMMARIES] = summaries or {"grad_rel_err": 0.2,
                                               "logprob_rms": 0.02}
    return r


def test_limits_rule_and_the_controls(monkeypatch):
    readings = [_reading(0.006, 0.001, 0.07, 30.0),
                _reading(0.005, 0.0009, 0.08, 31.0),
                _reading(0.0055, 0.0008, 0.09, 32.0), _reading(0.004, 0.0005)]
    got = runner.limits_from(readings)
    assert set(got) == {"grad_rel_err", "adam_update_rel_err"}
    assert got["grad_rel_err"]["limit"] == pytest.approx(
        (0.006 * 0.07) ** 0.5)
    assert got["grad_rel_err"]["sound_seeds"] == 4
    assert got["adam_update_rel_err"]["control"] == runner.UNCHANGED
    assert got["adam_update_rel_err"]["limit"] == pytest.approx(0.001 ** 0.5)
    with pytest.raises(SystemExit, match="control_bf16_masters would pass"):
        runner.limits_from(readings[:3] + [
            _reading(0.004, 0.0005, 0.08, 0.02)])
    # a control under three times the sound runs refuses the limits
    with pytest.raises(SystemExit):
        runner.limits_from(readings[:2] + [
            _reading(0.03, 0.0008, 0.07, 30.0)])
    # a kept limit that a sound run breaks
    with pytest.raises(SystemExit, match="logprob_rms"):
        runner.limits_from(readings + [_reading(0.004, 0.0005, lp=1.0)])
    # at the cell's size (on the chip): the summaries left out must be
    # refused, the kept limit must stand three times clear of fp8, and the
    # first loss leave three times of room
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert runner.limits_from(readings) == got
    with pytest.raises(SystemExit, match="summaries_left_out would pass"):
        runner.limits_from(readings[:3] + [_reading(
            0.004, 0.0005, 0.08, 30.0,
            summaries={"grad_rel_err": 0.001, "logprob_rms": 0.001})])
    with pytest.raises(SystemExit, match="first-loss"):
        runner.limits_from(readings + [_reading(0.004, 0.0005, loss=0.002)])
    # the islands' control is read and recorded, and refuses nothing
    assert runner.refused(readings[0][runner.ISLANDS], got) == []
    assert runner.refused({"grad_rel_err": 0.5, "logprob_rms": 0.5}, got) == [
        "grad_rel_err", "logprob_rms"]


def test_sampled_leaves_and_the_counters_check():
    assert runner.sampled_tops(CELL) == {
        "embed_tokens", "lm_head_kernel", "final_norm_weight", "layers_0",
        "layers_1", "layers_3"}
    assert runner.layers_counted(CELL, COUNTERS, dict(COUNTERS))
    assert not runner.layers_counted(
        CELL, COUNTERS, dict(COUNTERS, layer_applications=3.0))
    assert not runner.layers_counted(
        CELL, dict(COUNTERS, eva_pairs_visited=1.0))
    assert not runner.layers_counted(CELL, {})
    assert runner.first_rate({"optimizer": {"lr": 1e-4}}) == 1e-4
    traffic = core.find_cell(core.load_manifest(), NAME)[2]
    assert runner.first_rate(traffic) == 1e-6
    assert runner.engine_config(traffic, 2**31 + 3)["scheduler"][
        "type"] == "WarmupLR"


# --------------------------------------------------------------- the readers
def _record(step_s=1.0, steps=5, **more):
    return dict({"step_ready_at": [step_s * i for i in range(steps)],
                 "model_config": CELL, "seq_len": 16384, "micro_batch": 1,
                 "tokens": 16384 * steps, "attempted": steps, "chips": 1,
                 "device_kind": "TPU v5 lite", "losses": [1.0] * steps,
                 "step_counters": dict(COUNTERS)}, **more)


def test_eva_mfu_by_hand_and_against_the_programs_counters():
    reader = core.layer_metric_reader("train.eva_mfu_pct")
    got = reader.compute(_record(), None)
    assert got == pytest.approx(
        100 * ref.flops_per_token(CELL, 16384) * 16384 / 1.0 / 197e12)
    assert 30 < got < 40
    # a counter that disagrees with the layers held: no number
    assert reader.compute(_record(step_counters=dict(
        COUNTERS, layer_applications=3.0)), None) is None
    # no steps, another model, no counters, nothing at all
    assert reader.compute(_record(step_ready_at=[0.0]), None) is None
    for other in ("pythia-160m", "laguna-s-2.1"):
        config = core.load_json(f"{core.BENCH_DIR}/configs/{other}.json")
        assert reader.compute(_record(model_config=config), None) is None
    assert reader.compute(_record(step_counters=None), None) is None
    assert reader.compute({}, None) is None


def test_pairs_over_needed_reads_the_programs_counters():
    reader = core.layer_metric_reader("train.eva_pairs_over_needed")
    assert reader.compute(_record(), object()) == pytest.approx(
        28_311_552 / 24_125_440)
    assert reader.compute(_record(), None) is None
    assert reader.compute(_record(step_counters={}), object()) is None
    assert reader.compute(_record(step_counters={
        "eva_pairs_needed": 5.0}), object()) is None
    assert reader.compute({}, None) is None


class _Trace:
    """What the roofline reader asks of a reduced trace."""

    def __init__(self, ns=()):
        self.events = [(i * 10 ** 7, d) for i, d in enumerate(ns)]

    def scope_events(self, scope):
        return self.events if scope == "eva_attention" else []


def test_the_roofline_counts_the_pairs_inside_the_mask(monkeypatch):
    reader = core.layer_metric_reader("eva_attention_roofline")
    cost = core.load_kernel_cost("eva_attention")
    assert cost.pairs(16384, 2048, 16) == 24_125_440
    assert cost.pairs(160, 64, 8) == (2 * 64 * 65 // 2 + 32 * 33 // 2
                                      + 8 * (64 + 32 * 2))
    f = cost.forward(1, 16, 16384, 128, 2048, 16)
    b = cost.backward(1, 16, 16384, 128, 2048, 16)
    assert f["flops"] == 4.0 * 16 * 24_125_440 * 128
    assert b["flops"] == 2.5 * f["flops"]
    tensor = 16 * 16384 * 128 * 2
    assert f["bytes"] == 4 * tensor + 2 * tensor // 16 + 16 * 16384 * 4
    assert b["bytes"] == 8 * tensor + 4 * tensor // 16 + 16 * 16384 * 4
    # two steps: four layers' forwards of 2 ms and backwards of 5 ms
    trace = _Trace([2_000_000] * 4 + [5_000_000] * 4 + [2_000_000] * 4
                   + [5_000_000] * 4)
    monkeypatch.setattr(reader, "kernel_passes", lambda: {
        "forward": 4, "recomputed": 0, "backward": 4})
    got = reader.compute(_record(), trace)
    assert got == pytest.approx(
        100 * 4 * (f["flops"] + b["flops"]) / 197e12 / 28e-3)
    assert 0 < got < 100
    # a recomputed forward is a forward's work more
    monkeypatch.setattr(reader, "kernel_passes", lambda: {
        "forward": 4, "recomputed": 4, "backward": 4})
    trace3 = _Trace([2_000_000] * 8 + [5_000_000] * 4)
    assert reader.compute(_record(), trace3) == pytest.approx(
        100 * 4 * (2 * f["flops"] + b["flops"]) / 197e12 / 36e-3)
    # nothing to read: no events, no passes, another model, nothing at all
    laguna = core.load_json(core.BENCH_DIR + "/configs/laguna-s-2.1.json")
    assert reader.compute(_record(), _Trace()) is None
    assert reader.compute(_record(model_config=laguna), trace) is None
    assert reader.compute(_record(), None) is None
    assert reader.compute({}, None) is None
    monkeypatch.setattr(reader, "kernel_passes", lambda: None)
    assert reader.compute(_record(), trace) is None


def _rows():
    """Two steps of a hand-made trace of this model's step."""
    ops, host = [], []
    top = "jit(train_step)/jvp(EvaByte)/"
    back = "jit(train_step)/transpose(jvp(EvaByte))/"
    names = {
        "qkv.1": top + "layers_1/attention/attn/q_proj/dot_general",
        "pool.2": top + "layers_1/attention/attn/eva_pool/reduce_sum",
        "pool.3": back + "layers_1/attention/attn/eva_pool/mul",
        "kernel.4": top + "layers_1/attention/attn/eva_attend/eva_attention/"
                    "pallas_call",
        "kernel.5": back + "layers_1/attention/attn/eva_attend/eva_attention/"
                    "pallas_call",
        "copy.6": top + "layers_1/attention/attn/eva_attend/attention_layout/"
                  "reshape",
        "mlp.7": top + "layers_1/mlp/mlp/gate_proj/dot_general",
        "head.8": top + "head_ce/while/body/dot_general",
        "lost.9": "params['layers_1']['attn']['adaptive_phi']"}
    durations = {"qkv.1": 30_000, "pool.2": 2_000, "pool.3": 3_000,
                 "kernel.4": 6_000, "kernel.5": 15_000, "copy.6": 500,
                 "mlp.7": 40_000, "head.8": 4_000, "lost.9": 1_000}
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
        record, object()) for s in ("eva_pool", "eva_attend")}
    assert read["eva_pool"] == pytest.approx(0.005)
    assert read["eva_attend"] == pytest.approx(0.0215)
    # both lie inside the attention sublayer, whose reader holds their sum
    # with the projections; the kernel's layout copy is attention_layout's
    assert found.scope_ms_per_step("attention") == pytest.approx(0.0565)
    assert found.scope_ms_per_step("attention_layout") == pytest.approx(
        0.0005)
    assert found.scope_ms_per_step("mlp") == pytest.approx(0.040)
    assert found.scope_ms_per_step("head_ce") == pytest.approx(0.004)
    lost = core.layer_metric_reader("train.scope_unattributed_pct").compute(
        record, object())
    assert lost == pytest.approx(100 * 1_000 / 101_500)
    # a program that published no scope, no trace, no record
    bare = program_trace.ProgramTrace(dict(_rows(), scopes={}))
    for state in (bare, None):
        monkeypatch.setattr(program_trace, "of_run", lambda: state)
        for name in read:
            assert core.layer_metric_reader(
                "train.scope_ms." + name).compute(record, object()) is None
    for name in read:
        assert core.layer_metric_reader("train.scope_ms." + name).compute(
            {}, None) is None


#: this cell's own readers
OWN = ["train.eva_mfu_pct", "train.scope_ms.eva_pool",
       "train.scope_ms.eva_attend", "train.eva_pairs_over_needed",
       "eva_attention_roofline"]


def test_the_cell_lists_the_readers_that_serve_it(listed):
    manifest, bench_dir = listed
    names = {m["name"] for m in core.metrics_for(manifest, NAME, "per_layer")}
    assert names >= set(OWN) | {
        "train.step_ms", "device.idle_pct.train", "train.scope_ms.mlp",
        "train.scope_ms.attention", "train.scope_ms.attention_layout",
        "train.scope_ms.head_ce", "train.scope_ms.optimizer",
        "train.scope_unattributed_pct",
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
    assert by_name["eva_attention_roofline"]["unit"] == "%"
    assert "mfu" in "train.eva_mfu_pct"
    # readers that would print a wrong number here are not asked: the other
    # models' shares of the peak, the other kernels' rooflines, the scopes
    # this model has not
    assert not names & {
        "train.mfu_pct", "train.looped_mfu_pct", "train.hybrid_mfu_pct",
        "train.swa_moe_mfu_pct", "train.gated_swa_moe_mfu_pct",
        "flash_attention_roofline", "flash_attention_roofline_held",
        "flash_attention_window_roofline", "flash_attention_full_roofline",
        "grouped_matmul_roofline", "ssd_scan_roofline", "train.scope_ms.ssm",
        "train.scope_ms.moe_route", "train.moe_load_max_over_mean",
        "train.hybrid_unattributed_pct", "train.scope_ms.attention_window"}
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
    assert cell["chips"] == 1 and cell["config"] == "evabyte-6.5b"
    assert cell["traffic"] == "pretrain-16384-eva-remat"
    assert "half" in cell["why"] and len(cell["why"]) <= 200
    assert config == CELL
    assert traffic["runner"] == "train_evabyte" and "world" not in traffic
    assert (traffic["seq_len"], traffic["micro_batch"], traffic[
        "ce_chunk_tokens"], traffic["remat"]) == (16384, 1, 2048, True)
    assert traffic["optimizer"] == {"type": "Adam", "lr": 1e-4,
                                    "betas": [0.9, 0.999], "eps": 1e-8}
    assert traffic["scheduler"]["params"] == {
        "warmup_min_lr": 1e-6, "warmup_max_lr": 1e-4,
        "warmup_num_steps": 2000, "warmup_type": "linear"}
    assert traffic["token_dist"] == {"kind": "zipf", "exponent": 1.1}
    assert (traffic["clip"], traffic["zero_stage"], traffic["grad_accum"],
            traffic["dtype"]) == (1.0, 0, 1, "bfloat16")
    assert traffic["rehearsal"]["config"] == "tiny-evabyte-rehearsal"


def test_the_batches_are_bytes_fresh_from_the_seed():
    from benchmarks import traffic_gen

    traffic = core.find_cell(core.load_manifest(), NAME)[2]
    a = traffic_gen.TokenBatches(traffic, CELL["vocab_size"], 2**31 + 9)
    b = traffic_gen.TokenBatches(traffic, CELL["vocab_size"], 2**31 + 10)
    first = a.batch(0)
    assert first["input_ids"].shape == first["labels"].shape == (1, 16384)
    assert first["input_ids"].max() < 320 and a.order is None
    assert not np.array_equal(first["input_ids"], a.batch(1)["input_ids"])
    assert not np.array_equal(first["input_ids"], b.batch(0)["input_ids"])
    np.testing.assert_array_equal(first["input_ids"][:, 1:],
                                  first["labels"][:, :-1])


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
        "logprob_rms_vs_reference", "first_loss_abs_diff_vs_reference",
        "eva_pairs_needed_rel_diff_vs_reference", "layers_counted",
        "loss_fall_over_window", "compiles_in_window"}
    assert "first_loss_abs_diff" in next(
        x for x in lines if x.get("progress") == "reference")
    assert checks["compiles_in_window"]["value"] == 0
    assert checks["layers_counted"]["value"] == 1
    # the window's steps counted themselves, every one of them
    told = next(x for x in lines if x.get("progress") == "window_counters")
    assert told["layer_applications"] == 2
    assert told["eva_pairs_visited"] >= told["eva_pairs_needed"] > 0

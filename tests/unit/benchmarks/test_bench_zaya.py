"""The compressed-attention + MLP-router cell's part of the benchmark on the
CPU: the configuration file against the published row key for key, the cut
and its floors, its ``sizing`` against ``num_params()``, the FLOP count by
hand, the controls of the output check (fp8, bfloat16 masters, a state left
unchanged, each of the six mechanisms left out: each must come out as not
correct), the runner's limits rule, the world (ONE table moves) and the
cell's rehearsal.  The readers and the manifest's lists are in
``test_bench_zaya_readers.py``: ``--dist loadfile`` puts a whole file on one
worker and starts the files with the most cases first."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core
from benchmarks.reference import zaya_ref as ref

runner = core.load_runner("train_cca_moe")
TINY = core.load_json(core.BENCH_DIR + "/configs/tiny-zaya-rehearsal.json")
CELL = core.load_json(core.BENCH_DIR + "/configs/zaya1-8b.json")
NAME = "train-zaya1-8b-ep2-8k"
TRAFFIC = {"seq_len": 96, "micro_batch": 2, "dtype": "float32",
           "ce_chunk_tokens": 48, "clip": 1.0,
           "optimizer": {"type": "Adam", "lr": 1e-4, "betas": [0.9, 0.999],
                         "eps": 1e-8},
           "scheduler": {"type": "WarmupLR", "params": {
               "warmup_min_lr": 1e-6, "warmup_max_lr": 1e-4,
               "warmup_num_steps": 2000, "warmup_type": "linear"}}}
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
ROW = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048,
    "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
    "max_position_embeddings": 131072, "model_type": "zaya",
    "moe_intermediate_size": 2048, "num_attention_heads": 8,
    "num_experts": 16, "num_experts_per_tok": 1, "num_hidden_layers": 40,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272}
HELD = {"layers_held": CELL["layers_held"], "routed_experts_held": 8,
        "vocab_rows_held": 32784}
DEPTH = CELL["layers_held"]
LAYER = 106_919_698


def _ids(seed, b=2, s=96):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, TINY["vocab_size"], size=(b, s + 1), dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


# ------------------------------------------------------- the configuration
def test_the_configuration_is_the_published_row_key_for_key():
    for key, value in ROW.items():
        assert CELL[key] == value, key
    manifest = core.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == "zaya1-8b")
    assert entry["source"] == CELL["source"] == (
        "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json")
    assert entry["file"] == "benchmarks/configs/zaya1-8b.json"
    assert entry["reduced"] == CELL["reduced"] == list(HELD)
    assert (CELL["first_layer_held"], CELL["first_expert_held"]) == (0, 0)
    # everything beside the published keys is the cut or says what was done
    assert set(CELL) - set(ROW) - set(HELD) == {
        "first_layer_held", "first_expert_held", "initializer_range",
        "source", "reduced", "assumed", "program_preset", "deployment",
        "sizing", "distorts"}
    assert {"reports", "block", "residual_scaling", "latent_widths",
            "value_shift", "convolutions", "qk_mean", "qk_norm_temperature",
            "rotary", "router", "experts", "weights", "kept_as_published",
            "LEFT_OUT"} == set(CELL["assumed"])
    for said in ("updates beta", "mixture-of-depths", "sliding layers"):
        assert said in CELL["assumed"]["LEFT_OUT"], said
    for said in ("VECTOR", "NORMED input", "EXACT erf", "NOT renormalised",
                 "takes no gradient"):
        assert said in CELL["assumed"]["router"], said
    for said in ("2510.04476", "2511.17127"):
        assert said in CELL["assumed"]["reports"] and said in ref.__doc__
    for said in ("0.5 slots", "32,784-row head", "five layers",
                 "seeded routing"):
        assert said in CELL["distorts"], said
    for said in ("eight chips share each layer", "two-way expert-parallel",
                 "40 / 5", "16 / 8", "262,272 / 32,784"):
        assert said in CELL["deployment"], said
    assert CELL["program_preset"] == (
        f"ZayaConfig.zaya1_8b(layers_held={DEPTH}, first_layer_held=0, "
        "routed_experts_held=8, first_expert_held=0, vocab_rows_held=32784)")


def test_the_cut_is_a_stage_and_the_shares_and_keeps_the_floors():
    assert ref.layers_held(CELL) == DEPTH
    assert DEPTH >= 4 and 40 % DEPTH == 0               # a pipeline stage
    assert ref.share(CELL) == {"first_expert": 0, "experts": 8,
                               "vocab": 32784}
    assert 32784 * 8 == CELL["vocab_size"] and 8 * 2 == CELL["num_experts"]
    # no width is in ``reduced``, and none differs from the row
    assert not [k for k in CELL["reduced"] if k.endswith(
        ("_dim", "_rank", "_size")) or "per_tok" in k or "heads" in k]
    assert ref.layer_matmul_params(CELL) == 5_242_880 + 327_680 + 659_456
    assert ref.routed_expert_params(CELL) == 12_582_912
    held = DEPTH * LAYER - 256 + 32784 * 2048 + 2048
    assert ref.num_params(CELL) == held
    sizing = CELL["sizing"]
    assert sizing["held_params"] == held and sizing["layer_held"] == LAYER
    for number, where in (("5,242,880", "attention"),
                          ("332,802", "convolutions"),
                          ("660,240", "router"),
                          ("12,582,912", "routed_expert"),
                          ("67,141,632", "table_held")):
        assert number in sizing[where]
    model = runner.program_model(CELL, dict(TRAFFIC, seq_len=8192))
    assert model.num_params() == held
    assert model.stack().tied_head
    for wrong in ({"num_experts_per_tok": 2}, {"sliding_window": 4096},
                  {"first_layer_held": 38}, {"layer_types": ["hybrid"] * 39}):
        with pytest.raises(ValueError):
            ref.layers_held(dict(CELL, **wrong))
    with pytest.raises(ValueError, match="transpose"):
        runner.program_model(dict(CELL, tie_word_embeddings=False), TRAFFIC)


def test_flops_by_hand():
    """6 x the matmul weights a token passes (the latent's projections, the
    per-head convolution, the router's MLP, half an expert, the table once
    as the head) and the latent attention's causal half."""
    weights = DEPTH * (5_242_880 + 327_680 + 659_456 + 0.5 * 12_582_912) \
        + 2048 * 32784
    attention = DEPTH * 6 * 1024 * 8192
    assert ref.flops_per_token(CELL, 8192, 0.5) == pytest.approx(
        6 * weights + attention)
    if DEPTH == 5:      # ISSUE 56's shares: 41 % CCA, 20 % routed, 39 % head
        forward = ref.flops_per_token(CELL, 8192, 0.5) / 3
        assert forward == pytest.approx(343e6, rel=0.01)
        assert 2 * 2048 * 32784 / forward == pytest.approx(0.39, abs=0.005)
        cca = 5 * (2 * (5_242_880 + 327_680) + 2 * 1024 * 8192)
        assert cca / forward == pytest.approx(0.41, abs=0.005)
    # every token routed here costs a whole expert
    assert ref.flops_per_token(CELL, 8192, 1.0) - ref.flops_per_token(
        CELL, 8192, 0.0) == 6 * DEPTH * 12_582_912


# ------------------------------------------------------------ the controls
def _numbers(seed, **changed):
    """A control's forward numbers against the reference, tiny size."""
    params = ref.init_params(TINY, seed)
    ids, labels = _ids(seed, b=1)
    want = ref.token_logprobs(params, TINY, ids[0], labels[0])
    got = ref.token_logprobs(params, TINY, ids[0], labels[0], **changed)
    return {"logprob_rms": runner.train.compare_logprobs(got[0], want[0]),
            "routed_set_mismatch_share": runner.hybrid.compare_routing(
                got[1], want[1])}


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_controls_fail_the_forward_comparisons(seed):
    """bf16 (the stated precision) against the float32 reference reads a
    number; fp8 (the next step down) well over it; each mechanism left out
    reads more than bf16 too, by the log-probabilities or (the carried
    state) by the experts chosen."""
    sound = _numbers(seed)
    assert sound["logprob_rms"] == 0
    assert sound["routed_set_mismatch_share"] == 0
    bf16 = _numbers(seed, precision="bfloat16")
    fp8 = _numbers(seed, precision="fp8")
    assert 0 < bf16["logprob_rms"] < 0.03
    assert fp8["logprob_rms"] > 2 * bf16["logprob_rms"]
    for mechanism in ref.MECHANISMS:
        got = _numbers(seed, without=(mechanism,))
        if mechanism == "router_state":
            # the carried state decides the EXPERT (whose output a weight
            # near 1/16 scales: the log-probabilities hardly see it):
            # without it the later layers' choices are another model's
            assert got["routed_set_mismatch_share"] > max(
                3 * bf16["routed_set_mismatch_share"], 0.1)
            continue
        assert got["logprob_rms"] > 3 * bf16["logprob_rms"], mechanism


def _first_steps(seed, **changed):
    params = ref.init_params(TINY, seed)
    ids, labels = _ids(seed)
    init = runner.train.sample_leaves(params, runner.sampled_tops(TINY))
    steps = []
    for how in ({}, changed):
        _, grads, _, _ = ref.loss_and_grads(params, TINY, ids, labels, **how)
        steps.append(runner.plain_first_step(TINY, TRAFFIC, params, grads))
    want, got = steps
    return runner.train.compare_first_step(got, want, init)["grad_rel_err"]


def test_controls_fail_the_gradient_comparisons():
    limits = core.load_json(runner.REHEARSAL_LIMITS)
    bf16 = _first_steps(5, precision="bfloat16")
    fp8 = _first_steps(5, precision="fp8")
    assert 0 < bf16 < 0.15 and fp8 > 3 * bf16
    assert fp8 > limits["grad_rel_err"]["limit"]
    # an untied head gives the table the embedding's scatter alone, a
    # renormalised weight gives the router nothing
    for mechanism in ("tied_head", "routed_weight"):
        assert _first_steps(5, without=(mechanism,)) > limits[
            "grad_rel_err"]["limit"], mechanism


def test_rehearsal_limits_stand_clear_of_their_controls():
    limits = core.load_json(runner.REHEARSAL_LIMITS)
    assert limits["device"]["platform"] == "cpu"
    assert set(limits) == {"grad_rel_err", "adam_update_rel_err", "device"}
    for v in (limits["grad_rel_err"], limits["adam_update_rel_err"]):
        assert v["sound_largest"] < v["limit"] < v["control_smallest"]
        assert v["control_smallest"] >= 3 * v["sound_largest"]
    assert limits["adam_update_rel_err"]["control"] == runner.UNCHANGED


def _reading(grad, adam, fp8=None, low=None, slots=0.0002, lp=0.004,
             loss=0.0005, **left_out):
    sound = {"grad_rel_err": grad, "adam_update_rel_err": adam,
             "logprob_rms": lp, "first_loss_abs_diff": loss,
             "routed_set_mismatch_share": 0.002,
             "slots_held_rel_diff": slots}
    r = {"program": sound}
    if fp8 is not None:
        r["control_fp8"] = {
            "grad_rel_err": fp8, "logprob_rms": 0.3,
            "routed_set_mismatch_share": 0.4, "first_loss_abs_diff": 0.01,
            "slots_held_rel_diff": 0.05}
        r["control_bf16_masters"] = {"adam_update_rel_err": low}
        r["control_state_unchanged"] = {"adam_update_rel_err": 1.0}
        for control in runner.LEFT_OUT:
            r[control] = left_out.get(control, {
                "logprob_rms": 0.2, "routed_set_mismatch_share": 0.01})
    return r


def test_limits_rule_and_the_mechanism_controls(monkeypatch):
    monkeypatch.setattr(runner, "KEPT", {
        "logprob_rms": (0.03, "control_fp8"),
        "routed_set_mismatch_share": (0.03, "control_fp8")})
    monkeypatch.setattr(runner, "GUARDS", {"slots_held_rel_diff": 0.001})
    readings = [_reading(0.006, 0.001, 0.07, 30.0),
                _reading(0.005, 0.0009, 0.08, 31.0),
                _reading(0.0055, 0.0008, 0.09, 32.0), _reading(0.004, 0.0005)]
    got = runner.limits_from(readings)
    assert set(got) == {"grad_rel_err", "adam_update_rel_err"}
    assert got["grad_rel_err"]["limit"] == pytest.approx(
        (0.006 * 0.07) ** 0.5)
    assert got["grad_rel_err"]["sound_seeds"] == 4
    assert got["adam_update_rel_err"]["control"] == "control_state_unchanged"
    assert got["adam_update_rel_err"]["limit"] == pytest.approx(0.001 ** 0.5)
    with pytest.raises(SystemExit, match="control_bf16_masters would pass"):
        runner.limits_from(readings[:3] + [
            _reading(0.004, 0.0005, 0.08, 0.02)])
    # a control under three times the sound runs refuses the limits
    with pytest.raises(SystemExit, match="grad_rel_err"):
        runner.limits_from(readings[:2] + [
            _reading(0.03, 0.0008, 0.07, 30.0)])
    # a kept limit that a sound run breaks
    with pytest.raises(SystemExit, match="logprob_rms: the kept limit"):
        runner.limits_from(readings + [_reading(0.004, 0.0005, lp=1.0)])
    # at the cell's size (on the chip) a control that would pass refuses
    # them too: each mechanism left out
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert runner.limits_from(readings) == got
    passes = {"logprob_rms": 0.001, "routed_set_mismatch_share": 0.0}
    for control in runner.LEFT_OUT:
        with pytest.raises(SystemExit, match=control + " would pass"):
            runner.limits_from(readings[:3] + [_reading(
                0.004, 0.0005, 0.08, 30.0, **{control: passes})])
    # one broken limit refuses a control: the experts chosen alone
    assert runner.refused(dict(passes, routed_set_mismatch_share=0.5),
                          got) == ["routed_set_mismatch_share"]
    # a limit no control bounds leaves the sound readings three times of room
    with pytest.raises(SystemExit, match="slots_held_rel_diff: the guard"):
        runner.limits_from(readings + [_reading(0.004, 0.0005, slots=0.0004)])
    assert sorted(runner.LEFT_OUT.values()) == sorted(ref.MECHANISMS)


def test_the_kept_limits_stand_where_the_chip_read_them():
    """The limits kept in the runner's file: each with its control, the
    gradient's and the update's in the cell's file."""
    assert set(runner.KEPT) == {"logprob_rms", "routed_set_mismatch_share"}
    assert all(control == "control_fp8" for _, control in
               runner.KEPT.values())
    assert all(0 < limit < 1 for limit, _ in runner.KEPT.values())
    # the first step's loss is printed and has no limit here, as in the
    # Mellum cell: nothing separates three times clear (the runner's file)
    assert set(runner.GUARDS) == {"slots_held_rel_diff"}
    assert 0 < runner.GUARDS["slots_held_rel_diff"] < 0.1
    assert runner.KEPT["logprob_rms"][0] == pytest.approx(
        (0.00942 * 0.06907) ** 0.5, rel=0.01)
    assert runner.KEPT["routed_set_mismatch_share"][0] == pytest.approx(
        (0.0103 * 0.05635) ** 0.5, rel=0.01)
    assert set(runner.CONTROL_OF) == {"grad_rel_err", "adam_update_rel_err"}
    limits = core.load_limits(NAME)
    assert limits["device"]["platform"] == "tpu"
    for number, control in runner.CONTROL_OF.items():
        v = limits[number]
        assert v["control"] == control
        assert v["sound_largest"] < v["limit"] < v["control_smallest"]
        assert v["control_smallest"] >= 3 * v["sound_largest"]
        assert (v["sound_seeds"], v["control_seeds"]) == (8, 4)
    assert set(runner.held_limits(limits)) == {
        "grad_rel_err", "adam_update_rel_err", *runner.KEPT, *runner.GUARDS}


def test_sampled_leaves_cover_the_table_the_norm_and_three_layers():
    assert runner.sampled_tops(CELL) == {
        "embed_tokens", "final_norm_scale", "layers_0",
        f"layers_{DEPTH // 2}", f"layers_{DEPTH - 1}"}
    assert runner.sampled_tops(TINY) == {
        "embed_tokens", "final_norm_scale", "layers_0", "layers_1",
        "layers_2"}
    assert runner.vocab(CELL) == 32784


def test_the_world_renames_this_models_one_table():
    """Under a world the run's weights are the world's with the ONE table's
    rows moved to the run's names (it is the head too: no columns move): the
    first batch's loss is the world's."""
    from benchmarks import traffic_gen

    assert runner.swa.TABLE_COLUMNS == []
    assert runner.swa.TABLE_ROWS == [("embed_tokens", "embedding")]
    traffic = dict(TRAFFIC, token_dist={"kind": "zipf", "exponent": 1.1},
                   world={"seed": 3})
    vocab = runner.vocab(TINY)
    losses, tables = [], []
    for seed in (3, 2**31 + 5):
        batches = traffic_gen.TokenBatches(traffic, vocab, seed)
        assert batches.world_seed == 3
        params = runner.seeded_params(TINY, batches)
        assert "lm_head_kernel" not in params
        first = batches.batch(0)
        losses.append(float(ref.loss_and_grads(
            params, TINY, jnp.asarray(first["input_ids"]),
            jnp.asarray(first["labels"]))[0]))
        tables.append((np.asarray(params["embed_tokens"]["embedding"]),
                       batches.order))
    assert losses[0] == pytest.approx(losses[1], rel=1e-5)
    (a, order_a), (b, order_b) = tables
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a[order_a], b[order_b])


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
    assert last["failed"] == 0
    assert last["attempted"] > 0 and last["metrics"] == {}
    assert last["rehearsal"] is True and last["device"]["platform"] == "cpu"
    assert last["counts"]["steps"] > 0
    checks = {c["check"]: c for c in lines if "check" in c}
    assert set(checks) == {
        "grad_rel_err_vs_reference", "adam_update_rel_err_vs_reference",
        "logprob_rms_vs_reference", "routed_set_mismatch_share_vs_reference",
        "slots_held_rel_diff_vs_reference", "moe_slots_dropped",
        "layers_of_every_kind_counted", "nonfinite_losses",
        "loss_fall_over_window", "compiles_in_window"}
    # every check but the loss's fall (a couple of hundred steps at a rate
    # of 1e-6 on fresh batches go either way at this size) must hold
    assert all(c["ok"] for name, c in checks.items()
               if name != "loss_fall_over_window"), checks
    assert last["correct"] is checks["loss_fall_over_window"]["ok"]
    assert checks["compiles_in_window"]["value"] == 0
    assert checks["moe_slots_dropped"]["value"] == 0
    assert checks["layers_of_every_kind_counted"]["value"] == 1
    # the first step's loss is printed beside the reference's, not checked
    told = next(x for x in lines if x.get("progress") == "reference")
    assert told["first_loss_abs_diff"] < 1e-4
    # the window's steps counted themselves, every one of them
    told = next(x for x in lines if x.get("progress") == "window_counters")
    assert told["moe_slots_held_min"] <= told["moe_slots_held"] \
        <= told["moe_slots_held_max"]
    assert told["moe_slots_dropped"] == 0
    assert (told["cca_layer_applications"],
            told["moe_layer_applications"]) == (3, 3)
    assert told["moe_tokens_unrouted_here"] == pytest.approx(
        2 * 96 - told["moe_slots_held"])

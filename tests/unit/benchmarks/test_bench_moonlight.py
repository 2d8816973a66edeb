"""The latent-attention + sigmoid-mixture cell on the CPU: the configuration
against the published row, the cut's floors and statements, the controls
against the comparisons, the limits' rule, the world, and the rehearsal cell
end to end."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core
from benchmarks.reference import moonlight_ref as ref

runner = core.load_runner("train_mla_moe")
TINY = core.load_json(core.BENCH_DIR
                      + "/configs/tiny-moonlight-rehearsal.json")
CELL = core.load_json(core.BENCH_DIR + "/configs/moonlight-16b-a3b.json")
NAME = "train-moonlight-16b-ep8-8k"
TRAFFIC = {"seq_len": 64, "micro_batch": 2, "dtype": "float32",
           "ce_chunk_tokens": 48}
#: the catalog row's ``config`` (model-configs/architectures.jsonl,
#: Moonlight-16B-A3B), key for key
ROW = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_hidden_layers": 27,
    "num_key_value_heads": 16, "num_nextn_predict_layers": 0,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 50000,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
    "seq_aux": True, "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}
HELD = {"layers_held": 6, "routed_experts_held": 8, "vocab_rows_held": 20480}


def _ids(seed, b=2, s=64):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, TINY["vocab_size"], size=(b, s + 1), dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


# ------------------------------------------------------- the configuration
def test_the_configuration_is_the_published_row_key_for_key():
    for key, value in ROW.items():
        assert key in CELL and CELL[key] == value, key
    manifest = core.load_manifest()
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "moonlight-16b-a3b")
    assert entry["source"] == CELL["source"] == (
        "https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/"
        "config.json")
    assert entry["file"] == "benchmarks/configs/moonlight-16b-a3b.json"
    assert entry["reduced"] == CELL["reduced"] == list(HELD)
    assert {k: CELL[k] for k in HELD} == HELD
    assert (CELL["first_layer_held"], CELL["first_expert_held"]) == (0, 0)
    # everything beside the published keys is the cut or says what was done
    assert set(CELL) - set(ROW) - set(HELD) == {
        "first_layer_held", "first_expert_held", "initializer_range",
        "aux_loss_alpha", "source", "reduced", "assumed", "program_preset",
        "deployment", "sizing", "distorts"}
    assert {"reports", "block", "latent", "rotary", "router",
            "selection_bias", "experts", "aux_loss_alpha", "weights",
            "kept_as_published", "LEFT_OUT"} == set(CELL["assumed"])
    assert CELL["aux_loss_alpha"] == ref.ALPHA == 1e-4
    for said in ("update rule", "exchange between the eight chips",
                 "dropout", "absorbed decode"):
        assert said in CELL["assumed"]["LEFT_OUT"], said
    for said in ("halves convention", "adjacent pairs", "permutation",
                 "No rope_scaling"):
        assert said in CELL["assumed"]["rotary"], said
    for said in ("2405.04434", "2412.19437"):
        assert said in CELL["assumed"]["reports"] and said in ref.__doc__
    for said in ("0.75 slots", "Mellum cell", "20,480 rows",
                 "moe_slots_held"):
        assert said in CELL["distorts"], said
    for said in ("eight chips share each layer", "6 + 5 + 5 + 5 + 6",
                 "27 / 6", "64 / 8", "163,840 / 20,480"):
        assert said in CELL["deployment"], said
    assert CELL["program_preset"] == (
        "MoonlightConfig.moonlight_16b_a3b(layers_held=6, first_layer_held=0,"
        " routed_experts_held=8, first_expert_held=0, vocab_rows_held=20480)")


def test_the_cut_is_a_stage_and_the_shares_and_keeps_the_floors():
    kinds = ref.layer_kinds(CELL)
    assert kinds == [ref.DENSE] + [ref.SPARSE] * 5
    assert kinds.count(ref.SPARSE) >= 4     # the guide's floor
    assert ref.share(CELL) == {"first_expert": 0, "experts": 8,
                               "vocab": 20480}
    assert 20480 * 8 == CELL["vocab_size"]
    assert 8 * 8 == CELL["n_routed_experts"]
    # no width is in ``reduced``, and none differs from the row
    assert not [k for k in CELL["reduced"] if k.endswith(
        ("_dim", "_rank", "_size")) or "per_tok" in k or "heads" in k]
    assert ref.widths(CELL) == (16, 512, 128, 64, 128)
    assert ref.shared_width(CELL) == 2816
    sizing = CELL["sizing"]
    assert sizing["held_params"] == ref.num_params(CELL) == 668_890_432
    for number, where in (("13,763,072", "attention"),
                          ("82,973,184", "dense_layer"),
                          ("100,405,824", "sparse_layer"),
                          ("83,886,080", "tables_held"),
                          ("15,960,110,208", "whole_model")):
        assert number in sizing[where], where
    for wrong in ({"q_lora_rank": 1536}, {"n_group": 8},
                  {"scoring_func": "softmax"}, {"first_layer_held": 22},
                  {"rope_scaling": {"type": "yarn"}}):
        with pytest.raises(ValueError):
            ref.layer_kinds(dict(CELL, **wrong))
    with pytest.raises(ValueError, match="table of its own"):
        runner.program_model(dict(CELL, tie_word_embeddings=True), TRAFFIC)


# ---------------------------------------------------------------- the controls
def _numbers(seed, **changed):
    params = ref.init_params(TINY, seed)
    ids, labels = _ids(seed, b=1)
    sound_lp, sound = ref.token_logprobs(params, TINY, ids[0], labels[0])
    lp, chosen = ref.token_logprobs(params, TINY, ids[0], labels[0],
                                    **changed)
    return (runner.train.compare_logprobs(np.asarray(lp),
                                          np.asarray(sound_lp)),
            runner.hybrid.compare_routing(np.asarray(chosen),
                                          np.asarray(sound)))


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_controls_move_the_forward_comparisons(seed):
    """Each control against the sound reference at the tiny preset: the
    log-probabilities move by far more than float32's rounding, and where
    the mechanism stands before a router, so does the routing."""
    assert _numbers(seed) == (0.0, 0.0)
    alone = {**runner.LOW_PRECISION, **runner.READ_BESIDE}
    for control, which in alone.items():
        lp, _ = _numbers(seed, precision="fp8", low=which)
        assert lp > 1e-4, control
    assert _numbers(seed, precision="fp8")[0] > max(
        _numbers(seed, precision="fp8", low=which)[0]
        for which in alone.values())
    for control, mechanism in runner.LEFT_OUT.items():
        lp, _ = _numbers(seed, without=(mechanism,))
        assert lp > 1e-4, control
    assert sorted(runner.LEFT_OUT.values()) == sorted(ref.MECHANISMS)
    assert set(alone.values()) == set(ref.LOW) - {"all"}


def test_rehearsal_limits_stand_clear_of_their_controls():
    limits = core.load_json(runner.REHEARSAL_LIMITS)
    assert limits["device"]["platform"] == "cpu"
    assert set(limits) == {"grad_rel_err", "adam_update_rel_err", "device"}
    for v in (limits["grad_rel_err"], limits["adam_update_rel_err"]):
        assert v["sound_largest"] < v["limit"] < v["control_smallest"]
        assert v["control_smallest"] >= 3 * v["sound_largest"]
    assert limits["adam_update_rel_err"]["control"] == runner.UNCHANGED


def _reading(grad, adam, fp8=None, low=None, lp=0.004, **others):
    sound = {"grad_rel_err": grad, "adam_update_rel_err": adam,
             "logprob_rms": lp, "first_loss_abs_diff": 0.0005,
             "routed_set_mismatch_share": 0.002,
             "slots_held_rel_diff": 0.0002}
    r = {"program": sound}
    if fp8 is not None:
        r["control_fp8"] = {
            "grad_rel_err": fp8, "logprob_rms": 0.3,
            "routed_set_mismatch_share": 0.4, "first_loss_abs_diff": 0.01,
            "slots_held_rel_diff": 0.05}
        r["control_bf16_masters"] = {"adam_update_rel_err": low}
        r["control_state_unchanged"] = {"adam_update_rel_err": 1.0}
        for control in (*runner.LOW_PRECISION, *runner.LEFT_OUT):
            r[control] = others.get(control, {
                "logprob_rms": 0.2, "routed_set_mismatch_share": 0.01})
    return r


def test_limits_rule_and_every_control(monkeypatch):
    monkeypatch.setattr(runner, "KEPT", {
        "logprob_rms": (0.03, "control_fp8"),
        "routed_set_mismatch_share": (0.03, "control_fp8"),
        "slots_held_rel_diff": (0.003, "control_fp8")})
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
    # them too: the attention's products or the up-projection in fp8, and
    # each mechanism left out
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert runner.limits_from(readings) == got
    passes = {"logprob_rms": 0.001, "routed_set_mismatch_share": 0.0}
    for control in (*runner.LOW_PRECISION, *runner.LEFT_OUT):
        with pytest.raises(SystemExit):
            runner.limits_from(readings[:3] + [_reading(
                0.004, 0.0005, 0.08, 30.0, **{control: passes})])
    # one broken limit refuses a control: the experts chosen alone
    assert runner.refused(dict(passes, routed_set_mismatch_share=0.5),
                          got) == ["routed_set_mismatch_share"]


def test_the_kept_limits_stand_where_the_chip_read_them():
    """The limits kept in the runner's file, each the geometric mean of the
    largest sound reading and the smallest of the fp8 control's (my chip
    runs, PR 61, call 3); the gradient's and the update's in the cell's
    file; the products-alone reading refuses nothing."""
    assert set(runner.KEPT) == {"logprob_rms", "routed_set_mismatch_share",
                                "slots_held_rel_diff"}
    assert all(control == "control_fp8" for _, control in
               runner.KEPT.values())
    for number, sound, control in (
            ("logprob_rms", 0.034261, 0.171251),
            ("routed_set_mismatch_share", 0.023828, 0.134570),
            ("slots_held_rel_diff", 0.0029705, 0.0156666)):
        limit = runner.KEPT[number][0]
        assert limit == pytest.approx((sound * control) ** 0.5, rel=0.01)
        assert 3 * sound <= control and sound < limit < control
    assert list(runner.LOW_PRECISION) == ["control_fp8_up_projection"]
    assert list(runner.READ_BESIDE) == ["control_fp8_products"]
    # the up-projection alone in fp8 read 0.0933 and 0.0775 at the least:
    # over both limits in every seed; the products alone 0.0668 and 0.0512
    assert 0.0933 > runner.LOGPROB_RMS_LIMIT > 0.0668
    assert 0.0775 > runner.ROUTED_SET_MISMATCH_LIMIT > 0.0512
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
        "grad_rel_err", "adam_update_rel_err", *runner.KEPT}


def test_sampled_leaves_cover_the_tables_the_norm_and_three_layers():
    """The dense layer, the first sparse one and the last held: the latent
    projections, q's rotary columns, the router, the experts held, the
    shared experts, both tables."""
    assert runner.sampled_tops(CELL) == {
        "embed_tokens", "lm_head_kernel", "final_norm_scale", "layers_0",
        "layers_1", "layers_5"}
    assert runner.sampled_tops(TINY) == {
        "embed_tokens", "lm_head_kernel", "final_norm_scale", "layers_0",
        "layers_1", "layers_2"}
    assert runner.vocab(CELL) == 20480
    shapes = ref.layer_shapes(CELL, ref.share(CELL), ref.SPARSE)
    for leaf in (("attn", "kv_a_proj", "kernel"),
                 ("attn", "q_rope_proj", "kernel"),
                 ("attn", "k_b_proj", "kernel"), ("moe", "router_kernel"),
                 ("moe", "experts_gate_up_proj"),
                 ("shared_experts", "down_proj", "kernel")):
        assert leaf in shapes
    assert shapes[("attn", "kv_a_proj", "kernel")] == (2048, 576)
    assert shapes[("attn", "q_rope_proj", "kernel")] == (2048, 16 * 64)


def test_the_world_renames_both_tables():
    """Under a world the run's weights are the world's with the embedding's
    rows and the head's columns moved to the run's names: the first batch's
    loss is the world's."""
    from benchmarks import traffic_gen

    traffic = dict(TRAFFIC, token_dist={"kind": "zipf", "exponent": 1.1},
                   world={"seed": 3})
    vocab = runner.vocab(TINY)
    losses, tables = [], []
    for seed in (3, 2**31 + 5):
        batches = traffic_gen.TokenBatches(traffic, vocab, seed)
        assert batches.world_seed == 3
        params = runner.seeded_params(TINY, batches)
        first = batches.batch(0)
        losses.append(float(ref.loss_and_grads(
            params, TINY, jnp.asarray(first["input_ids"]),
            jnp.asarray(first["labels"]))[0]))
        tables.append((np.asarray(params["embed_tokens"]["embedding"]),
                       np.asarray(params["lm_head_kernel"]), batches.order))
    assert losses[0] == pytest.approx(losses[1], rel=1e-5)
    (a, head_a, order_a), (b, head_b, order_b) = tables
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a[order_a], b[order_b])
    np.testing.assert_array_equal(head_a[:, order_a], head_b[:, order_b])


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
    # the first step's loss (cross entropy + balance) is printed beside the
    # reference's, not checked
    told = next(x for x in lines if x.get("progress") == "reference")
    assert told["first_loss_abs_diff"] < 1e-4
    assert 1e-4 < told["balance_loss"] < 1e-3
    # the window's steps counted themselves, every one of them
    told = next(x for x in lines if x.get("progress") == "window_counters")
    assert told["moe_slots_held_min"] <= told["moe_slots_held"] \
        <= told["moe_slots_held_max"]
    assert told["moe_slots_dropped"] == 0
    assert (told["mla_layer_applications"], told["moe_layer_applications"],
            told["dense_mlp_layer_applications"]) == (3, 2, 1)
    assert 1e-4 < told["moe_balance_loss"] < 1e-3

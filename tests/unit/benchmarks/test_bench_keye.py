"""The learned-sparse-attention cell's part of the benchmark on the CPU: the
configuration file against the published row key for key, its ``sizing``
against ``num_params()``, the controls of the output check (fp8, bfloat16
masters, a state left unchanged, each of the six mechanisms left out: each
must come out as not correct), the runner's limits rule, the FLOP count by
hand, the world, and the cell's rehearsal (the new readers:
``test_bench_keye_readers.py``)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core
from benchmarks.reference import keye_ref as ref

runner = core.load_runner("train_dsa_moe")
TINY = core.load_json(core.BENCH_DIR + "/configs/tiny-keye-rehearsal.json")
CELL = core.load_json(core.BENCH_DIR + "/configs/keye-vl-2.0-30b-a3b.json")
NAME = "train-keye-vl2-ep8-16k"
TRAFFIC = {"seq_len": 96, "micro_batch": 2, "dtype": "float32",
           "ce_chunk_tokens": 48, "clip": 1.0,
           "optimizer": {"type": "Adam", "lr": 1e-4, "betas": [0.9, 0.999],
                         "eps": 1e-8},
           "scheduler": {"type": "WarmupLR", "params": {
               "warmup_min_lr": 1e-6, "warmup_max_lr": 1e-4,
               "warmup_num_steps": 2000, "warmup_type": "linear"}}}
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
ROW = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
HELD = {"layers_held": 6, "routed_experts_held": 16, "vocab_rows_held": 18992}
CHOSEN = 2048 * 2049 // 2 + (16384 - 2048) * 2048        # a sequence, a layer


def _ids(seed, b=2, s=96):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, TINY["vocab_size"], size=(b, s + 1), dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


# ------------------------------------------------------- the configuration
def test_the_configuration_is_the_published_row_key_for_key():
    for key, value in ROW.items():
        assert CELL[key] == value, key
    manifest = core.load_manifest()
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "keye-vl-2.0-30b-a3b")
    assert manifest["configs"][-1] is entry
    assert entry["source"] == CELL["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
        "config.json")
    assert entry["file"] == "benchmarks/configs/keye-vl-2.0-30b-a3b.json"
    assert entry["reduced"] == CELL["reduced"] == list(HELD)
    assert {k: CELL[k] for k in HELD} == HELD
    assert (CELL["first_layer_held"], CELL["first_expert_held"]) == (18, 0)
    # everything beside the published keys is the cut or says what was done
    assert set(CELL) - set(ROW) - set(HELD) == {
        "first_layer_held", "first_expert_held", "initializer_range",
        "source", "reduced", "assumed", "program_preset", "deployment",
        "sizing", "distorts"}
    assert {"block", "rotary", "indexer", "chunk_sizes", "selection",
            "indexer_loss", "mixture", "weights", "kept_as_published",
            "LEFT_OUT"} == set(CELL["assumed"])
    for said in ("vision tower", "warm-up", "multi-token-prediction"):
        assert said in CELL["assumed"]["LEFT_OUT"], said
    for said in ("Hadamard", "LayerNorm", "no query latent", "TEMPORAL"):
        assert said in CELL["assumed"]["indexer"], said
    for said in ("1.0 slot", "18,992 rows", "text only", "seeded weights"):
        assert said in CELL["distorts"], said
    assert "eight chips share each layer" in CELL["deployment"]
    # the reference's docstring says the same assumptions
    for said in ("*assumed*", "LEFT OUT", "Hadamard", "lower position first",
                 "q_chunk_size", "coefficient 1"):
        assert said in ref.__doc__, said


def test_the_cut_is_a_pipeline_stage_and_the_shares():
    assert ref.layers_held(CELL) == 6
    assert ref.share(CELL) == {"first_expert": 0, "experts": 16,
                               "vocab": 18992}
    assert ref.indexer_params(CELL) == 2048 * (1024 + 64 + 16)
    assert ref.layer_matmul_params(CELL) == 18_874_368 + 2_260_992 + 262_144
    assert ref.routed_expert_params(CELL) == 4_718_592
    assert ref.num_params(CELL) == 659_190_016
    sizing = CELL["sizing"]
    assert sizing["held_params"] == 659_190_016
    assert sizing["layer_held"] == 96_899_456
    assert 6 * 96_899_456 + 77_791_232 + 2_048 == 659_190_016
    for number, where in (("18,874,368", "attention"),
                          ("2,261,120", "indexer"),
                          ("4,718,592", "routed_expert"),
                          ("77,791,232", "tables_held")):
        assert number in sizing[where]
    model = runner.program_model(CELL, dict(TRAFFIC, seq_len=16384))
    assert model.num_params() == 659_190_016
    with pytest.raises(ValueError):
        ref.layers_held(dict(CELL, mlp_only_layers=[0]))
    with pytest.raises(ValueError):
        ref.layers_held(dict(CELL, first_layer_held=44))


def test_flops_by_hand():
    """6 x the matmul weights a token passes, and a pair: 14 x 4096 over the
    chosen (the main attention's five products forward and backward and the
    loss's second q . k), 6 x 1024 over the causal (the indexer's scores)."""
    causal = 16384 * 16385 // 2
    assert ref.pairs(CELL, 16384) == (CHOSEN, causal)
    assert CHOSEN == 31_458_304 and round(100 * CHOSEN / causal) == 23
    weights = 6 * (18_874_368 + 2_260_992 + 262_144 + 1.0 * 4_718_592) \
        + 2048 * 18992
    pairs = 6 * (14 * 4096 * CHOSEN + 6 * 1024 * causal) / 16384
    assert ref.flops_per_token(CELL, 16384, 1.0) == pytest.approx(
        6 * weights + pairs)
    # a step: 35.0 TFLOP, of which the chosen pairs' attention 10.8
    step = 16384 * ref.flops_per_token(CELL, 16384, 1.0)
    assert step == pytest.approx(35.0e12, rel=0.001)
    assert 16384 * (6 * 14 * 4096 * CHOSEN / 16384) == pytest.approx(
        10.8e12, rel=0.01)
    # rows that see fewer keys than topk keep them all
    assert ref.pairs(TINY, 10) == (55, 55)


# ------------------------------------------------------------ the controls
def _numbers(seed, **changed):
    """A control's forward numbers against the reference, tiny size."""
    params = ref.init_params(TINY, seed)
    ids, labels = _ids(seed, b=1)
    want = ref.token_logprobs(params, TINY, ids[0], labels[0])
    got = ref.token_logprobs(params, TINY, ids[0], labels[0], **changed)
    mismatch, selected = runner.compare_keys(got[3], want[3])
    return {"logprob_rms": runner.train.compare_logprobs(got[0], want[0]),
            "routed_set_mismatch_share": runner.hybrid.compare_routing(
                got[2], want[2]),
            "dsa_selected_set_mismatch": mismatch, "selected": selected,
            "kl": abs(float(got[1]) - float(want[1]))}


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_controls_fail_the_forward_comparisons(seed):
    """bf16 (the stated precision) against the float32 reference reads a
    number; fp8 (the next step down) at least three times that; each
    mechanism that shows on the forward pass reads more than bf16 too, or
    breaks the exact count."""
    expected = 2 * runner.pairs_expected(TINY, 1, 96) // ref.layers_held(TINY)
    sound = _numbers(seed)
    assert sound["logprob_rms"] == 0 and sound["selected"] == expected
    bf16 = _numbers(seed, precision="bfloat16")
    fp8 = _numbers(seed, precision="fp8")
    # (at this size, 64 wide, bf16 reads 0.005-0.014 and fp8 0.034-0.036:
    # twice clear; at the cell's size they stand eight times apart)
    assert 0 < bf16["logprob_rms"] < 0.03
    assert fp8["logprob_rms"] > 2 * bf16["logprob_rms"]
    assert fp8["dsa_selected_set_mismatch"] > max(
        bf16["dsa_selected_set_mismatch"], 0.01)
    # the selection stays exact whatever the precision
    assert fp8["selected"] == bf16["selected"] == expected
    for mechanism in ("selection", "topk_halved"):
        assert _numbers(seed, without=(mechanism,))["selected"] != expected
    assert _numbers(seed, without=("qk_norm",))[
        "logprob_rms"] > 3 * bf16["logprob_rms"]
    assert _numbers(seed, without=("indexer_relu",))[
        "dsa_selected_set_mismatch"] > 3 * max(
            bf16["dsa_selected_set_mismatch"], 0.01)
    dropped = _numbers(seed, without=("indexer_loss",))
    assert dropped["kl"] > 0.1 and dropped["logprob_rms"] == 0


def _first_steps(seed, **changed):
    params = ref.init_params(TINY, seed)
    ids, labels = _ids(seed)
    init = runner.train.sample_leaves(params, runner.sampled_tops(TINY))
    steps = []
    for how in ({}, changed):
        _, grads, _, _, _ = ref.loss_and_grads(params, TINY, ids, labels,
                                               **how)
        steps.append(runner.plain_first_step(TINY, TRAFFIC, params, grads))
    want, got = steps
    own = {k: v for k, v in want["moment"].items() if ref.INDEXER in k}
    assert len(own) == 5
    return (runner.train.compare_first_step(got, want, init)["grad_rel_err"],
            runner.train.compare_first_step(
                got, dict(want, moment=own), init)["grad_rel_err"])


def test_controls_fail_the_gradient_comparisons():
    limits = core.load_json(runner.REHEARSAL_LIMITS)
    bf16, bf16_indexer = _first_steps(5, precision="bfloat16")
    whole, indexer = _first_steps(5, precision="fp8")
    assert 0 < bf16 < 0.15 and whole > 3 * bf16
    assert indexer > 3 * bf16_indexer > 0
    assert whole > limits["grad_rel_err"]["limit"]
    # the indexer's loss dropped: its leaves get no gradient at all, and
    # nothing else of the gradient moves
    whole, indexer = _first_steps(5, without=("indexer_loss",))
    assert indexer == pytest.approx(1.0) and whole < bf16
    # its input not detached: the trunk gets the indexer's gradient too,
    # the indexer's own leaves what they got before
    whole, indexer = _first_steps(5, without=("indexer_detach",))
    # (the clip by the global norm, which the trunk's share moves)
    assert whole > limits["grad_rel_err"]["limit"]
    assert indexer < 0.01 < whole


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
    sound = {"grad_rel_err": grad, "indexer_grad_rel_err": 2 * grad,
             "adam_update_rel_err": adam, "logprob_rms": lp,
             "first_loss_abs_diff": loss,
             "routed_set_mismatch_share": 0.002,
             "dsa_selected_set_mismatch": 0.003,
             "first_indexer_kl_abs_diff": 0.001,
             "slots_held_rel_diff": slots}
    r = {"program": sound}
    if fp8 is not None:
        r["control_fp8"] = {
            "grad_rel_err": fp8, "indexer_grad_rel_err": 3 * fp8,
            "logprob_rms": 0.3, "routed_set_mismatch_share": 0.4,
            "dsa_selected_set_mismatch": 0.2, "first_loss_abs_diff": 0.01,
            "first_indexer_kl_abs_diff": 0.002}
        r["control_bf16_masters"] = {"adam_update_rel_err": low}
        r["control_state_unchanged"] = {"adam_update_rel_err": 1.0}
        for control in runner.LEFT_OUT:
            r[control] = left_out.get(control, {
                "logprob_rms": 0.2, "dsa_selected_set_mismatch": 0.5,
                "first_indexer_kl_abs_diff": 0.25})
    return r


def test_limits_rule_and_the_mechanism_controls(monkeypatch):
    monkeypatch.setattr(runner, "KEPT", {
        "logprob_rms": (0.03, "control_fp8"),
        "routed_set_mismatch_share": (0.03, "control_fp8"),
        "dsa_selected_set_mismatch": (0.025, "control_fp8"),
        "indexer_grad_rel_err": (0.05, "control_fp8"),
        "first_loss_abs_diff": (0.004, "control_fp8"),
        "first_indexer_kl_abs_diff": (0.016,
                                      "control_indexer_loss_left_out")})
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
    # the first step's own loss: a sound run over its kept limit, or under
    # it and over a third of the control's 0.01, refuses it as any other's
    for loss in (0.0045, 0.0035):
        with pytest.raises(SystemExit, match="first_loss_abs_diff: the kept"):
            runner.limits_from(readings + [
                _reading(0.004, 0.0005, loss=loss)])
    passes = {"logprob_rms": 0.001, "dsa_selected_set_mismatch": 0.0,
              "first_indexer_kl_abs_diff": 0.3,
              "dsa_pairs_selected_first_sequence": 7,
              "dsa_pairs_selected_first_sequence_expected": 7}
    for control in runner.LEFT_OUT:
        # the indexers' loss is the dropped loss's own number: where that
        # control reads like a sound run, no limit stands
        if control == "control_indexer_loss_left_out":
            with pytest.raises(SystemExit,
                               match="first_indexer_kl_abs_diff: the kept"):
                runner.limits_from(readings[:3] + [_reading(
                    0.004, 0.0005, 0.08, 30.0, **{control: dict(
                        passes, first_indexer_kl_abs_diff=0.0)})])
            continue
        with pytest.raises(SystemExit, match=control + " would pass"):
            runner.limits_from(readings[:3] + [_reading(
                0.004, 0.0005, 0.08, 30.0, **{control: dict(
                    passes, first_indexer_kl_abs_diff=0.0)})])
    # the exact count alone refuses a control
    assert runner.refused(dict(passes, first_indexer_kl_abs_diff=0.0,
                               dsa_pairs_selected_first_sequence=8),
                          got) == ["dsa_pairs_selected"]
    # a limit no control bounds leaves the sound readings three times of room
    with pytest.raises(SystemExit, match="slots_held_rel_diff: the guard"):
        runner.limits_from(readings + [_reading(0.004, 0.0005, slots=0.0004)])
    assert sorted(runner.LEFT_OUT.values()) == sorted(ref.MECHANISMS)
    assert set(runner.BY_GRADIENT) <= set(runner.LEFT_OUT)


def test_the_kept_limits_stand_where_the_chip_read_them():
    """The limits kept in the runner's file: each with its control, the
    gradient's and the update's in the cell's file (whose form
    ``test_bench_manifest.py`` holds)."""
    assert set(runner.KEPT) == {
        "logprob_rms", "routed_set_mismatch_share",
        "dsa_selected_set_mismatch", "indexer_grad_rel_err",
        "first_loss_abs_diff", "first_indexer_kl_abs_diff"}
    assert runner.KEPT["first_indexer_kl_abs_diff"][1] == \
        "control_indexer_loss_left_out"
    assert runner.KEPT["first_loss_abs_diff"][1] == "control_fp8"
    assert all(0 < limit < 1 for limit, _ in runner.KEPT.values())
    assert set(runner.GUARDS) == {"slots_held_rel_diff"}
    assert 0 < runner.GUARDS["slots_held_rel_diff"] < 0.1
    assert set(runner.CONTROL_OF) == {"grad_rel_err", "adam_update_rel_err"}


def test_sampled_leaves_cover_tables_norm_and_a_layer():
    assert runner.sampled_tops(CELL) == {
        "embed_tokens", "lm_head_kernel", "final_norm_scale", "layers_0"}
    assert runner.vocab(CELL) == 18992
    assert runner.pairs_expected(CELL, 1, 16384) == 6 * CHOSEN == 188_749_824


def test_the_world_renames_the_tables_of_this_model():
    """Under a world the run's weights are the world's with both tables
    moved to the run's names: the first batch's two losses are the
    world's."""
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
        losses.append([float(x) for x in ref.loss_and_grads(
            params, TINY, jnp.asarray(first["input_ids"]),
            jnp.asarray(first["labels"]))[0]])
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
    assert last["failed"] == 0
    assert last["attempted"] > 0 and last["metrics"] == {}
    assert last["rehearsal"] is True and last["device"]["platform"] == "cpu"
    assert last["counts"]["steps"] > 0
    checks = {c["check"]: c for c in lines if "check" in c}
    assert set(checks) == {
        "grad_rel_err_vs_reference", "indexer_grad_rel_err_vs_reference",
        "adam_update_rel_err_vs_reference", "logprob_rms_vs_reference",
        "routed_set_mismatch_share_vs_reference",
        "dsa_selected_set_mismatch_vs_reference",
        "first_loss_abs_diff_vs_reference",
        "first_indexer_kl_abs_diff_vs_reference",
        "slots_held_rel_diff_vs_reference", "dsa_pairs_selected",
        "moe_slots_dropped", "layers_of_every_kind_counted",
        "nonfinite_losses", "loss_fall_over_window", "compiles_in_window"}
    # every check but the loss's fall (a couple of hundred steps at a rate
    # of 1e-6 on fresh batches go either way at this size) must hold
    assert all(c["ok"] for name, c in checks.items()
               if name != "loss_fall_over_window"), checks
    assert last["correct"] is checks["loss_fall_over_window"]["ok"]
    told = next(x for x in lines if x.get("progress") == "reference")
    assert told["dsa_pairs_selected"] == told["dsa_pairs_expected"] == 5664
    assert "first_loss_abs_diff" in told and "indexer_kl_reference" in told
    assert checks["compiles_in_window"]["value"] == 0
    assert checks["moe_slots_dropped"]["value"] == 0
    assert checks["layers_of_every_kind_counted"]["value"] == 1
    assert checks["dsa_pairs_selected"]["value"] == 5664
    # the window's steps counted themselves, every one of them
    told = next(x for x in lines if x.get("progress") == "window_counters")
    assert told["moe_slots_held_min"] <= told["moe_slots_held"] \
        <= told["moe_slots_held_max"]
    assert told["moe_slots_held_first"] > 0 and told["moe_slots_dropped"] == 0
    assert (told["dsa_layer_applications"], told["moe_layer_applications"],
            told["dsa_pairs_selected"], told["dsa_tiles_skipped"]) == (
                2, 2, 5664, 0)
    assert told["lm_loss"] > 0 and told["dsa_indexer_kl"] > 0

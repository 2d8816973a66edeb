"""The looped cell's part of the benchmark on the CPU: the plain reference's
controls (a lower precision must come out as not correct), the runner's
limits rule and sampled leaves, the two new readers on hand-made fixtures and
on nothing, the looped FLOP count by hand, and the cell's rehearsal."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core, program_trace
from benchmarks.reference import ouro_ref as ref

runner = core.load_runner("train_looped")
TINY = core.load_json(core.BENCH_DIR + "/configs/tiny-ouro-rehearsal.json")
CELL = core.load_json(core.BENCH_DIR + "/configs/ouro-2.6b.json")
TRAFFIC = {"seq_len": 32, "micro_batch": 2, "dtype": "float32",
           "ce_chunk_tokens": 48, "clip": 1.0,
           "optimizer": {"lr": 1e-4, "betas": [0.9, 0.999], "eps": 1e-8}}


def _ids(seed, b=2, s=32):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, TINY["vocab_size"], size=(b, s + 1), dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


# ------------------------------------------------------------ the reference
@pytest.mark.parametrize("seed", [1, 2**31 + 11])
def test_same_seed_same_weights(seed):
    a, b = ref.init_params(TINY, seed), ref.init_params(TINY, seed)
    other = ref.init_params(TINY, seed + 1)
    ka, kb, ko = (x["lm_head"]["kernel"] for x in (a, b, other))
    assert np.array_equal(ka, kb) and not np.array_equal(ka, ko)
    assert abs(float(jnp.std(ka)) - 0.02) < 0.002
    assert float(a["final_norm"]["scale"][0]) == 1.0
    # the gate is seeded like a matrix (a zero gate makes every share a
    # power of 1/2 in any precision), its bias is zero
    assert float(jnp.std(a["exit_gate"]["kernel"])) > 0.01
    assert float(a["exit_gate"]["bias"][0]) == 0.0


def test_the_cells_configuration_is_the_published_one_cut_in_depth_only():
    row = {"head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
           "num_attention_heads": 16, "num_hidden_layers": 48,
           "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
           "rope_theta": 1000000, "total_ut_steps": 4, "vocab_size": 49152,
           "max_position_embeddings": 65536, "early_exit_threshold": 1}
    assert {k: CELL[k] for k in row} == row
    assert len(CELL["layer_types"]) == 48 and CELL["reduced"] == [
        "layers_held"]
    assert ref.depth(CELL) == 8 and ref.passes(CELL) == 4
    assert ref.beta(CELL) == 0.1
    # ISSUE 29's arithmetic: 51,388,416 a layer with its four norms, 612.4M
    layer = ref.layer_params(CELL) + 4 * 2048
    assert layer == 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048 == 51_388_416
    assert ref.num_params(CELL) == 8 * layer + 2 * 49152 * 2048 + 2048 + 2049
    assert 612.3e6 < ref.num_params(CELL) < 612.5e6
    assert set(CELL["assumed"]) >= {"block", "loop", "gate",
                                    "exit_entropy_beta", "weights"}
    model = runner.program_model(CELL, {"seq_len": 4096,
                                        "ce_chunk_tokens": 2048})
    assert model.num_params() == ref.num_params(CELL)
    assert model.flops_per_token() == ref.flops_per_token(CELL, 4096)


def test_looped_flops_by_hand():
    """T passes of L blocks, T heads, T - 1 gates; 12 H S per application."""
    cfg = {"hidden_size": 8, "intermediate_size": 16, "vocab_size": 10,
           "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 4,
           "num_hidden_layers": 3, "total_ut_steps": 2}
    layer = 4 * 8 * 8 + 3 * 8 * 16                         # 640
    assert ref.layer_params(cfg) == layer
    want = 6 * (2 * 3 * layer + 2 * 8 * 10 + 1 * 8) + 12 * 2 * 3 * 8 * 5
    assert ref.flops_per_token(cfg, 5) == want == 26_928
    # not 6 N: the parameters are counted once, the work T times
    assert ref.num_params(cfg, with_input_embedding=False) == \
        3 * (layer + 4 * 8) + 8 * 10 + 8 + 8 + 1
    # the cell: 15.5 GFLOP a token, a sixth of it head + cross entropy
    cell = ref.flops_per_token(CELL, 4096)
    assert 15.4e9 < cell < 15.6e9
    assert 0.15 < 6 * 4 * 2048 * 49152 / cell < 0.17


def test_unknown_precision_is_an_error():
    with pytest.raises(ValueError):
        ref.exit_states(ref.init_params(TINY, 4), TINY, _ids(4)[0][0], "int3")


def test_remat_changes_no_number():
    params = ref.init_params(TINY, 6)
    ids, labels = _ids(6)
    a = ref.exits(params, TINY, ids[0], labels[0])
    b = ref.exits(params, TINY, ids[0], labels[0], remat=True)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert a[0].shape == a[1].shape == (4, 32)
    np.testing.assert_allclose(np.asarray(a[1]).sum(0), 1.0, atol=1e-6)


# ------------------------------------------------------------- the controls
@pytest.mark.parametrize("seed", [21, 22, 23])
def test_control_fails_the_exit_comparisons(seed):
    """bf16 (the stated precision) against the float32 reference reads a
    number for every exit's log-probabilities and for the exit distribution;
    fp8 (the next step down) reads at least three times that."""
    params = ref.init_params(TINY, seed)
    ids, labels = _ids(seed)
    exact = ref.exits(params, TINY, ids[0], labels[0])
    bf16 = runner.compare_exits(
        ref.exits(params, TINY, ids[0], labels[0], "bfloat16"), exact)
    fp8 = runner.compare_exits(
        ref.exits(params, TINY, ids[0], labels[0], "fp8"), exact)
    for number, ceiling in (("exit_logprob_rms", 0.004),
                            ("exit_share_abs", 0.003)):
        assert 0 < bf16[number] < ceiling
        assert fp8[number] > 3 * bf16[number]
        limit = 2.0 * bf16[number]
        assert core.check("x", bf16[number], limit)["ok"]
        assert not core.check("x", fp8[number], limit)["ok"]
    same = runner.compare_exits(exact, exact)
    assert same == {"exit_logprob_rms": 0.0, "exit_share_abs": 0.0}


def test_compare_exits_takes_the_worst_exit():
    lp = np.zeros((3, 4))
    off = lp.copy()
    off[1] = 0.5                      # one exit wrong on every token
    p = np.full((3, 4), 1 / 3)
    moved = p.copy()
    moved[2, 3] += 0.25
    got = runner.compare_exits((off, moved), (lp, p))
    assert got["exit_logprob_rms"] == pytest.approx(0.5)
    assert got["exit_share_abs"] == pytest.approx(0.25)


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
    assert got["adam_update_rel_err"] > 100 * limits[
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
def _reading(grad, adam, fp8=None, low=None, lp=0.004, share=0.002):
    r = {"program": {"grad_rel_err": grad, "adam_update_rel_err": adam,
                     "exit_logprob_rms": lp, "exit_share_abs": share,
                     "first_loss_abs_diff": 0.0001}}
    if fp8 is not None:
        r["control_fp8"] = {"grad_rel_err": fp8, "exit_logprob_rms": 0.05,
                            "exit_share_abs": 0.05}
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
    for number in ("lp", "share"):                        # a kept limit
        with pytest.raises(SystemExit):
            runner.limits_from(sound + [_reading(0.01, 1e-5, **{number: 0.5})])
    bad = _reading(0.01, 1e-5)
    bad["program"]["first_loss_abs_diff"] = 0.1
    with pytest.raises(SystemExit):
        runner.limits_from(sound + [bad])


def test_sampled_leaves_cover_tables_norm_gate_and_three_layers():
    assert runner.sampled_tops(CELL) == {
        "embed_tokens", "lm_head", "final_norm", "exit_gate", "layers_0",
        "layers_3", "layers_7"}
    got = runner.train.sample_leaves(ref.init_params(TINY, 1),
                                     runner.sampled_tops(TINY))
    assert ("exit_gate", "kernel") in got and ("lm_head", "kernel") in got
    assert ("layers_0", "attention", "q_proj", "kernel") in got
    assert ("layers_1", "post_attention_layernorm_2", "scale") in got


def test_cast_for_compute_keeps_what_the_model_keeps_float32():
    cast = runner.cast_for_compute(ref.init_params(TINY, 2),
                                   {"dtype": "bfloat16"})
    assert cast["lm_head"]["kernel"].dtype == jnp.bfloat16
    assert cast["layers_0"]["mlp"]["up_proj"]["kernel"].dtype == jnp.bfloat16
    assert cast["embed_tokens"]["embedding"].dtype == jnp.float32
    assert cast["exit_gate"]["kernel"].dtype == jnp.float32
    assert cast["final_norm"]["scale"].dtype == jnp.float32
    model = runner.program_model(TINY, dict(TRAFFIC, dtype="bfloat16"))
    assert sorted(model.no_cast_paths()) == [r"embed_tokens/embedding",
                                             r"exit_gate/"]


# -------------------------------------------------------------- the readers
def _record(step_s=1.4, steps=5):
    return {"step_ready_at": [step_s * i for i in range(steps)],
            "model_config": CELL, "seq_len": 4096, "tokens": 8192 * steps,
            "attempted": steps, "chips": 1, "device_kind": "TPU v5 lite",
            "losses": [1.0] * steps}


@pytest.fixture
def published():
    """Counters as the engine would leave them, gone after the test."""
    from deeperspeed_tpu.telemetry import trace

    def publish(**counters):
        trace.publish_step_counters("train_step", {
            k: jnp.asarray(v, jnp.float32) for k, v in counters.items()})

    yield publish
    trace._STEP_COUNTERS.clear()


def test_looped_mfu_by_hand_and_against_the_programs_counters(published):
    reader = core.layer_metric_reader("train.looped_mfu_pct")
    # no counters (the parent's program): no number
    assert reader.program_counters() is None
    assert reader.compute(_record(), None) is None
    published(layer_applications=32, head_applications=4,
              exit_share=[0.25] * 4)
    assert reader.program_counters()["exit_share"] == [0.25] * 4
    got = reader.compute(_record(), None)
    want = 100 * ref.flops_per_token(CELL, 4096) * 8192 / 1.4 / 197e12
    assert got == pytest.approx(want) and 45 < got < 47
    # no steps, another model, nothing at all
    assert reader.compute(dict(_record(), step_ready_at=[0.0]), None) is None
    pythia = core.load_json(core.BENCH_DIR + "/configs/pythia-160m.json")
    assert reader.compute(dict(_record(), model_config=pythia), None) is None
    assert reader.compute({}, None) is None
    # a program that ran another number of applications gets no number
    published(layer_applications=8, head_applications=4)
    assert reader.compute(_record(), None) is None
    published(layer_applications=32, head_applications=1)
    assert reader.compute(_record(), None) is None


def _rows():
    """Two steps of a hand-made trace: 10 us under the gate (forward and
    backward), 30 us of head + CE outside it, 60 us elsewhere, a step."""
    ops, host = [], []
    names = {"gate.1": "jit(train_step)/jvp(Ouro)/head_ce/exit_gate/mul",
             "gate.2": "jit(train_step)/transpose(jvp(head_ce))/exit_gate/mul",
             "ce.3": "jit(train_step)/jvp(Ouro)/head_ce/while/body/dot_general",
             "mlp.4": "jit(train_step)/jvp(Ouro)/while/body/mlp/dot_general",
             "gatep.5": "params['exit_gate']['kernel']"}
    durations = {"gate.1": 4_000, "gate.2": 6_000, "ce.3": 30_000,
                 "mlp.4": 55_000, "gatep.5": 5_000}
    for step in range(2):
        at = step * 200_000
        host.append(["dst:train/step", at, 150_000, {"step_num": str(step)}])
        for name, dur in durations.items():
            ops.append([name, at, dur, "jit_train_step"])
            at += dur
    return {"ops": ops, "host": host, "scopes": {"jit_train_step": names}}


def test_exit_gate_reader_on_a_fixture(monkeypatch):
    reader = core.layer_metric_reader("train.scope_ms.exit_gate")
    found = program_trace.ProgramTrace(_rows())
    monkeypatch.setattr(program_trace, "of_run", lambda: found)
    assert reader.compute({"losses": [1.0]}, object()) == pytest.approx(0.010)
    # the gate's time is part of the head's, which the accepted reader reads
    assert found.scope_ms_per_step("head_ce") == pytest.approx(0.040)
    assert reader.under_scope("jit(f)/transpose(jvp(exit_gate))/mul")
    assert not reader.under_scope("params['exit_gate']['bias']")
    # a program that published no scope, no trace, no record
    bare = program_trace.ProgramTrace(dict(_rows(), scopes={}))
    monkeypatch.setattr(program_trace, "of_run", lambda: bare)
    assert reader.compute({"losses": [1.0]}, object()) is None
    monkeypatch.setattr(program_trace, "of_run", lambda: None)
    assert reader.compute({"losses": [1.0]}, object()) is None
    assert reader.compute({}, None) is None


def test_the_cell_lists_the_readers_that_serve_it(listed):
    manifest, _ = listed
    names = {m["name"] for m in core.metrics_for(
        manifest, "train-ouro-2.6b-loop4", "per_layer")}
    assert {"train.looped_mfu_pct", "train.scope_ms.exit_gate",
            "flash_attention_roofline", "train.step_ms",
            "device.idle_pct.train", "train.scope_ms.head_ce"} <= names
    # GPT-NeoX's count of parameters would print a wrong number here
    assert "train.mfu_pct" not in names
    for cell in ("train-410m", "train-160m"):
        old = {m["name"] for m in core.metrics_for(manifest, cell,
                                                   "per_layer")}
        assert "train.mfu_pct" in old and "train.looped_mfu_pct" not in old


# ------------------------------------------------------------ the rehearsal
def test_rehearsal_prints_counts_only():
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "train-ouro-2.6b-loop4", "--seed", str(2**31 + 77), "--seconds", "2",
         "--trace", "0", "--rehearse"], cwd=core.ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=400)
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
        "exit_logprob_rms_vs_reference", "exit_share_abs_diff_vs_reference",
        "first_loss_abs_diff_vs_reference", "compiles_in_window"}
    assert checks["compiles_in_window"]["value"] == 0
    assert 0 < checks["exit_share_abs_diff_vs_reference"]["value"]

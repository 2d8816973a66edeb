"""The latent-attention + sigmoid-mixture cell's readers and lists on the CPU:
the three readers this cell adds and the kernel's cost functions, on
hand-made fixtures, by hand and on nothing; what ``BENCHMARK.json`` lists for
the cell, as committed and after a later PR's entries; and the tests of the
benchmark's that an appended entry moves from what they pin, on the lists
they were written for."""

import copy
import os

import pytest

from benchmarks import core, program_trace
from benchmarks.reference import moonlight_ref as ref

CELL = core.load_json(core.BENCH_DIR + "/configs/moonlight-16b-a3b.json")
NAME = "train-moonlight-16b-ep8-8k"
COUNTERS = {"mla_layer_applications": 6.0,
            "dense_mlp_layer_applications": 1.0,
            "moe_layer_applications": 5.0,
            "shared_expert_layer_applications": 5.0,
            "moe_slots_held": 24576.0, "moe_load_max_over_mean": 1.4,
            "moe_slots_dropped": 0.0, "moe_rows_computed": 25000.0,
            "moe_balance_loss": 5e-4}
OWN = ["train.mla_moe_mfu_pct", "train.scope_ms.mla_latent",
       "flash_attention_mla_roofline"]
SHAPE = dict(B=4, S=8192, N=16, d_nope=128, d_rope=64, d_v=128)


def _record(step_s=1.0, steps=5, **more):
    return dict({"step_ready_at": [step_s * i for i in range(steps)],
                 "model_config": CELL, "seq_len": 8192, "micro_batch": 4,
                 "tokens": 32768 * steps, "attempted": steps, "chips": 1,
                 "device_kind": "TPU v5 lite", "losses": [1.0] * steps,
                 "step_counters": dict(COUNTERS)}, **more)


def test_mla_moe_mfu_by_hand_and_against_the_programs_counters():
    reader = core.layer_metric_reader("train.mla_moe_mfu_pct")
    got = reader.compute(_record(), None)
    per_token = ref.flops_per_token(CELL, 8192, 0.75)
    assert per_token == pytest.approx(6 * 439.2e6, rel=1e-3)
    assert got == pytest.approx(100 * per_token * 32768 / 1.0 / 197e12)
    assert 0 < got < 100
    busy = dict(COUNTERS, moe_slots_held=32768.0)
    assert reader.compute(_record(step_counters=busy), None) > got
    for wrong in ({"mla_layer_applications": 5.0},
                  {"moe_layer_applications": 6.0},
                  {"moe_slots_dropped": 3.0}):
        assert reader.compute(_record(
            step_counters=dict(COUNTERS, **wrong)), None) is None
    assert reader.compute(_record(step_ready_at=[0.0]), None) is None
    for other in ("pythia-160m", "mellum2-12b-a2.5b", "zaya1-8b"):
        config = core.load_json(f"{core.BENCH_DIR}/configs/{other}.json")
        assert reader.compute(_record(model_config=config), None) is None
    assert reader.compute(_record(step_counters=None), None) is None
    assert reader.compute({}, None) is None


def test_the_kernels_cost_functions_against_a_hand_count():
    cost = core.load_kernel_cost("flash_attention_mla")
    half = 4 * 16 * 8192 * 8192 // 2            # (row, column) pairs seen
    f, b = cost.forward(**SHAPE), cost.backward(**SHAPE)
    # forward: the score over 192 and P V over 128, 2 FLOPs a multiply-add
    assert f["flops"] == 2 * half * (128 + 64 + 128)
    # backward: the score again, dQ and dK over 192; dP and dV over 128
    assert b["flops"] == 2 * half * (3 * 192 + 2 * 128)
    rows = 4 * 8192 * 2
    # q_nope, q_rope, k_nope at 16 heads; k_rope at ONE; v, o; the lse
    assert f["bytes"] == (rows * 16 * (128 + 64 + 128) + rows * 64
                          + 2 * rows * 16 * 128 + 4 * 16 * 8192 * 4)
    # the same again and do, o; five gradients, dk_rope at ONE head
    assert b["bytes"] == (2 * (rows * 16 * 320 + rows * 64)
                          + 4 * rows * 16 * 128 + 4 * 16 * 8192 * 4)
    # a rotary key copied to the heads, or a value padded to 192, is more
    copied = rows * 16 * 64 - rows * 64
    assert f["bytes"] + copied > f["bytes"] and copied == 15 * rows * 64
    plain = core.load_kernel_cost("flash_attention")
    assert plain.forward(4, 16, 8192, 192)["flops"] > f["flops"] > (
        plain.forward(4, 16, 8192, 128)["flops"])
    passes = {"forward": 6, "recomputed": 0, "backward": 6}
    work = cost.step_work(passes, **SHAPE)
    assert work["flops"] == 6 * (f["flops"] + b["flops"])
    assert work["bytes"] == 6 * (f["bytes"] + b["bytes"])
    again = cost.step_work(dict(passes, recomputed=6), **SHAPE)
    assert again["flops"] == work["flops"] + 6 * f["flops"]
    # less the backward's recomputed score, the step's attention FLOPs are
    # the model's own count of them (``moonlight_ref.flops_per_token``)
    per_token = 6 * 3 * 16 * 320 * 8192
    assert work["flops"] - 6 * 2 * half * 192 == pytest.approx(
        per_token * 32768)


class _Trace:
    """What the roofline readers ask of a reduced trace."""

    def __init__(self, **events_ns):
        self.by_scope = {k: [(i * 10 ** 7, d) for i, d in enumerate(v)]
                         for k, v in events_ns.items()}

    def scope_events(self, scope):
        return self.by_scope.get(scope, [])


def test_the_flash_roofline_reads_the_kernels_own_events(monkeypatch):
    reader = core.layer_metric_reader("flash_attention_mla_roofline")
    passes = {"forward": 6, "recomputed": 0, "backward": 6}
    monkeypatch.setattr(reader, "kernel_passes", lambda: passes)
    # two steps: a forward of 10 ms and a backward of 28 ms a layer
    trace = _Trace(flash_attention_mla=[10_000_000, 28_000_000] * 6 * 2)
    cost = core.load_kernel_cost("flash_attention_mla")
    f, b = cost.forward(**SHAPE), cost.backward(**SHAPE)
    got = reader.compute(_record(), trace)
    assert got == pytest.approx(
        100 * (f["flops"] + b["flops"]) / 197e12 / 38e-3)
    assert 0 < got < 100
    # the plain kernel's events are another kernel's: nothing to read
    assert reader.compute(_record(), _Trace(
        flash_attention=[10_000_000] * 12)) is None
    assert reader.compute(_record(), None) is None
    zaya = core.load_json(f"{core.BENCH_DIR}/configs/zaya1-8b.json")
    assert reader.compute(_record(model_config=zaya), trace) is None
    # a program without the kernel (the parent commit): no passes, no raise
    monkeypatch.setattr(reader, "kernel_passes", lambda: None)
    assert reader.compute(_record(), trace) is None
    monkeypatch.undo()
    assert reader.kernel_passes() is None      # nothing published here


def _rows():
    """Two steps of a hand-made trace of this model's step."""
    ops, host = [], []
    top = "jit(train_step)/jvp(Moonlight)/"
    back = "jit(train_step)/transpose(jvp(Moonlight))/"
    attn, mlp = "layers_1/attention/attn/", "layers_1/mlp/"
    names = {
        "q.1": top + attn + "q_nope_proj/dot_general",
        "down.2": top + attn + "mla_latent/kv_a_proj/dot_general",
        "rope.3": top + attn + "mla_latent/mul",
        "up.4": back + attn + "mla_latent/k_b_proj/dot_general",
        "flash.5": top + attn + "flash_attention_mla/pallas_call",
        "route.6": top + mlp + "moe/moe_route/top_k",
        "experts.7": top + mlp + "moe/moe_experts/grouped_matmul/pallas_call",
        "shared.8": top + mlp + "moe_shared/shared_experts/dot_general",
        "dense.9": top + "layers_0/mlp/mlp_dense/mlp/dot_general",
        "lost.10": "params['layers_1']['moe']['router_kernel']"}
    durations = {"q.1": 30_000, "down.2": 6_000, "rope.3": 4_000,
                 "up.4": 10_000, "flash.5": 60_000, "route.6": 9_000,
                 "experts.7": 20_000, "shared.8": 40_000, "dense.9": 30_000,
                 "lost.10": 1_000}
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
        record, object()) for s in ("mla_latent", "moe_route", "moe_experts",
                                    "moe_shared", "mlp_dense")}
    assert read["mla_latent"] == pytest.approx(0.020)
    assert read["moe_route"] == pytest.approx(0.009)
    assert read["moe_experts"] == pytest.approx(0.020)
    assert read["moe_shared"] == pytest.approx(0.040)
    assert read["mlp_dense"] == pytest.approx(0.030)
    # the latent's scope lies inside the attention's, beside the kernel
    assert found.scope_ms_per_step("attention") == pytest.approx(
        0.030 + 0.020 + 0.060)
    assert found.scope_ms_per_step("mlp") == pytest.approx(
        0.009 + 0.020 + 0.040 + 0.030)
    lost = core.layer_metric_reader("train.hybrid_unattributed_pct").compute(
        record, object())
    assert lost == pytest.approx(100 * 1_000 / 210_000)
    # a program that published no scope, no trace, no record
    bare = program_trace.ProgramTrace(dict(_rows(), scopes={}))
    reader = core.layer_metric_reader("train.scope_ms.mla_latent")
    for state in (bare, None):
        monkeypatch.setattr(program_trace, "of_run", lambda: state)
        assert reader.compute(record, object()) is None
    assert reader.compute({}, None) is None


def test_the_cell_lists_the_readers_that_serve_it(listed):
    manifest, bench_dir = listed
    names = {m["name"] for m in core.metrics_for(manifest, NAME, "per_layer")}
    assert names >= set(OWN) | {
        "train.scope_ms.moe_route", "train.scope_ms.moe_experts",
        "train.scope_ms.moe_shared", "train.scope_ms.mlp_dense",
        "train.hybrid_unattributed_pct", "train.moe_load_max_over_mean",
        "train.step_ms", "device.idle_pct.train", "train.scope_ms.mlp",
        "train.scope_ms.attention", "train.scope_ms.attention_layout",
        "train.scope_ms.head_ce", "train.scope_ms.optimizer",
        "train.idle_ms.fence", "train.idle_ms.input",
        "train.idle_ms.dispatch", "train.idle_ms.outside",
        "train.host_cpu_ms.step", "train.host_cpu_ms.outside",
        "train.host_ms.input", "train.host_ms.dispatch",
        "train.host_ms.report", "train.host_ms.outside",
        "train.step_ms.unprofiled_less_profiled", "grouped_matmul_roofline",
        "setup.import_s", "setup.initialize_s", "setup.first_steps_s",
        "setup.trace_s", "setup.lower_s", "setup.cache_load_s",
        "setup.backend_compile_s", "setup.outside_program_s"}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    layers = {m["layer"] for m in manifest["per_layer"]
              if m["name"] not in OWN}
    for name in OWN:
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["workloads"][0] == NAME        # a later cell may follow
        assert m["moves"] == "train_tokens_per_s_chip" and m["moves"] in e2e
        assert m["layer"] in layers
        assert callable(core.layer_metric_reader(name, bench_dir).compute)
    assert "mfu" in OWN[0] and by_name[OWN[0]]["source"] == "host_clock"
    assert by_name["flash_attention_mla_roofline"]["unit"] == "%"
    # readers that would print a wrong number here are not asked
    assert not names & {
        "train.mfu_pct", "train.looped_mfu_pct", "train.hybrid_mfu_pct",
        "train.swa_moe_mfu_pct", "train.gated_swa_moe_mfu_pct",
        "train.eva_mfu_pct", "train.dsa_moe_mfu_pct", "train.cca_moe_mfu_pct",
        "flash_attention_roofline", "flash_attention_roofline_held",
        "flash_attention_window_roofline", "flash_attention_full_roofline",
        "flash_attention_cca_roofline", "eva_attention_roofline",
        "dsa_attention_roofline", "ssd_scan_roofline",
        "train.scope_unattributed_pct", "train.scope_ms.ssm",
        "train.scope_ms.cca_mix"}
    # and none of the nine cells that were there is asked for this cell's
    # (lists only grow at their end: this cell is the tenth)
    assert manifest["workloads"][9]["name"] == NAME
    for w in manifest["workloads"][:9]:
        assert not set(OWN) & {m["name"] for m in core.metrics_for(
            manifest, w["name"], "per_layer")}
    assert {m["name"] for m in core.metrics_for(
        manifest, NAME, "end_to_end")} == {"train_tokens_per_s_chip",
                                           "setup_s"}
    assert manifest["configs"][9]["name"] == "moonlight-16b-a3b"
    assert all(w["chips"] == 1 for w in manifest["workloads"][:10])
    cell, config, traffic = core.find_cell(manifest, NAME,
                                           os.path.dirname(bench_dir))
    assert cell["chips"] == 1 and cell["config"] == "moonlight-16b-a3b"
    assert cell["traffic"] == "pretrain-8192-mla-moe-remat"
    assert traffic["runner"] == "train_mla_moe"
    assert (traffic["seq_len"], traffic["ce_chunk_tokens"],
            traffic["remat"]) == (8192, 2048, True)
    assert traffic["seq_len"] == config["max_position_embeddings"]
    assert traffic["micro_batch"] in (2, 3, 4)
    assert traffic["sizing"]["chosen"] == {
        "layers_held": config["layers_held"],
        "micro_batch": traffic["micro_batch"]}
    mellum = core.load_json(os.path.join(
        bench_dir, "traffic", "pretrain-8192-swa-moe-remat.json"))
    for same in ("optimizer", "scheduler", "token_dist", "clip",
                 "zero_stage", "grad_accum", "dtype", "trace_seconds"):
        assert traffic[same] == mellum[same], same
    # one world: the seed among the nine readings it names
    assert 0 <= traffic["world"]["seed"] <= 8
    assert str(traffic["world"]["seed"]) + ":" in traffic["world"]["why"]
    assert traffic["rehearsal"]["config"] == "tiny-moonlight-rehearsal"


# ------------------------- the benchmark's tests that appended entries move
def _before(manifest):
    """The manifest less what this PR appended to the lists those tests
    pin: its three per-layer entries, and its cell's name in the two lists
    the Laguna cell's test holds to that cell alone."""
    before = copy.deepcopy(manifest)
    assert [m["name"] for m in before["per_layer"][-3:]] == OWN
    del before["per_layer"][-3:]
    for m in before["per_layer"]:
        if m["name"] in ("train.scope_ms.moe_shared",
                         "train.scope_ms.mlp_dense"):
            assert m["workloads"][-1] == NAME
            m["workloads"] = m["workloads"][:-1]
    return before


def test_the_setup_readers_test_holds_on_the_list_it_was_written_for(
        monkeypatch):
    """``test_bench_setup_readers.py`` asks for its eight entries at the END
    of ``per_layer``, where this PR's three now stand (tests/conftest.py
    expects that line to fail until a ``benchmark`` PR repairs it).  Every
    assertion of that test still guards the entries: here it runs on the
    manifest less this PR's three, with this cell in every list."""
    import test_bench_setup_readers as setup

    before = _before(core.load_manifest())
    monkeypatch.setattr(setup, "MANIFEST", before)
    for name in setup.SETUP:
        setup.test_the_entries_are_as_the_issue_gives_them(name)
        entry, = [m for m in before["per_layer"] if m["name"] == name]
        assert entry["workloads"][-1] == NAME


def test_the_laguna_cells_test_holds_on_the_lists_it_was_written_for():
    """``test_bench_laguna.py`` holds the lists of ``train.scope_ms.
    moe_shared`` and ``.mlp_dense`` to the Laguna cell alone; this cell has
    shared experts and a dense layer under the same scopes and ISSUE 61
    appends it to both (tests/conftest.py expects that line to fail until a
    ``benchmark`` PR reads ``[0] == NAME``).  Every other assertion of that
    test runs here on the manifest less the two appended names."""
    import test_bench_laguna as laguna

    laguna.test_the_cell_lists_the_readers_that_serve_it(
        (_before(core.load_manifest()), core.BENCH_DIR))
    manifest = core.load_manifest()
    for name in ("train.scope_ms.moe_shared", "train.scope_ms.mlp_dense"):
        entry, = [m for m in manifest["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [laguna.NAME, NAME]

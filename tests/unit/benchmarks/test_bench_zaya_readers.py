"""The compressed-attention + MLP-router cell's readers and lists on the CPU:
the four readers this cell adds, on hand-made fixtures and on nothing; what
``BENCHMARK.json`` lists for the cell, as committed and after a later PR's
entries; and the one test of the benchmark's that an appended configuration
moves from its list's end, on the lists it was written for."""

import os

import pytest

from benchmarks import core, program_trace
from benchmarks.reference import zaya_ref as ref

CELL = core.load_json(core.BENCH_DIR + "/configs/zaya1-8b.json")
NAME = "train-zaya1-8b-ep2-8k"
DEPTH = CELL["layers_held"]

COUNTERS = {"cca_layer_applications": float(DEPTH),
            "moe_layer_applications": float(DEPTH),
            "moe_slots_held": 16384.0, "moe_load_max_over_mean": 3.1,
            "moe_slots_dropped": 0.0, "moe_rows_computed": 17000.0,
            "moe_tokens_unrouted_here": 16384.0}
OWN = ["train.cca_moe_mfu_pct", "train.scope_ms.cca_mix",
       "train.scope_ms.moe_router_mlp", "flash_attention_cca_roofline"]


def _record(step_s=0.4, steps=5, **more):
    return dict({"step_ready_at": [step_s * i for i in range(steps)],
                 "model_config": CELL, "seq_len": 8192, "micro_batch": 4,
                 "tokens": 32768 * steps, "attempted": steps, "chips": 1,
                 "device_kind": "TPU v5 lite", "losses": [1.0] * steps,
                 "step_counters": dict(COUNTERS)}, **more)


def test_cca_moe_mfu_by_hand_and_against_the_programs_counters():
    reader = core.layer_metric_reader("train.cca_moe_mfu_pct")
    got = reader.compute(_record(), None)
    per_token = ref.flops_per_token(CELL, 8192, 0.5)
    assert got == pytest.approx(100 * per_token * 32768 / 0.4 / 197e12)
    assert 0 < got < 100
    busy = dict(COUNTERS, moe_slots_held=32768.0)
    assert reader.compute(_record(step_counters=busy), None) > got
    for wrong in ({"cca_layer_applications": DEPTH - 1.0},
                  {"moe_layer_applications": DEPTH + 1.0},
                  {"moe_slots_dropped": 3.0}):
        assert reader.compute(_record(
            step_counters=dict(COUNTERS, **wrong)), None) is None
    assert reader.compute(_record(step_ready_at=[0.0]), None) is None
    for other in ("pythia-160m", "mellum2-12b-a2.5b",
                  "keye-vl-2.0-30b-a3b"):
        config = core.load_json(f"{core.BENCH_DIR}/configs/{other}.json")
        assert reader.compute(_record(model_config=config), None) is None
    assert reader.compute(_record(step_counters=None), None) is None
    assert reader.compute({}, None) is None


class _Trace:
    """What the roofline readers ask of a reduced trace."""

    def __init__(self, **events_ns):
        self.by_scope = {k: [(i * 10 ** 7, d) for i, d in enumerate(v)]
                         for k, v in events_ns.items()}

    def scope_events(self, scope):
        return self.by_scope.get(scope, [])


def test_the_flash_roofline_counts_the_latents_heads(monkeypatch):
    reader = core.layer_metric_reader("flash_attention_cca_roofline")
    passes = {"forward": DEPTH, "recomputed": 0, "backward": DEPTH}
    monkeypatch.setattr(reader.held, "kernel_passes", lambda: passes)
    # two steps: a forward of 4 ms and a backward of 10 ms a layer
    trace = _Trace(flash_attention=[4_000_000, 10_000_000] * DEPTH * 2)
    cost = core.load_kernel_cost("flash_attention")
    f, b = (fn(4, 8, 8192, 128) for fn in (cost.forward, cost.backward))
    assert f["flops"] == 4.0 * 4 * 8 * 8192 * 8192 * 128 * 0.5
    got = reader.compute(_record(), trace)
    assert got == pytest.approx(
        100 * (f["flops"] + b["flops"]) / 197e12 / 14e-3)
    assert 0 < got < 100
    # nothing to read: no events, another model, no trace, nothing, a
    # program that publishes no passes (the parent)
    assert reader.compute(_record(), _Trace()) is None
    mellum = core.load_json(core.BENCH_DIR + "/configs/mellum2-12b-a2.5b.json")
    assert reader.compute(_record(model_config=mellum), trace) is None
    assert reader.compute(_record(), None) is None
    assert reader.compute({}, None) is None
    monkeypatch.setattr(reader.held, "kernel_passes", lambda: None)
    assert reader.compute(_record(), trace) is None


def _rows():
    """Two steps of a hand-made trace of this model's step."""
    ops, host = [], []
    top = "jit(train_step)/jvp(Zaya)/"
    back = "jit(train_step)/transpose(jvp(Zaya))/"
    attn, mlp = "layers_1/attention/attn/", "layers_1/mlp/moe/"
    names = {
        "qkv.1": top + attn + "q_proj/dot_general",
        "mix.2": top + attn + "cca_mix/mul",
        "mix.3": back + attn + "cca_mix/dot_general",
        "flash.4": top + attn + "flash_attention/pallas_call",
        "router.5": top + mlp + "moe_router_mlp/dot_general",
        "router.6": back + mlp + "moe_router_mlp/erf",
        "route.7": top + mlp + "moe_route/top_k",
        "experts.8": top + mlp + "moe_experts/grouped_matmul/pallas_call",
        "lost.9": "params['layers_1']['moe']['router_mlp_1']"}
    durations = {"qkv.1": 30_000, "mix.2": 6_000, "mix.3": 9_000,
                 "flash.4": 40_000, "router.5": 2_000, "router.6": 3_000,
                 "route.7": 9_000, "experts.8": 50_000, "lost.9": 1_000}
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
    new = ("cca_mix", "moe_router_mlp")
    read = {s: core.layer_metric_reader("train.scope_ms." + s).compute(
        record, object()) for s in new + ("moe_route", "moe_experts")}
    assert read["cca_mix"] == pytest.approx(0.015)
    assert read["moe_router_mlp"] == pytest.approx(0.005)
    assert read["moe_route"] == pytest.approx(0.009)
    assert read["moe_experts"] == pytest.approx(0.050)
    # each lies inside its sublayer's scope
    assert found.scope_ms_per_step("attention") == pytest.approx(
        0.030 + 0.015 + 0.040)
    assert found.scope_ms_per_step("mlp") == pytest.approx(
        0.005 + 0.009 + 0.050)
    lost = core.layer_metric_reader("train.hybrid_unattributed_pct").compute(
        record, object())
    assert lost == pytest.approx(100 * 1_000 / 150_000)
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
        "train.step_ms.unprofiled_less_profiled", "grouped_matmul_roofline"}
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
    assert by_name["flash_attention_cca_roofline"]["unit"] == "%"
    # readers that would print a wrong number here are not asked
    assert not names & {
        "train.mfu_pct", "train.looped_mfu_pct", "train.hybrid_mfu_pct",
        "train.swa_moe_mfu_pct", "train.gated_swa_moe_mfu_pct",
        "train.eva_mfu_pct", "train.dsa_moe_mfu_pct",
        "flash_attention_roofline", "flash_attention_roofline_held",
        "flash_attention_window_roofline", "flash_attention_full_roofline",
        "eva_attention_roofline", "dsa_attention_roofline",
        "ssd_scan_roofline", "train.scope_unattributed_pct",
        "train.scope_ms.ssm"}
    # and none of the eight cells that were there is asked for this cell's
    # (lists only grow at their end: this cell is the ninth)
    assert manifest["workloads"][8]["name"] == NAME
    for w in manifest["workloads"][:8]:
        assert not set(OWN) & {m["name"] for m in core.metrics_for(
            manifest, w["name"], "per_layer")}
    assert {m["name"] for m in core.metrics_for(
        manifest, NAME, "end_to_end")} == {"train_tokens_per_s_chip",
                                           "setup_s"}
    assert manifest["configs"][8]["name"] == "zaya1-8b"
    assert all(w["chips"] == 1 for w in manifest["workloads"][:9])
    cell, config, traffic = core.find_cell(manifest, NAME,
                                           os.path.dirname(bench_dir))
    assert cell["chips"] == 1 and cell["config"] == "zaya1-8b"
    assert cell["traffic"] == "pretrain-8192-cca-moe-remat"
    assert traffic["runner"] == "train_cca_moe"
    assert (traffic["seq_len"], traffic["ce_chunk_tokens"],
            traffic["remat"]) == (8192, 2048, True)
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


def test_the_keye_configurations_test_holds_on_the_lists_it_was_written_for(
        monkeypatch):
    """``test_bench_keye.py`` asks for its configuration at the END of
    ``configs``, where this PR's now stands (tests/conftest.py expects that
    line to fail until a ``benchmark`` PR repairs it).  Every assertion of
    that test still guards the files it reads: here it runs on the manifest
    less what this PR appended, which has to be the manifest PR 53 left."""
    import test_bench_keye as keye

    manifest = core.load_manifest()
    assert [c["name"] for c in manifest["configs"][7:9]] == [
        "keye-vl-2.0-30b-a3b", "zaya1-8b"]
    before = dict(manifest, configs=manifest["configs"][:8],
                  workloads=manifest["workloads"][:8])
    monkeypatch.setattr(keye.core, "load_manifest", lambda *a: before)
    keye.test_the_configuration_is_the_published_row_key_for_key()

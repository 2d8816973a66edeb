"""The learned-sparse-attention cell's readers on the CPU: each new per-layer
metric on a hand-made fixture and on nothing, and the manifest's lists of
the readers that serve the cell (the cell's configuration, controls and
rehearsal: ``test_bench_keye.py``)."""

import os

import pytest

from benchmarks import core, program_trace
from benchmarks.reference import keye_ref as ref

runner = core.load_runner("train_dsa_moe")
CELL = core.load_json(core.BENCH_DIR + "/configs/keye-vl-2.0-30b-a3b.json")
NAME = "train-keye-vl2-ep8-16k"
CHOSEN = 2048 * 2049 // 2 + (16384 - 2048) * 2048        # a sequence, a layer
COUNTERS = {"dsa_layer_applications": 6.0, "moe_layer_applications": 6.0,
            "dsa_pairs_selected": 6.0 * CHOSEN,
            "dsa_pairs_visited": 6.0 * 528 * 512 * 512,
            "dsa_tiles_skipped": 0.0, "moe_slots_held": 16384.0,
            "moe_load_max_over_mean": 3.1, "moe_slots_dropped": 0.0,
            "lm_loss": 9.0, "dsa_indexer_kl": 0.25}


def _record(step_s=2.0, steps=5, **more):
    return dict({"step_ready_at": [step_s * i for i in range(steps)],
                 "model_config": CELL, "seq_len": 16384, "micro_batch": 1,
                 "tokens": 16384 * steps, "attempted": steps, "chips": 1,
                 "device_kind": "TPU v5 lite", "losses": [1.0] * steps,
                 "step_counters": dict(COUNTERS)}, **more)


def test_dsa_moe_mfu_by_hand_and_against_the_programs_counters():
    reader = core.layer_metric_reader("train.dsa_moe_mfu_pct")
    got = reader.compute(_record(), None)
    per_token = ref.flops_per_token(CELL, 16384, 1.0)
    assert got == pytest.approx(100 * per_token * 16384 / 2.0 / 197e12)
    assert 8 < got < 10
    busy = dict(COUNTERS, moe_slots_held=32768.0)
    assert reader.compute(_record(step_counters=busy), None) > got
    # counters that disagree with the layers or the exact selection, or a
    # dropped slot: no number
    for wrong in ({"dsa_layer_applications": 5.0},
                  {"moe_layer_applications": 7.0},
                  {"dsa_pairs_selected": 6.0 * CHOSEN - 1},
                  {"moe_slots_dropped": 3.0}):
        assert reader.compute(_record(
            step_counters=dict(COUNTERS, **wrong)), None) is None
    assert reader.compute(_record(step_ready_at=[0.0]), None) is None
    for other in ("pythia-160m", "mellum2-12b-a2.5b", "laguna-s-2.1"):
        config = core.load_json(f"{core.BENCH_DIR}/configs/{other}.json")
        assert reader.compute(_record(model_config=config), None) is None
    assert reader.compute(_record(step_counters=None), None) is None
    assert reader.compute({}, None) is None


def test_pairs_over_selected():
    reader = core.layer_metric_reader("train.dsa_pairs_over_selected")
    got = reader.compute(_record(), object())
    assert got == pytest.approx(528 * 512 * 512 / CHOSEN)
    assert 4.3 < got < 4.5
    assert reader.compute(_record(), None) is None
    assert reader.compute(_record(step_counters={}), object()) is None
    assert reader.compute({}, None) is None


class _Trace:
    """What the roofline readers ask of a reduced trace."""

    def __init__(self, **events_ns):
        self.by_scope = {k: [(i * 10 ** 7, d) for i, d in enumerate(v)]
                         for k, v in events_ns.items()}

    def scope_events(self, scope):
        return self.by_scope.get(scope, [])


def test_rooflines_count_the_chosen_pairs(monkeypatch):
    shared = core.layer_metric_reader("_dsa_roofline")
    passes = {"dsa_attention": {"forward": 6, "recomputed": 0,
                                "backward": 12},
              "dsa_head_probs": {"forward": 192, "recomputed": 0,
                                 "backward": 0},
              "dsa_select": {"forward": 6, "recomputed": 0, "backward": 0}}
    attention = core.layer_metric_reader("dsa_attention_roofline")
    probs = core.layer_metric_reader("dsa_head_probs_roofline")
    select = core.layer_metric_reader("dsa_select_roofline")
    for reader in (attention, probs, select):
        monkeypatch.setattr(reader._shared, "kernel_passes", passes.get)
    # two steps: six layers' forward of 40 ms, dq of 25 ms, dk/dv of 40 ms
    trace = _Trace(
        dsa_attention=[40_000_000, 25_000_000, 40_000_000] * 12,
        dsa_head_probs=[1_000_000] * 384, dsa_select=[11_500_000] * 12)
    cost = core.load_kernel_cost("dsa_attention")
    assert cost.pairs(16384, 2048) == CHOSEN
    f = cost.forward(1, 32, 4, 16384, 128, 2048)
    b = cost.backward(1, 32, 4, 16384, 128, 2048)
    assert f["flops"] == 4.0 * 32 * CHOSEN * 128
    assert b["flops"] == 2.5 * f["flops"]
    got = attention.compute(_record(), trace)
    assert got == pytest.approx(
        100 * 6 * (f["flops"] + b["flops"]) / 197e12 / (6 * 105e-3))
    # the walk computes the triangle for its 23 %: a share under a quarter
    assert 5 < got < 25
    layer = core.load_kernel_cost("dsa_head_probs").layer(
        1, 32, 4, 16384, 128, 2048)
    assert layer["flops"] == 2.0 * 32 * CHOSEN * 128
    assert probs.compute(_record(), trace) == pytest.approx(
        100 * 6 * layer["flops"] / 197e12 / (192e-3))
    call = core.load_kernel_cost("dsa_select").forward(1, 16, 16384, 64)
    assert call["flops"] == 2.0 * 16 * (16384 * 16385 // 2) * 64
    assert select.compute(_record(), trace) == pytest.approx(
        100 * call["flops"] / 197e12 / 11.5e-3)
    for reader in (attention, probs, select):
        assert 0 < reader.compute(_record(), trace) < 100
        # nothing to read: no events, another model, no trace, nothing
        assert reader.compute(_record(), _Trace()) is None
        mellum = core.load_json(core.BENCH_DIR
                                + "/configs/mellum2-12b-a2.5b.json")
        assert reader.compute(_record(model_config=mellum), trace) is None
        assert reader.compute(_record(), None) is None
        assert reader.compute({}, None) is None
    # a program that publishes no passes (the parent): no number
    for reader in (attention, probs, select):
        monkeypatch.setattr(reader._shared, "kernel_passes", lambda k: None)
        assert reader.compute(_record(), trace) is None
    assert shared.shapes({"model_config": {}}) is None


def _rows():
    """Two steps of a hand-made trace of this model's step."""
    ops, host = [], []
    top = "jit(train_step)/jvp(Keye)/"
    back = "jit(train_step)/transpose(jvp(Keye))/"
    attn = "layers_1/attention/attn/"
    names = {
        "qkv.1": top + attn + "q_proj/dot_general",
        "index.2": top + attn + "dsa_index/indexer/wq_index/dot_general",
        "index.3": back + attn + "dsa_index/indexer/wk_index/dot_general",
        "select.4": top + attn + "dsa_select/dsa_select/pallas_call",
        "count.5": top + attn + "dsa_select/reduce_sum",
        "fwd.6": top + attn + "dsa_attend/dsa_attention/pallas_call",
        "layout.7": top + attn + "dsa_attend/attention_layout/reshape",
        "bwd.8": back + attn + "dsa_attend/dsa_attention/pallas_call",
        "probs.9": top + attn + "dsa_indexer_loss/while/body/dsa_head_probs/"
                   "pallas_call",
        "loss.10": top + attn + "dsa_indexer_loss/while/body/dot_general",
        "loss.11": back + attn + "dsa_indexer_loss/mul",
        "route.12": top + "layers_1/mlp/moe/moe_route/top_k",
        "lost.13": "params['layers_1']['moe']['router_kernel']"}
    durations = {"qkv.1": 30_000, "index.2": 2_000, "index.3": 3_000,
                 "select.4": 11_000, "count.5": 500, "fwd.6": 40_000,
                 "layout.7": 400, "bwd.8": 65_000, "probs.9": 25_000,
                 "loss.10": 130_000, "loss.11": 100, "route.12": 9_000,
                 "lost.13": 2_500}
    for step in range(2):
        at = step * 800_000
        host.append(["dst:train/step", at, 700_000, {"step_num": str(step)}])
        for name, dur in durations.items():
            ops.append([name, at, dur, "jit_train_step"])
            at += dur
    return {"ops": ops, "host": host, "scopes": {"jit_train_step": names}}


def test_scope_readers_on_a_fixture(monkeypatch):
    found = program_trace.ProgramTrace(_rows())
    monkeypatch.setattr(program_trace, "of_run", lambda: found)
    record = {"losses": [1.0]}
    new = ("dsa_index", "dsa_select", "dsa_attend", "dsa_indexer_loss")
    read = {s: core.layer_metric_reader("train.scope_ms." + s).compute(
        record, object()) for s in new + ("moe_route",)}
    assert read["dsa_index"] == pytest.approx(0.005)
    assert read["dsa_select"] == pytest.approx(0.0115)
    assert read["dsa_attend"] == pytest.approx(0.1054)
    assert read["dsa_indexer_loss"] == pytest.approx(0.1551)
    assert read["moe_route"] == pytest.approx(0.009)
    # the four lie inside the attention sublayer, whose scope holds their
    # sum with the projections
    assert found.scope_ms_per_step("attention") == pytest.approx(
        0.030 + sum(read[s] for s in new))
    lost = core.layer_metric_reader("train.hybrid_unattributed_pct").compute(
        record, object())
    assert lost == pytest.approx(100 * 2_500 / 318_500)
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
OWN = ["train.dsa_moe_mfu_pct", "train.scope_ms.dsa_index",
       "train.scope_ms.dsa_select", "train.scope_ms.dsa_attend",
       "train.scope_ms.dsa_indexer_loss", "train.dsa_pairs_over_selected",
       "dsa_attention_roofline", "dsa_head_probs_roofline",
       "dsa_select_roofline"]


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
        "train.step_ms.unprofiled_less_profiled",
        # the held experts walk the grouped form here as in the Mellum cell
        "grouped_matmul_roofline"}
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
    assert "mfu" in OWN[0] and by_name[OWN[0]]["source"] == "host_clock"
    assert all(by_name[n]["unit"] == "%" for n in OWN if "roofline" in n)
    # readers that would print a wrong number here are not asked: the dense
    # flash kernels' (no call of them is made), the other models' shares of
    # the peak
    assert not names & {
        "train.mfu_pct", "train.looped_mfu_pct", "train.hybrid_mfu_pct",
        "train.swa_moe_mfu_pct", "train.gated_swa_moe_mfu_pct",
        "train.eva_mfu_pct", "flash_attention_roofline",
        "flash_attention_roofline_held", "flash_attention_window_roofline",
        "flash_attention_full_roofline", "eva_attention_roofline",
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
    assert cell["chips"] == 1 and cell["config"] == "keye-vl-2.0-30b-a3b"
    assert cell["traffic"] == "pretrain-16384-dsa-moe-remat"
    assert traffic["runner"] == "train_dsa_moe"
    assert (traffic["seq_len"], traffic["micro_batch"], traffic[
        "ce_chunk_tokens"], traffic["remat"]) == (16384, 1, 2048, True)
    assert traffic["optimizer"] == {"type": "Adam", "lr": 1e-4,
                                    "betas": [0.9, 0.999], "eps": 1e-8}
    assert traffic["scheduler"]["params"] == {
        "warmup_min_lr": 1e-6, "warmup_max_lr": 1e-4,
        "warmup_num_steps": 2000, "warmup_type": "linear"}
    assert traffic["token_dist"] == {"kind": "zipf", "exponent": 1.1}
    assert (traffic["clip"], traffic["zero_stage"], traffic["grad_accum"],
            traffic["dtype"], traffic["trace_seconds"]) == (
                1.0, 0, 1, "bfloat16", 6.0)
    # one world from the start: the seed among the nine readings it names
    assert 0 <= traffic["world"]["seed"] <= 8
    assert str(traffic["world"]["seed"]) + ":" in traffic["world"]["why"]
    # the cell's limits were set on the chip
    limits = core.load_limits(NAME, bench_dir=bench_dir)
    assert limits["device"]["platform"] == "tpu"
    for number, control in runner.CONTROL_OF.items():
        assert limits[number]["control"] == control

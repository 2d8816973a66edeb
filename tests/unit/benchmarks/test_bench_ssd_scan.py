"""The SSD scan's cost functions and its roofline reader on the CPU: the
forward's FLOPs are the reference's own count of the chunked form at the
hybrid cell's shapes, the bytes each operand and result once, the reader's
share from a trace of known durations, and no number where there is nothing
to read (another model, no kernel events: the plain ``jnp`` scan's program,
which is what the parent of the PR that brought the kernels runs)."""

import pytest

from benchmarks import core
from benchmarks.reference import nemotron_h_ref as ref

NAME = "train-nemotron3-super-ep64-8k"
CELL = core.load_json(core.BENCH_DIR
                      + "/configs/nemotron-3-super-120b-a12b.json")
# the cell's call: batch, sequence, heads and groups held, P, N, chunk
SHAPES = (2, 8192, 32, 2, 64, 128, 128)
PASSES = {"forward": 5, "recomputed": 5, "backward": 5}

cost = core.load_kernel_cost("ssd_scan")


def test_the_forwards_flops_are_the_references_chunked_form():
    sh = ref.share(CELL)
    assert (sh["mamba_heads"], sh["mamba_groups"]) == SHAPES[2:4]
    _, conv, _ = ref.mamba_widths(CELL, sh)
    per_token = ref.scan_flops_per_token(CELL, sh) - 2 * CELL[
        "conv_kernel"] * conv
    assert cost.forward(*SHAPES)["flops"] == per_token * 2 * 8192
    # by hand: per head 2 Q P + 4 P N, per group 2 Q N (scores once a group)
    assert per_token == 32 * (2 * 128 * 64 + 4 * 64 * 128) + 2 * 2 * 128 * 128
    assert cost.backward(*SHAPES)["flops"] == 2 * cost.forward(*SHAPES)["flops"]


def test_bytes_are_each_operand_and_result_once():
    tokens = 2 * 8192
    wide, narrow, steps = tokens * 2048 * 2, tokens * 256 * 2, tokens * 32 * 4
    assert cost.forward(*SHAPES)["bytes"] == 2 * wide + 2 * narrow + steps
    assert cost.backward(*SHAPES)["bytes"] == (3 * wide + 4 * narrow
                                               + 2 * steps)
    # memory-bound on the v5e: the bytes take longer than the matmuls
    peaks = core.device_peaks("TPU v5 lite")
    f = cost.forward(*SHAPES)
    assert (f["bytes"] / peaks["hbm_bytes_per_s"]
            > f["flops"] / peaks["bf16_flops_per_s"])


class _Trace:
    def __init__(self, durations_ns, scope="ssd_scan"):
        self.events = [(i * 10 ** 7, d) for i, d in enumerate(durations_ns)]
        self.scope = scope

    def scope_events(self, scope):
        return self.events if scope == self.scope else []


def _record(**more):
    return dict({"model_config": CELL, "seq_len": 8192, "micro_batch": 2,
                 "device_kind": "TPU v5 lite"}, **more)


def test_the_readers_share_by_hand_and_nothing_without_events(monkeypatch):
    reader = core.layer_metric_reader("ssd_scan_roofline")
    f, b = cost.forward(*SHAPES), cost.backward(*SHAPES)
    work = reader.step_work(PASSES, *SHAPES)
    assert work == {"flops": 10 * f["flops"] + 5 * b["flops"],
                    "bytes": 10 * f["bytes"] + 5 * b["bytes"]}
    monkeypatch.setattr(reader, "kernel_passes", lambda: PASSES)
    # two steps: per layer a forward of 1 ms, a recomputed one of 1.2 ms
    # and a backward of 4 ms
    trace = _Trace([1_000_000, 1_200_000, 4_000_000] * 5 * 2)
    got = reader.compute(_record(), trace)
    least = work["bytes"] / 819e9
    assert got == pytest.approx(100 * least / (5 * 6.2e-3))
    assert 0 < got < 100
    # no kernel events (the plain scan's program), no published passes,
    # another model, no trace: no number, and no error
    assert reader.compute(_record(), _Trace([])) is None
    assert reader.compute(_record(), _Trace([1_000_000],
                                            scope="flash_attention")) is None
    monkeypatch.setattr(reader, "kernel_passes", lambda: None)
    assert reader.compute(_record(), trace) is None
    monkeypatch.setattr(reader, "kernel_passes", lambda: PASSES)
    pythia = core.load_json(core.BENCH_DIR + "/configs/pythia-160m.json")
    assert reader.compute(_record(model_config=pythia), trace) is None
    assert reader.compute({}, None) is None


def test_the_reader_asks_the_program_for_its_passes():
    """``kernel_passes`` reads what the step program published; with none
    published (no profiler session in this process) there is no entry."""
    reader = core.layer_metric_reader("ssd_scan_roofline")
    assert reader.kernel_passes() in (None, {}) or set(
        reader.kernel_passes()) == set(PASSES)


def test_only_the_hybrid_cell_lists_the_metric(listed):
    manifest, _ = listed
    entry = [m for m in manifest["per_layer"]
             if m["name"] == "ssd_scan_roofline"]
    assert entry == [{"name": "ssd_scan_roofline", "unit": "%",
                      "better": "higher", "source": "device_trace",
                      "layer": "kernels", "moves": "train_tokens_per_s_chip",
                      "workloads": [NAME]}]
    assert entry[0] in manifest["per_layer"]

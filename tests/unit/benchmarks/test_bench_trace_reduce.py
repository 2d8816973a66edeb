"""The trace reduction on hand-made rows and on a slice recorded on the v5e
(PR 26, ``train-160m``: the first 1200 device ops of a traced window)."""

import os

import pytest

from benchmarks import core, trace_reduce as tr

SLICE = os.path.join(os.path.dirname(__file__),
                     "trace_slice_train160m.json.gz")


def _rows(ops, host=()):
    return {"devices": {"/device:TPU:0": {"ops": [list(o) for o in ops],
                                           "modules": [["jit_step(1)", 0, 1]]}},
            "host": [list(h) for h in host]}


@pytest.mark.parametrize("intervals,want", [
    ([(0, 10)], 10),
    ([(0, 10), (5, 15)], 15),               # overlap counted once
    ([(0, 10), (20, 30)], 20),              # a gap
    ([(0, 10), (2, 3), (10, 12)], 12),      # nested and touching
    ([], 0),
])
def test_union_length(intervals, want):
    assert tr.union_length(intervals) == want


def test_gaps():
    assert tr.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]
    assert tr.gaps([(0, 10)], 0, 10) == []
    assert tr.gaps([(0, 3), (2, 5)], 0, 6) == [(5, 6)]


def test_short_name_strips_layouts_and_operands():
    text = ("%fusion.6 = bf16[8,2048,1024]{2,1,0:T(8,128)(2,1)} fusion("
            "f32[8,2048]{1,0:T(8,128)S(1)} %get-tuple-element.3367), kind=kOutput")
    assert tr.short_name(text) == "fusion.6 bf16[8,2048,1024]"
    tup = ("%flash_attention.144 = (bf16[128,2048,64]{2,1,0:T(8,128)(2,1)}, "
           "f32[128,2048,128]{2,1,0:T(8,128)}) custom-call(bf16[1]{0} %x)")
    assert tr.short_name(tup) == (
        "flash_attention.144 (bf16[128,2048,64], f32[128,2048,128])")
    assert tr.short_name("bench:put_round") == "bench:put_round"


def test_reduced_busy_idle_scope_and_gap_names():
    ops = [("fusion.1 f32[4]", 0, 35), ("flash_attention.7 (bf16[2])", 30, 30),
           ("flash_attention.9 (bf16[2])", 80, 10), ("copy.3 s32[1]", 95, 5)]
    host = [("bench:batch_prep", 58, 24), ("bench:wait_step", 0, 50)]
    r = tr.Reduced(_rows(ops, host))
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(75e-9)        # 0-60, 80-90, 95-100
    assert r.idle_pct == pytest.approx(25.0)
    assert r.scope_seconds("flash_attention") == pytest.approx(40e-9)
    assert len(r.scope_events("flash_attention")) == 2
    assert r.scope_events("flash") == []           # whole names only
    top = r.top_ops()
    assert top[0] == ["flash_attention (bf16[2])", pytest.approx(40e-9)]
    gaps = r.idle_gaps()
    assert gaps[0] == ["batch_prep", pytest.approx(20e-9)]
    assert gaps[1] == ["host:unattributed", pytest.approx(5e-9)]
    assert set(r.breakdown()) == {"device_ops", "idle_gaps"}


def test_two_chips_average_busy_time():
    rows = _rows([("a.1 f32[1]", 0, 100)])
    rows["devices"]["/device:TPU:1"] = {"ops": [["a.1 f32[1]", 0, 50]],
                                        "modules": []}
    r = tr.Reduced(rows, chips=2)
    assert r.busy_s == pytest.approx(75e-9)
    assert tr.Reduced(rows, chips=1).busy_s == pytest.approx(100e-9)


def test_a_trace_without_device_ops_is_an_error():
    with pytest.raises(ValueError):
        tr.Reduced({"devices": {}, "host": []})
    with pytest.raises(ValueError):
        tr.Reduced(_rows([]))


def test_recorded_slice(tmp_path):
    rows = tr.load_rows(SLICE)
    r = tr.Reduced(rows)
    s = r.summary()
    assert s["ops"] == 1200 and s["chips"] == 1
    assert 0 < r.busy_s <= r.window_s
    assert 0 <= r.idle_pct < 50
    flash = r.scope_events("flash_attention")
    assert flash and all(d > 0 for _, d in flash)
    assert r.scope_seconds("flash_attention") < r.busy_s
    names = [n for n, _ in r.top_ops()]
    assert len(names) <= 10 and any(n.startswith("fusion") for n in names)
    assert sum(sec for _, sec in r.top_ops(10 ** 6)) == pytest.approx(
        sum(op[2] for op in rows["devices"]["/device:TPU:0"]["ops"]) / 1e9)
    # rows survive a save / load round trip
    tr.save_rows(rows, str(tmp_path / "x.json.gz"), max_ops=100)
    assert tr.Reduced(tr.load_rows(str(tmp_path / "x.json.gz"))
                      ).summary()["ops"] == 100


def test_flash_roofline_reader_on_hand_made_events():
    """24 forward + 12 backward calls at the fastest the chip could run them
    read 100 %; at twice that time, 50 %."""
    cfg = core.load_json(core.BENCH_DIR + "/configs/pythia-160m.json")
    cost = core.load_kernel_cost("flash_attention")
    peaks = core.device_peaks("TPU v5 lite")
    f, b = cost.forward(16, 12, 1024, 64), cost.backward(16, 12, 1024, 64)
    least = lambda c: max(c["flops"] / peaks["bf16_flops_per_s"],
                          c["bytes"] / peaks["hbm_bytes_per_s"])
    t_f, t_b = int(least(f) * 1e9), int(least(b) * 1e9)
    ops, t = [], 0
    for i in range(12):
        for dur in (t_f, t_f, t_b):
            ops.append((f"flash_attention.{len(ops)} (bf16[1])", t, 2 * dur))
            t += 2 * dur
    record = {"remat": True, "model_config": cfg, "micro_batch": 16,
              "seq_len": 1024, "device_kind": "TPU v5 lite"}
    reader = core.layer_metric_reader("flash_attention_roofline")
    got = reader.compute(record, tr.Reduced(_rows(ops)))
    assert got == pytest.approx(50.0, rel=0.02)
    assert reader.compute(record, tr.Reduced(_rows([("x.1 f32[1]", 0, 5)]))
                          ) is None

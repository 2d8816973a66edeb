"""The readers of the program's step timeline
(``benchmarks/layer_metrics/_step_timeline.py``) on made-up timelines, and
``grouped_matmul_roofline`` on a made-up slice of two steps."""

import pytest

from benchmarks import core
from benchmarks.layer_metrics import _step_timeline

CELL = core.load_json(core.ROOT + "/benchmarks/configs/mellum2-12b-a2.5b.json")
L, F, HELD = 2304, 896, 16
HOST = ["train.host_cpu_ms.step", "train.host_cpu_ms.outside",
        "train.host_ms.input", "train.host_ms.dispatch",
        "train.host_ms.report", "train.host_ms.outside"]
#: wall ms a step in each phase's spans
PHASE_MS = {"train/input": 1.5, "train/dispatch": 3.0, "train/fence": 490.0,
            "train/report": 0.25, "train/readback": 0.5}
#: CPU ms a step: the process's from start to start, the thread's inside
STEP_CPU_MS, THREAD_CPU_MS = 30.0, 12.0
#: wall ms between the end of a step and the start of the next
BETWEEN_MS = 2.0


def timeline(step_ms, profiled, first_step=10, t0=100.0, program="train_step"):
    """Records as the program keeps them: step ``i`` takes ``step_ms[i]`` from
    its start to the next one's and is ``profiled[i]``."""
    records, cpu = [], 50.0
    for i, (ms, flag) in enumerate(zip(step_ms, profiled)):
        records.append({
            "step": first_step + i, "program": program, "t0": t0,
            "t1": t0 + 1e-3 * (ms - BETWEEN_MS), "cpu0": cpu,
            "cpu1": cpu + 0.9e-3 * STEP_CPU_MS, "thread_cpu0": 7.0,
            "thread_cpu1": 7.0 + 1e-3 * THREAD_CPU_MS, "compiled": False,
            "profiled": flag,
            "phases": {k: [1e-3 * v, 1 + (k == "train/fence")]
                       for k, v in PHASE_MS.items()},
            "counters": {}})
        t0 += 1e-3 * ms
        cpu += 1e-3 * STEP_CPU_MS
    return records


@pytest.fixture
def kept(monkeypatch):
    """-> a function that makes the program's timeline the given records."""
    def keep(records):
        def program_timeline(read=False, steps=None):
            return [r for r in records
                    if steps is None or r["step"] in set(steps)]
        monkeypatch.setattr(_step_timeline, "program_timeline",
                            program_timeline)
    return keep


def window(records):
    return {"t0": records[0]["t0"] - 0.01, "t1": records[-1]["t1"] + 0.01}


# ------------------------------------------------------------ the host's CPU
def test_a_slow_run_reads_the_distance_between_the_two_speeds(kept):
    """Five profiled steps of 500 ms, the last holding the session's end,
    then eight of 507.5: the run drew the slow speed."""
    records = timeline([500.0] * 4 + [2500.0] + [507.5] * 8 + [507.5],
                       [True] * 5 + [False] * 9)
    kept(records)
    reader = core.layer_metric_reader("train.step_ms.unprofiled_less_profiled")
    assert reader.compute(window(records), object()) == pytest.approx(7.5)
    # a fast run reads nothing between them
    even = timeline([500.0] * 14, [True] * 5 + [False] * 9)
    kept(even)
    assert reader.compute(window(even), object()) == pytest.approx(0.0)
    # fewer than three steps of a kind: no number (the last record closes
    # the step before it and opens none)
    for flags in ([True] * 2 + [False] * 12, [True] * 11 + [False] * 3):
        kept(timeline([500.0] * 14, flags))
        assert reader.compute(window(even), object()) is None


@pytest.mark.parametrize("name,want", [
    ("train.host_cpu_ms.step", STEP_CPU_MS),
    ("train.host_cpu_ms.outside", STEP_CPU_MS - THREAD_CPU_MS),
    ("train.host_ms.input", 1.5), ("train.host_ms.dispatch", 3.0),
    ("train.host_ms.report", 0.75), ("train.host_ms.outside", BETWEEN_MS)])
def test_host_time_is_read_over_the_unprofiled_steps(kept, name, want):
    records = timeline([500.0] * 12, [True] * 4 + [False] * 8)
    # the profiled steps' host works more: they are not read
    for r in records[:4]:
        r["phases"]["train/dispatch"][0] = 1.0
        r["thread_cpu1"] += 0.5
    kept(records)
    reader = core.layer_metric_reader(name)
    assert reader.compute(window(records), object()) == pytest.approx(want)
    # with fewer than three unprofiled steps left there is no number
    kept(timeline([500.0] * 12, [True] * 9 + [False] * 3))
    assert reader.compute(window(records), object()) is None


def test_cpu_is_a_mean_and_wall_a_median(kept):
    """A CPU clock that ticks every 10 ms reads a step's 27 ms as 20 or 30:
    the mean over the steps is still the window's; a step that stalled moves
    no median of the wall."""
    records = timeline([500.0] * 12, [False] * 12)
    cpu = records[0]["cpu0"]
    for i, r in enumerate(records):
        r["cpu0"] = cpu
        cpu += 0.030 if i % 10 < 7 else 0.020          # 27 ms a step
    records[5]["phases"]["train/dispatch"][0] = 0.900  # one stalled dispatch
    kept(records)
    read = {name: core.layer_metric_reader(name).compute(
        window(records), object()) for name in HOST}
    assert read["train.host_cpu_ms.step"] == pytest.approx(
        1e3 * (records[-1]["cpu0"] - records[0]["cpu0"]) / 11)
    assert 26.0 < read["train.host_cpu_ms.step"] < 28.0
    assert read["train.host_ms.dispatch"] == pytest.approx(3.0)


def test_records_outside_the_window_and_of_other_programs_are_left_out(kept):
    records = timeline([500.0] * 12, [False] * 12)
    for r in records[:3] + records[-2:]:
        r["phases"]["train/input"][0] = 1.0     # 1000 ms: set-up's, check's
    kept(records + timeline([9.0] * 12, [False] * 12, t0=records[4]["t0"],
                            program="eval_step"))
    inside = {"t0": records[3]["t0"] - 1e-4, "t1": records[-3]["t1"] + 1e-4}
    steps = _step_timeline.window_steps(inside)
    assert len(steps) == 12 - 5 - 1
    assert {s["host_ms"]["input"] for s in steps} == {1.5}
    assert all(s["host_ms"]["outside"] == pytest.approx(BETWEEN_MS)
               for s in steps)
    assert all(s["wall_ms"] == pytest.approx(500.0) for s in steps)
    # a step the ring no longer follows with its next one is no step
    kept(records[:6] + records[7:])
    assert len(_step_timeline.window_steps(window(records))) == 11 - 2


@pytest.mark.parametrize("name", HOST + [
    "train.step_ms.unprofiled_less_profiled", "grouped_matmul_roofline"])
def test_a_program_without_a_timeline_has_no_number(monkeypatch, name):
    records = timeline([500.0] * 12, [True] * 4 + [False] * 8)
    monkeypatch.setattr(_step_timeline, "program_timeline",
                        lambda read=False, steps=None: None)
    monkeypatch.setattr(_step_timeline, "slice_rows", lambda: pytest.fail(
        "no trace is read for a program that keeps no timeline"))
    reader = core.layer_metric_reader(name)
    record = dict(window(records), model_config=CELL,
                  device_kind="TPU v5 lite")
    assert reader.compute(record, object()) is None
    assert reader.compute({}, None) is None


def test_the_program_is_asked_for_what_it_keeps(monkeypatch):
    """Against the program itself: its records come back, and a program
    whose telemetry has no ``step_timeline`` (the parent's) gives None."""
    from deeperspeed_tpu import telemetry
    from deeperspeed_tpu.telemetry import trace

    with trace.step_span("train/step", 7, "train_step"):
        pass
    assert [r["step"] for r in _step_timeline.program_timeline()] == [7]
    assert _step_timeline.program_timeline(steps=[8]) == []
    monkeypatch.delattr(telemetry, "step_timeline")
    assert _step_timeline.program_timeline() is None


# ------------------------------------------------- the grouped matmul's share
def test_grouped_matmul_cost_hand_worked():
    cost = core.load_kernel_cost("grouped_matmul")
    # one slot, L = 4, F = 2, gate | up side by side: 4 x 4 and 2 x 4
    # products, 2 x 24 FLOPs forward, four times that with the recomputed
    # forward and the backward's transposes
    assert cost.slot_flops(4, 2, gated=True, remat=True) == 8 * 24
    assert cost.slot_flops(4, 2, gated=False, remat=False) == 6 * 16
    step = cost.train_step(3, 1, 4, 2)
    assert step["flops"] == 3 * 8 * 24
    # rows: forward twice (4 + 4 + 2 + 4), backward 2 (4 + 2) + 2 (4 + 4);
    # the expert's 24 weights read three times in bf16, written once in f32
    assert step["bytes"] == 3 * (2 * 14 + 28) * 2 + 3 * 24 * 2 + 24 * 4
    assert cost.slot_flops(L, F, True, True) == 8 * (L * 1792 + F * L)
    # on the compute side of the v5e's ridge at the cell's load: 16.5 ms of
    # operations over 5.9 ms of traffic a layer
    cell = cost.train_step(65536, HELD, L, F)
    assert cell["flops"] / 197e12 > 2.5 * cell["bytes"] / 819e9


def slice_of_two_steps(ms=(60.0, 120.0)):
    """Rows as ``program_trace.read_rows`` gives them: two whole steps, in
    each 32 kernel events that add up to ``ms``, other operations beside
    them and one kernel event before the first step."""
    host, ops = [], [["grouped_matmul.1", 500, 10_000, "jit_warm"]]
    for i, took in enumerate(ms):
        start = 1_000_000 + i * 1_000_000_000
        host.append(["dst:train/step", start, 900_000_000,
                     {"step_num": str(40 + i)}])
        host.append(["dst:train/dispatch", start + 10, 5_000_000, {}])
        for k in range(32):
            ops.append([f"grouped_matmul.{k}", start + 1000 + k * 20_000_000,
                        int(took * 1e6 / 32), "jit_train_step"])
        ops.append(["fusion.3", start + 2000, 7_000_000, "jit_train_step"])
    return {"ops": ops, "host": host, "scopes": {}}


COUNTERS = {"moe_layer_applications": 4, "moe_slots_dropped": 0.0,
            "moe_rows_computed": 70000.0}


@pytest.fixture
def traced(kept, monkeypatch):
    """A traced Mellum run of two steps of different load -> (reader,
    record, the two steps' records)."""
    from deeperspeed_tpu import telemetry

    records = timeline([900.0, 900.0], [True, True], first_step=40)
    for r, slots in zip(records, (40000.0, 80000.0)):
        r["counters"] = dict(COUNTERS, moe_slots_held=slots)
    kept(records)
    monkeypatch.setattr(_step_timeline, "slice_rows", slice_of_two_steps)
    monkeypatch.setattr(telemetry, "kernel_passes", lambda: {
        "grouped_matmul": {"forward": 8, "recomputed": 0, "backward": 24}})
    record = {"model_config": CELL, "device_kind": "TPU v5 lite"}
    return core.layer_metric_reader("grouped_matmul_roofline"), record, records


def test_grouped_matmul_roofline_counts_each_steps_own_slots(traced):
    reader, record, records = traced
    assert _step_timeline.kernel_ns_by_step(
        slice_of_two_steps(), "grouped_matmul") == {
        40: 60_000_000, 41: 120_000_000}
    per_slot = 8 * (L * 2 * F + F * L)
    want = 100 * 4 * (40000 + 80000) * per_slot / 197e12 / 0.180
    got = reader.compute(record, object())
    assert got == pytest.approx(want) and 66 < got < 68
    # the window's mean would not do: the same slots the other way round
    for r, slots in zip(records, (80000.0, 40000.0)):
        r["counters"]["moe_slots_held"] = slots
    assert reader.compute(record, object()) == pytest.approx(want)
    records[1]["counters"]["moe_slots_held"] = 160000.0
    assert reader.compute(record, object()) == pytest.approx(2 * want)


@pytest.mark.parametrize("spoil", [
    "a step's record is missing", "a slot was dropped",
    "the step holds other kernel calls", "a step without a kernel event",
    "another model", "no trace"])
def test_grouped_matmul_roofline_without_what_it_stands_on(traced, spoil,
                                                           monkeypatch):
    reader, record, records = traced
    trace = object()
    if spoil == "a step's record is missing":
        del records[0]
    elif spoil == "a slot was dropped":
        records[1]["counters"]["moe_slots_dropped"] = 2.0
    elif spoil == "the step holds other kernel calls":
        records[0]["counters"]["moe_layer_applications"] = 3
    elif spoil == "a step without a kernel event":
        rows = slice_of_two_steps()
        rows["ops"] = [op for op in rows["ops"] if op[1] < 1_000_000_000]
        monkeypatch.setattr(_step_timeline, "slice_rows", lambda: rows)
    elif spoil == "another model":
        record = dict(record, model_config={"hidden_size": 768})
    else:
        trace = None
    assert reader.compute(record, trace) is None

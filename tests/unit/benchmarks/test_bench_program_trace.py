"""``benchmarks/program_trace.py``: device time by the program's scopes and
idle time by its host spans, on a recorded slice of a v5e trace (two steps of
``train-160m`` with the program's ``dst:`` events, raw instruction names and
the scopes the program published; my chip run, PR 27) and on hand-made rows
for the edges; and every reader that stands on it.
"""

import os

import pytest

from benchmarks import core, program_trace as pt, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
SLICE = os.path.join(HERE, "program_trace_slice_train160m.json.gz")
NEW_METRICS = [
    "train.scope_ms.attention", "train.scope_ms.attention_layout",
    "train.scope_ms.mlp", "train.scope_ms.head_ce",
    "train.scope_ms.optimizer", "train.scope_unattributed_pct",
    "train.idle_ms.fence", "train.idle_ms.input", "train.idle_ms.dispatch",
    "train.idle_ms.outside"]


@pytest.fixture(scope="module")
def rows():
    return trace_reduce.load_rows(SLICE)


@pytest.fixture(scope="module")
def found(rows):
    return pt.ProgramTrace(rows)


# ------------------------------------------------------- the recorded slice
def test_slice_holds_what_the_chip_wrote(rows):
    programs = {op[3] for op in rows["ops"]}
    assert "jit_train_step" in programs and list(rows["scopes"]) == [
        "jit_train_step"]
    names = {h[0] for h in rows["host"]}
    assert {"dst:train/step", "dst:train/fence", "dst:train/input",
            "dst:train/dispatch", "dst:train/report",
            "bench:train_batch", "bench:batch_prep"} <= names
    fences = {h[3]["who"] for h in rows["host"] if h[0] == "dst:train/fence"}
    assert fences == {"throughput_timer.start", "throughput_timer.stop"}
    # raw names: an instruction's own, as the program's registry keys them
    assert all(" " not in op[0] and not op[0].startswith("%")
               for op in rows["ops"])


def test_scope_sums_on_the_slice(found):
    assert found.steps == 2
    # the old reduction and this one agree on what busy is
    assert found.busy_ns == pytest.approx(2 * 130.927035e6, rel=1e-6)
    assert found.scope_ms_per_step("attention") == pytest.approx(66.2814, abs=1e-3)
    assert found.scope_ms_per_step("attention_layout") == pytest.approx(11.9076, abs=1e-3)
    assert found.scope_ms_per_step("mlp") == pytest.approx(31.2054, abs=1e-3)
    assert found.scope_ms_per_step("head_ce") == pytest.approx(25.4522, abs=1e-3)
    assert found.scope_ms_per_step(
        "grad_accumulate", "grad_norm_clip", "optimizer") == pytest.approx(
        6.8152, abs=1e-3)
    assert found.unattributed_pct() == pytest.approx(0.00561, abs=1e-4)
    # the outermost scopes and what is under none make up the busy time
    outer = ("embed", "attention", "mlp", "head_ce", "grad_accumulate",
             "grad_norm_clip", "optimizer")
    total = sum(found.by_scope.get(s, 0) for s in outer)
    assert total + found.unattributed_ns == found.busy_ns
    # nested scopes are inside their parents
    assert found.by_scope["attention_layout"] < found.by_scope["attention"]
    assert found.by_scope["flash_attention"] < found.by_scope["attention"]


def test_busy_time_agrees_with_the_first_reduction(rows, found):
    ops = [[op[0], op[1], op[2]] for op in rows["ops"]]
    reduced = trace_reduce.Reduced(
        {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
         "host": []})
    assert found.busy_ns == pytest.approx(reduced.busy_s * 1e9, rel=1e-9)


def test_idle_is_cut_along_the_programs_spans(rows, found):
    idle = found.idle_by_span
    busy = [(op[1], op[1] + op[2]) for op in rows["ops"]]
    lo, hi = min(s for s, _ in busy), max(e for _, e in busy)
    assert sum(idle.values()) == (hi - lo) - found.busy_ns
    four = [found.idle_ms_per_step(p)
            for p in ("fence", "input", "dispatch", pt.OUTSIDE)]
    assert four == pytest.approx([2.1177, 1.5645, 0.9779, 0.8568], abs=1e-3)
    assert sum(four) * found.steps * 1e6 == pytest.approx(sum(idle.values()))
    # the harness's spans name only what the program's leave open
    assert idle["bench:batch_prep"] > 0.5e6
    assert idle["bench:train_batch"] < idle["train/fence"]


# ------------------------------------------------------------ hand-made rows
def span(name, start, end):
    return [name, start, end - start, {}]


def test_a_gap_under_two_spans_is_cut_not_given_to_the_longer():
    spans = pt.timeline([span("dst:train/fence", 0, 40),
                         span("dst:train/input", 40, 100)])
    assert pt.cut_along((10, 90), [spans]) == {
        "dst:train/fence": 30, "dst:train/input": 50}


def test_a_gap_under_no_span_is_outside_or_the_harnesss():
    program = pt.timeline([span("dst:train/report", 0, 10)])
    harness = pt.timeline([span("bench:batch_prep", 20, 50)])
    assert pt.cut_along((5, 60), [program]) == {
        "dst:train/report": 5, pt.OUTSIDE: 50}
    assert pt.cut_along((5, 60), [program, harness]) == {
        "dst:train/report": 5, "bench:batch_prep": 30, pt.OUTSIDE: 20}
    assert pt.cut_along((5, 60), [[], []]) == {pt.OUTSIDE: 55}


def test_nested_spans_give_the_innermost():
    spans = pt.timeline([span("dst:train/input", 0, 100),
                         span("dst:train/prefetch", 20, 60),
                         span("dst:train/fence", 30, 40)])
    assert spans == [(0, 20, "dst:train/input"), (20, 30, "dst:train/prefetch"),
                     (30, 40, "dst:train/fence"),
                     (40, 60, "dst:train/prefetch"),
                     (60, 100, "dst:train/input")]
    assert pt.cut_along((0, 100), [spans]) == {
        "dst:train/input": 60, "dst:train/prefetch": 30, "dst:train/fence": 10}


def test_self_times_take_a_loops_body_out_of_the_loop():
    ops = [["while.1", 0, 100, "p"], ["fusion.1", 10, 30, "p"],
           ["fusion.2", 50, 20, "p"], ["copy.3", 120, 5, "p"]]
    assert {op[0]: ns for op, ns in pt.self_times(ops)} == {
        "while.1": 50, "fusion.1": 30, "fusion.2": 20, "copy.3": 5}


def test_scopes_of_reads_components_not_substrings():
    assert pt.scopes_of(
        "jit(train_step)/transpose(jvp(GPTNeoX))/layers_3/attention/attention"
        "/jit(flash_attention)/attention_layout/transpose") == (
        "attention", "attention", "attention_layout")
    assert pt.scopes_of("jit(step)/transpose(jvp(head_ce))/dot_general") == (
        "head_ce",)
    assert pt.scopes_of("jit(step)/jvp(GPTNeoX)/layers_0/add") == ()
    assert pt.scopes_of("") == ()
    assert pt.instruction_name("%fusion.6 = bf16[8,2]{1,0} fusion(%p)") == "fusion.6"
    assert pt.program_name("jit_train_step(1594121)") == "jit_train_step"


def test_a_program_without_scopes_or_spans_reads_as_nothing(rows):
    bare = pt.ProgramTrace({
        "ops": rows["ops"], "scopes": {},
        "host": [h for h in rows["host"] if h[0].startswith("bench:")]})
    assert bare.steps == 0
    assert bare.scope_ms_per_step("attention") is None
    assert bare.unattributed_pct() is None
    assert bare.idle_ms_per_step("fence") is None


def test_slice_rows_keeps_whole_steps(rows):
    one = pt.slice_rows(rows, 1)
    assert pt.ProgramTrace(one).steps == 1
    assert len(one["ops"]) < len(rows["ops"])
    assert set(one["scopes"]["jit_train_step"]) <= set(
        rows["scopes"]["jit_train_step"])


# ------------------------------------------------------------------ readers
@pytest.mark.parametrize("metric", NEW_METRICS)
def test_reader_finds_nothing_where_there_is_nothing(metric):
    assert core.layer_metric_reader(metric).compute({}, None) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_reader_reads_the_runs_trace(metric, found, monkeypatch):
    """Every reader goes through ``for_reader`` -> ``of_run``; given the
    slice as the run's trace, each finds a number."""
    monkeypatch.setattr(pt, "of_run", lambda: found)
    reader = core.layer_metric_reader(metric)
    value = reader.compute({"losses": [1.0]}, object())
    assert isinstance(value, float) and value > 0
    # a serve record, or an untraced run, is not theirs
    assert reader.compute({"rounds": 3}, object()) is None
    assert reader.compute({"losses": [1.0]}, None) is None


def test_of_run_without_a_trace_or_a_registry(tmp_path, monkeypatch):
    pt.of_run.cache_clear()
    assert pt.of_run(str(tmp_path)) is None          # no .xplane.pb there
    monkeypatch.setattr(pt, "published_scopes", lambda: None)
    pt.of_run.cache_clear()
    assert pt.of_run(str(tmp_path)) is None          # the parent's program
    pt.of_run.cache_clear()


def test_manifest_lists_the_new_metrics_for_both_train_cells():
    manifest = core.load_manifest()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for cell in ("train-410m", "train-160m"):
        reported = {m["name"] for m in
                    core.metrics_for(manifest, cell, "per_layer")}
        assert set(NEW_METRICS) <= reported
    for name in NEW_METRICS:
        entry = by_name[name]
        assert entry["moves"] == "train_tokens_per_s_chip"
        assert entry["better"] == "lower"
        assert os.path.exists(os.path.join(
            core.BENCH_DIR, "layer_metrics", name + ".py"))

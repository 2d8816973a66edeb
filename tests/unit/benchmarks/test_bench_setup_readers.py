"""The readers of the program's set-up timeline
(``benchmarks/layer_metrics/_setup_timeline.py`` and the eight ``setup.*``
metrics on it) on a made-up timeline and after a tiny run, their entries in
BENCHMARK.json, and every clause but one of the manifest's own test of its
per-layer entries (``tests/conftest.py::SETUP_HAD_NO_INSIDE``)."""

import time

import pytest

from benchmarks import core
from benchmarks.layer_metrics import _setup_timeline, _step_timeline
from deeperspeed_tpu import telemetry
from deeperspeed_tpu.telemetry.trace import _CompileStats

MANIFEST = core.load_manifest()
SETUP = ["setup.import_s", "setup.initialize_s", "setup.first_steps_s",
         "setup.trace_s", "setup.lower_s", "setup.cache_load_s",
         "setup.backend_compile_s", "setup.outside_program_s"]
#: a made-up measuring process: born at 100, its window opens at 150
BORN, OPENS = 100.0, 150.0
SPANS = [
    {"name": "setup/import", "t0": 104.0, "t1": 110.0, "parent": None},
    {"name": "setup/initialize", "t0": 120.0, "t1": 129.0, "parent": None},
    {"name": "setup/initialize/state", "t0": 122.0, "t1": 128.0,
     "parent": "setup/initialize"},
    # the check's second engine, after the window opened: not set-up
    {"name": "setup/initialize", "t0": 210.0, "t1": 215.0, "parent": None},
]
STEPS = [
    {"program": "train_step", "step": 0, "t0": 130.0, "t1": 145.0},
    {"program": "train_step", "step": 1, "t0": 145.5, "t1": 146.0},
    {"program": "train_step", "step": None, "t0": None, "t1": None},
    {"program": "train_step", "step": 2, "t0": 150.5, "t1": 151.0},
]
#: (kind, start, end): a trace of 8 s with 5 s of traces inside it, a
#: lowering, a cache load of 2 s inside a backend-compile event of 2.5, the
#: reference's jit before the engine, and a compile after the window opened
COMPILES = [
    ("trace", 131.0, 134.0), ("trace", 134.5, 136.5), ("trace", 130.5, 138.5),
    ("trace", 112.0, 113.0),
    ("lower", 138.5, 141.0),
    ("cache_load", 141.2, 143.2), ("backend_compile", 141.0, 143.5),
    ("backend_compile", 123.0, 124.0),
    ("trace", 160.0, 170.0), ("backend_compile", 170.0, 190.0),
]
BY_HAND = {
    "setup.import_s": 6.0, "setup.initialize_s": 9.0,
    "setup.first_steps_s": 15.5, "setup.trace_s": 9.0, "setup.lower_s": 2.5,
    "setup.cache_load_s": 2.0, "setup.backend_compile_s": 1.5,
    "setup.outside_program_s": 50.0 - 6.0 - 9.0 - 15.5}


def heard(compiles):
    stats = _CompileStats()
    for kind, start, end in compiles:
        stats._keep(kind, start, end, None)
    return stats


@pytest.fixture
def kept(monkeypatch):
    """-> a function that makes the program's set-up the given one."""
    def keep(spans=SPANS, steps=STEPS, compiles=COMPILES, born=BORN):
        timeline = {"process_t0": born, "spans": spans, "compile": {},
                    "slowest": {}}
        monkeypatch.setattr(_setup_timeline, "program_setup",
                            lambda: (timeline, heard(compiles)))
        monkeypatch.setattr(_step_timeline, "program_timeline",
                            lambda read=False, steps_=None: steps)
    return keep


@pytest.mark.parametrize("name", SETUP)
def test_each_reader_gives_the_number_reckoned_by_hand(name, kept):
    kept()
    reader = core.layer_metric_reader(name)
    assert reader.compute({"t0": OPENS, "t1": OPENS + 51.0},
                          None) == pytest.approx(BY_HAND[name])
    # no window's record: nothing to read
    assert reader.compute({}, None) is None
    # a process whose start cannot be had: no stretch to cut
    kept(born=None)
    assert reader.compute({"t0": OPENS}, None) is None


@pytest.mark.parametrize("name", SETUP)
def test_against_a_program_without_a_setup_timeline_a_reader_gives_none(
        name, monkeypatch):
    monkeypatch.delattr(telemetry, "setup_timeline")
    assert _setup_timeline.program_setup() is None
    assert core.layer_metric_reader(name).compute(
        {"t0": time.perf_counter()}, None) is None


def test_the_four_parts_are_the_stretch_and_no_phase_exceeds_it(kept):
    """A span, a step and compile work that began before the process's start
    as the clock has it, or end after the window opened, count as far as
    they lie inside ``[process start, record["t0"]]``."""
    kept(spans=[{"name": "setup/import", "t0": 90.0, "t1": 110.0,
                 "parent": None},
                {"name": "setup/initialize", "t0": 140.0, "t1": 160.0,
                 "parent": None}],
         steps=[{"program": "train_step", "step": 0, "t0": 120.0,
                 "t1": 125.0}],
         compiles=[(kind, 50.0, 500.0) for kind in _CompileStats.EVENTS])
    record = {"t0": OPENS}
    got = {name: core.layer_metric_reader(name).compute(record, None)
           for name in SETUP}
    assert got["setup.import_s"] == 10.0
    assert got["setup.initialize_s"] == 10.0
    assert got["setup.first_steps_s"] == 5.0
    assert got["setup.outside_program_s"] == 25.0
    for kind in ("trace", "lower", "cache_load"):
        assert got[f"setup.{kind}_s"] == OPENS - BORN
    assert got["setup.backend_compile_s"] == 0.0    # all of it a cache load
    assert all(0.0 <= v <= OPENS - BORN for v in got.values())


@pytest.mark.parametrize("name", SETUP)
def test_the_entries_are_as_the_issue_gives_them(name):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": "s", "better": "lower",
        "source": "program_span", "layer": "set-up", "moves": "setup_s",
        "workloads": [w["name"] for w in MANIFEST["workloads"]]}
    assert [m["name"] for m in MANIFEST["per_layer"][-8:]] == SETUP
    assert [m["name"] for m in MANIFEST["per_layer"]
            if m["moves"] == "setup_s"] == SETUP


def test_after_a_tiny_run_every_reader_returns_a_number():
    import deeperspeed_tpu as dst
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    model = GPTNeoX(GPTNeoXConfig.tiny())
    engine, _, _, _ = dst.initialize(model=model, config={
        "train_batch_size": 16, "gradient_accumulation_steps": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2}, "bf16": {"enabled": True}})
    batch = model.example_batch(batch_size=16, seq_len=32)
    for _ in range(2):
        engine.train_batch(batch=batch)
    record = {"t0": time.perf_counter()}
    got = {name: core.layer_metric_reader(name).compute(record, None)
           for name in SETUP}
    assert all(isinstance(v, float) for v in got.values()), got
    stretch = record["t0"] - telemetry.setup_timeline()["process_t0"]
    assert all(0.0 <= v <= stretch for v in got.values()), got
    assert got["setup.import_s"] > 0 and got["setup.initialize_s"] > 0
    assert got["setup.first_steps_s"] > 0 and got["setup.trace_s"] > 0
    assert sum(got[n] for n in ("setup.import_s", "setup.initialize_s",
                                "setup.first_steps_s",
                                "setup.outside_program_s")
               ) == pytest.approx(stretch)


def test_the_manifests_test_holds_but_for_its_clause_on_setup(listed):
    """``test_bench_manifest.py::test_layer_metrics_have_readers_and_move_
    what_their_cells_report`` less ``m["moves"] != "setup_s"`` (PR 26, when
    set-up had no inside), on the manifest as committed and as a later PR's
    appended entries would leave it."""
    manifest, bench_dir = listed
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = [w["name"] for w in manifest["workloads"]]
    layers = {}
    for m in manifest["per_layer"]:
        reader = core.layer_metric_reader(m["name"], bench_dir)
        assert callable(reader.compute)
        assert m["moves"] in e2e
        for cell in m.get("workloads", []):
            assert cell in cells
            assert m["moves"] in [x["name"] for x in core.metrics_for(
                manifest, cell, "end_to_end")]
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())   # letter for letter
    # and what the clause stood for: nothing else moves set-up
    assert [m["name"] for m in manifest["per_layer"]
            if m["moves"] == "setup_s"] == SETUP

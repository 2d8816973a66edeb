"""BENCHMARK.json against the contract's limits, and the harness as data:
a new cell, traffic mix and per-layer metric are new files plus one entry."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks import core

MANIFEST = core.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "head_dim",
               "_dim", "_rank", "experts_per_tok")
#: the rate a training cell reports, which a claim on its step has to move
STEP_RATE = "train_tokens_per_s_chip"


def test_top_level_keys_and_sizes():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert 1 <= len(MANIFEST["workloads"]) <= 24
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    assert any(MANIFEST["command"][1].startswith(path + "/")
               for path in MANIFEST["paths"])
    for path in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(core.ROOT, path))


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_units_and_uniqueness(kind):
    names = [e["name"] for e in MANIFEST[kind]]
    assert len(names) == len(set(names))
    for e in MANIFEST[kind]:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]


def test_entries_have_exactly_the_contract_keys():
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_every_workloads_files_exist_and_configs_are_used():
    used = set()
    pairs = set()
    for w in MANIFEST["workloads"]:
        cell, config, traffic = core.find_cell(MANIFEST, w["name"])
        used.add(w["config"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        runner = os.path.join(core.BENCH_DIR, "runners",
                              traffic["runner"] + ".py")
        assert os.path.isfile(runner)
        assert config["hidden_size"] % config["num_attention_heads"] == 0
    assert used == {c["name"] for c in MANIFEST["configs"]}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    for c in MANIFEST["configs"]:
        assert c["file"].startswith("benchmarks/")
        assert not any(w in key for key in c["reduced"] for w in WIDTH_WORDS)


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]]
                         + ["rehearsal"])
def test_every_cell_has_limits_set_from_readings_that_stand_clear(cell):
    """limits/<cell>.json: each limit between what sound runs and what the
    control read, the control three times clear or more, on the cell's own
    device (the rehearsal's on the CPU)."""
    limits = core.load_limits(cell, rehearse=cell == "rehearsal")
    want = "cpu" if cell == "rehearsal" else "tpu"
    assert limits["device"]["platform"] == want
    numbers = {k: v for k, v in limits.items() if k != "device"}
    assert set(numbers) == {"grad_rel_err", "adam_update_rel_err"}
    for v in numbers.values():
        assert v["sound_largest"] < v["limit"] < v["control_smallest"]
        assert v["control_smallest"] >= 3 * v["sound_largest"]
        assert v["control_seeds"] >= 3 and v["sound_seeds"] >= 3
        assert v["limit"] == pytest.approx(
            (v["sound_largest"] * v["control_smallest"]) ** 0.5)


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in MANIFEST["end_to_end"])
    for w in MANIFEST["workloads"]:
        e2e = [m["name"] for m in core.metrics_for(MANIFEST, w["name"],
                                                   "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert core.metrics_for(MANIFEST, w["name"], "per_layer")


def test_layer_metrics_have_readers_and_move_what_their_cells_report(listed):
    manifest, bench_dir = listed
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = [w["name"] for w in manifest["workloads"]]
    layers = {}
    for m in manifest["per_layer"]:
        reader = core.layer_metric_reader(m["name"], bench_dir)
        assert callable(reader.compute)
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for cell in m.get("workloads", []):
            assert cell in cells
            assert m["moves"] in [x["name"] for x in core.metrics_for(
                manifest, cell, "end_to_end")]
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())   # letter for letter


def test_a_reader_with_nothing_to_read_returns_nothing(listed):
    manifest, bench_dir = listed
    for m in manifest["per_layer"]:
        assert core.layer_metric_reader(m["name"], bench_dir).compute(
            {}, None) is None


def test_no_reader_waits_unlisted_and_no_manifest_beside_the_manifest(listed):
    """A reader that ``per_layer`` does not list is never asked, and a list
    of entries beside BENCHMARK.json is how a metric goes missing.  What else
    a later PR keeps here (a module its readers share, named ``_...``) is its
    own affair."""
    manifest, bench_dir = listed
    held = os.listdir(os.path.join(bench_dir, "layer_metrics"))
    assert not [f for f in held if f.endswith(".json")]
    assert {f[:-3] for f in held if f.endswith(".py")
            and not f.startswith("_")} <= {m["name"]
                                           for m in manifest["per_layer"]}


def cells_reporting(manifest, metric):
    return [w["name"] for w in manifest["workloads"]
            if metric in [m["name"] for m in core.metrics_for(
                manifest, w["name"], "end_to_end")]]


def shares_of_the_peak(manifest, cell):
    """The whole step's shares of the chip's peak that a cell prints: the
    per-layer entries with ``mfu`` in their names that move the rate the cell
    reports.  A claim on the cell's step stands only where one bounds it:
    with none, a kernel taken off the path leaves nothing to hold the gain
    to.  A later PR may list another (the step count by the device); which
    one bounds a claim, and how it is compared, PERF.md section 3 says: one
    that counts routed slots (``train.swa_moe_mfu_pct``) reads the seed's
    load with the step and is compared in pairs on one seed only."""
    return [m["name"] for m in core.metrics_for(manifest, cell, "per_layer")
            if "mfu" in m["name"] and m["moves"] == STEP_RATE]


@pytest.mark.parametrize("cell", cells_reporting(MANIFEST, STEP_RATE))
def test_every_training_cell_has_a_share_of_the_whole_steps_peak(cell, listed):
    manifest, _ = listed
    assert shares_of_the_peak(manifest, cell), cell


@pytest.mark.parametrize("name", [m["name"] for m in MANIFEST["per_layer"]
                                  if "roofline" in m["name"]])
def test_every_roofline_is_read_in_some_cell(name):
    assert any(name in [m["name"] for m in core.metrics_for(
        MANIFEST, w["name"], "per_layer")] for w in MANIFEST["workloads"])


@pytest.mark.parametrize("cell", cells_reporting(MANIFEST, STEP_RATE))
def test_a_cell_without_its_share_of_the_peak_is_refused(cell, bench_copy):
    """The rule above on a copy of the manifest: whole it holds; with the
    cell's shares taken out it fails for every cell they served and for no
    other."""
    whole = core.load_manifest(bench_copy)
    gone = shares_of_the_peak(whole, cell)
    assert gone
    removed = core.load_manifest(bench_copy)
    removed["per_layer"] = [m for m in removed["per_layer"]
                            if m["name"] not in gone]
    assert not shares_of_the_peak(removed, cell)
    for other in cells_reporting(whole, STEP_RATE):
        kept = set(shares_of_the_peak(whole, other)) - set(gone)
        assert set(shares_of_the_peak(removed, other)) == kept


def test_new_cell_metric_and_mix_are_files_plus_entries(bench_copy):
    """No file that is there is edited: a traffic file, a metric reader and
    one entry each are enough for the harness to find them by name."""
    before = {p: open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(bench_copy / "benchmarks")
              for p in fs}
    mix = core.load_json(bench_copy / "benchmarks/traffic/pretrain-1024.json")
    mix["micro_batch"] = 4
    (bench_copy / "benchmarks/traffic/pretrain-tmp.json").write_text(
        json.dumps(mix))
    (bench_copy / "benchmarks/layer_metrics/tmp.steps.py").write_text(
        "def compute(record, trace):\n    return record.get('attempted')\n")
    shutil.copy(bench_copy / "benchmarks/limits/train-160m.json",
                bench_copy / "benchmarks/limits/train-tmp.json")
    manifest = core.load_manifest(bench_copy)
    manifest["workloads"].append({
        "name": "train-tmp", "config": "pythia-160m", "traffic":
        "pretrain-tmp", "chips": 1, "why": "temporary"})
    manifest["per_layer"].append({
        "name": "tmp.steps", "unit": "steps", "better": "higher", "source":
        "program_counter", "layer": "train step", "moves":
        "train_tokens_per_s_chip", "workloads": ["train-tmp"]})
    # the cell's name joins the list of the end-to-end metric it reports
    next(m for m in manifest["end_to_end"]
         if m["name"] == "train_tokens_per_s_chip")["workloads"].append(
        "train-tmp")
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(manifest))

    manifest = core.load_manifest(bench_copy)
    cell, config, traffic = core.find_cell(manifest, "train-tmp", bench_copy)
    assert traffic["micro_batch"] == 4 and config["hidden_size"] == 768
    names = [m["name"] for m in core.metrics_for(manifest, "train-tmp",
                                                 "per_layer")]
    assert "tmp.steps" in names and "train.step_ms" in names
    reader = core.layer_metric_reader(
        "tmp.steps", bench_copy / "benchmarks")
    assert reader.compute({"attempted": 7}, None) == 7
    assert core.load_limits("train-tmp", bench_dir=bench_copy / "benchmarks")[
        "grad_rel_err"]["limit"] > 0
    # nothing that was there changed
    after = {p: open(os.path.join(dp, p), "rb").read()
             for dp, _, fs in os.walk(bench_copy / "benchmarks") for p in fs}
    assert all(after[p] == before[p] for p in before)


def test_without_the_program_the_command_fails_and_prints_no_result(bench_copy):
    """A directory that holds only BENCHMARK.json and the paths: non-zero
    exit and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "train-160m",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=bench_copy, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout

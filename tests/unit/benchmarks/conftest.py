"""What the benchmark's tests share: the benchmark alone in a temporary copy,
and the benchmark as a PR that may only append would leave it, so that a rule
which such a PR cannot keep shows here and not at that PR's desk."""

import json
import os
import shutil

import pytest

from benchmarks import core

STEP_RATE = "train_tokens_per_s_chip"
LATER = "after a later PR's entries"
#: a reader as small as a later PR's may be: nothing to read, nothing returned
READER = "def compute(record, trace):\n    return record.get('attempted')\n"


@pytest.fixture
def bench_copy(tmp_path):
    """The benchmark alone in a temporary copy: BENCHMARK.json and paths."""
    shutil.copy(os.path.join(core.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(core.BENCH_DIR, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def later_entries(manifest):
    """What PRs of other kinds are allowed to bring, each a reader's file
    and one appended entry, for every training cell: a kernel's roofline (a
    ``perf_opt`` PR's new kernel), a second share of the peak (the step
    count by the device), and an entry without a ``workloads`` key, which
    every cell that reports the rate then prints."""
    cells = [w["name"] for w in manifest["workloads"]
             if STEP_RATE in [m["name"] for m in core.metrics_for(
                 manifest, w["name"], "end_to_end")]]
    entry = {"unit": "%", "better": "higher", "source": "device_trace",
             "moves": STEP_RATE}
    return [dict(entry, name="later.grouped_matmul_roofline",
                 layer="kernels", workloads=cells),
            dict(entry, name="later.device_mfu_pct", layer="train step",
                 workloads=cells),
            {"name": "later.steps", "unit": "steps", "better": "higher",
             "source": "program_counter", "layer": "train step",
             "moves": STEP_RATE}]


@pytest.fixture(params=["as committed", LATER])
def listed(request):
    """-> (manifest, benchmark directory): the committed benchmark, and a
    copy of it to which ``later_entries`` and their readers were added and
    in which no file that was there was edited."""
    if request.param != LATER:
        return core.load_manifest(), core.BENCH_DIR
    copy = request.getfixturevalue("bench_copy")
    manifest = core.load_manifest(copy)
    manifest["per_layer"] += later_entries(manifest)
    for m in later_entries(manifest):
        (copy / "benchmarks/layer_metrics" / (m["name"] + ".py")).write_text(
            READER)
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))
    return core.load_manifest(copy), str(copy / "benchmarks")

"""``run.py`` end to end at the tiny preset on the CPU: counts only, and it
refuses to call itself a device run."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmarks import core, run

ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _run(*args, timeout=400):
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", *args], cwd=core.ROOT, env=ENV,
        capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("workload,count_key", [
    ("train-160m", "steps"),
    ("train-410m", "steps"),
])
def test_rehearsal_prints_counts_only(workload, count_key):
    out = _run("--workload", workload, "--seed", str(2**31 + 77),
               "--seconds", "2", "--trace", "0", "--rehearse")
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["metrics"] == {}                 # no metric of time
    assert last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["counts"][count_key] > 0
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    checks = [x for x in lines if "check" in x]
    assert checks and all("limit" in c and "value" in c for c in checks)
    assert any(c["check"] == "compiles_in_window" and c["value"] == 0
               for c in checks)


def test_without_a_tpu_a_cell_fails_and_prints_no_result():
    out = _run("--workload", "train-160m", "--seed", "1", "--seconds", "1",
               "--trace", "0", timeout=120)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_unknown_workload_is_an_error():
    out = _run("--workload", "no-such-cell", "--seed", "1", "--seconds", "1",
               "--trace", "0", "--rehearse", timeout=120)
    assert out.returncode != 0 and '"correct"' not in out.stdout


def test_spans_seconds_by_name_counts_only_the_overlap():
    spans = core.Spans()
    spans.records += [("a", 0.0, 2.0), ("b", 1.5, 4.0), ("a", 5.0, 6.0),
                      ("c", 8.0, 9.0)]
    assert spans.seconds_by_name(1.0, 5.5) == {"a": 1.5, "b": 2.5}


def test_heartbeat_reports_its_longest_silence_inside_a_window():
    hb = run.Heartbeat()
    hb.beats = [0.9, 1.05, 1.1, 1.6, 1.65, 2.2]
    assert hb.longest_silence(1.0, 2.0) == pytest.approx(0.5)
    assert hb.longest_silence(3.0, 4.0) == pytest.approx(1.0)
    with run.Heartbeat(period=0.01) as live:
        time.sleep(0.1)
    assert len(live.beats) >= 3 and not live._thread.is_alive()


@pytest.mark.parametrize("exit_codes,stages,final", [
    ([0], ["prime"], 0),                       # programs found in the cache
    ([run.PRIMED, 0], ["prime", "measure"], 0),  # the first child compiled
    ([1], ["prime"], 1),                       # no chip: no second try
    ([run.PRIMED, 3], ["prime", "measure"], 3),
])
def test_supervise_measures_in_a_child_that_did_not_compile(exit_codes, stages,
                                                            final):
    commands, left = [], list(exit_codes)

    def spawn(command):
        commands.append(command)
        return left.pop(0)

    argv = ["--workload", "train-160m", "--seed", "5", "--seconds", "1"]
    assert run.supervise(argv, spawn) == final
    assert [c[c.index("--stage") + 1] for c in commands] == stages
    for c in commands:
        assert c[0] == sys.executable and c[1].endswith("benchmarks/run.py")
        assert c[2:2 + len(argv)] == argv
        # every child counts set-up from the start of the command itself
        assert abs(float(c[c.index("--started") + 1]) - time.time()) < 600


@pytest.mark.parametrize("body,expected", [
    ("import sys; sys.exit(0)", 0),
    ("import sys; sys.exit(7)", 7),
    ("import os, signal; os.kill(os.getpid(), signal.SIGKILL)", 128 + 9),
])
def test_run_child_gives_the_childs_exit_code(body, expected):
    assert run.run_child([sys.executable, "-c", body]) == expected


def test_compile_counter_tells_cache_writes_from_other_events():
    counter = core.CompileCounter()
    counter._on_cache_event("/jax/compilation_cache/cache_hits")
    counter._on_cache_event("/jax/compilation_cache/cache_misses")
    counter._on_event("/jax/core/compile/backend_compile_duration", 2.0)
    assert counter.cache_writes == 1 and counter.count == 1


def test_cpu_seconds_grow_with_work():
    before = run.cpu_seconds()
    sum(i * i for i in range(200_000))
    after = run.cpu_seconds()
    assert sum(after.values()) > sum(before.values())

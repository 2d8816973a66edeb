"""Set-up as the program times it (``telemetry/trace.py``): the compile
phases by interval and their unions, the ``dst:setup/*`` spans and
``setup_timeline()``, the first step's ``compile`` record and the operator's
time to first step."""

import glob
import time

import jax
import jax.numpy as jnp
import pytest

import deeperspeed_tpu as dst
from deeperspeed_tpu import telemetry
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.telemetry import trace
from deeperspeed_tpu.telemetry.trace import _CompileStats, span, step_span

EVENTS = _CompileStats.EVENTS
CONFIG = {"train_batch_size": 16, "gradient_accumulation_steps": 2,
          "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
          "zero_optimization": {"stage": 2}, "bf16": {"enabled": True}}


def fed(spans, kind="trace"):
    """A ``_CompileStats`` of its own that heard ``spans`` (start, end) of a
    kind, on the perf_counter clock."""
    stats = _CompileStats()
    shift = time.time() - time.perf_counter()
    for i, (start, end) in enumerate(spans):
        stats.on_time_span(EVENTS[kind], start + shift, end + shift,
                           fun_name=f"f{i}")
    return stats


# ------------------------------------------------------------------ the union
@pytest.mark.parametrize("spans,total,union", [
    # a trace of 3 s with two traces of 1 s inside it: 5 s of durations
    ([(10.5, 11.5), (11.8, 12.8), (10.0, 13.0)], 5.0, 3.0),      # nested
    ([(11.0, 12.0), (12.5, 13.5), (10.0, 13.0)], 5.0, 3.5),      # and beyond
    ([(10.0, 12.0), (11.0, 13.0), (12.5, 13.0)], 4.5, 3.0),      # overlapping
    ([(10.0, 11.0), (12.0, 13.0), (20.0, 21.0)], 3.0, 3.0),      # disjoint
    ([(10.0, 11.0), (10.0, 11.0), (10.0, 11.0)], 3.0, 1.0),      # repeated
])
def test_a_phase_is_the_union_of_its_intervals_not_their_sum(spans, total,
                                                             union):
    stats = fed(spans)
    phase = stats.phases["trace"]
    assert phase.count == len(spans)
    assert phase.seconds == pytest.approx(total, abs=1e-3)
    assert stats.seconds("trace") == pytest.approx(union, abs=1e-3)
    # nothing of it in a stretch that holds none of it
    assert stats.seconds("trace", 0.0, 9.0) == 0.0
    assert stats.seconds("lower") == 0.0


@pytest.mark.parametrize("t0,t1,want", [
    (None, None, 3.0), (10.5, None, 2.5), (None, 12.0, 2.0),
    (11.0, 11.25, 0.25), (12.9, 40.0, 0.1), (13.0, 14.0, 0.0)])
def test_a_union_is_clipped_to_the_stretch_asked_for(t0, t1, want):
    stats = fed([(10.5, 11.5), (11.8, 12.8), (10.0, 13.0)])
    got = stats.seconds("trace", t0, t1)
    assert got == pytest.approx(want, abs=1e-3)
    if t0 is not None and t1 is not None:
        assert got <= t1 - t0 + 1e-9


def test_backend_compile_is_less_the_cache_loads_inside_it(monkeypatch):
    """jax's backend-compile event wraps ``compile_or_get_cached``: a load
    from the cache lies inside it and is not a compile."""
    stats = fed([(10.0, 12.0), (20.0, 20.5)], kind="backend_compile")
    assert stats.seconds("backend_compile") == pytest.approx(2.5, abs=1e-3)
    # the cache's load has a duration only: its interval ends at the callback
    with monkeypatch.context() as patched:
        patched.setattr(trace.time, "perf_counter", lambda: 11.9)
        stats.on_duration(EVENTS["cache_load"], 1.5)
    assert stats.seconds("cache_load") == pytest.approx(1.5)
    assert stats.seconds("backend_compile") == pytest.approx(1.0, abs=1e-3)
    assert stats.seconds("backend_compile", 11.0, 21.0) == pytest.approx(
        0.1 + 0.5, abs=1e-3)
    assert stats.phases["backend_compile"].seconds == pytest.approx(
        2.5, abs=1e-3)                     # the sum keeps jax's own reading
    assert stats.programs == 2 and len(stats.compiles) == 2
    assert len(stats.cache_loads) == 1


def test_the_old_counters_keep_their_names_and_meaning():
    stats = _CompileStats()
    assert (stats.programs, stats.cache_hits, stats.cache_misses) == (0, 0, 0)
    stats.on_event("/jax/compilation_cache/cache_hits")
    stats.on_event("/jax/compilation_cache/cache_misses")
    stats.on_event("/jax/compilation_cache/cache_misses")
    stats.on_event("/jax/compilation_cache/compile_requests_use_cache")
    assert (stats.cache_hits, stats.cache_misses) == (1, 2)
    now = time.time()
    stats.on_time_span(EVENTS["backend_compile"], now - 2.0, now,
                       fun_name="jit(f)")
    (at, seconds), = stats.compiles
    assert seconds == pytest.approx(2.0)
    assert at == pytest.approx(time.perf_counter(), abs=0.5)
    assert stats.programs == 1 and not stats.cache_loads
    # the duration beside that time span is the same event: not counted twice
    stats.on_duration(EVENTS["backend_compile"], 2.0, fun_name="jit(f)")
    assert stats.programs == 1
    assert stats.phases["backend_compile"].by_name == {
        "jit(f)": [1, pytest.approx(2.0)]}
    found = stats.between(at - 3.0, at + 1.0)
    assert (found["programs"], found["cache_hits"],
            found["cache_misses"]) == (1, 1, 2)
    assert stats.between(at + 1.0, at + 2.0)["programs"] == 0


def test_without_time_spans_the_durations_serve_without_names():
    """A jax whose ``monitoring`` has no time-span listener: every kind's
    interval is ``[now - seconds, now]`` of its duration."""
    class Older:
        def __init__(self):
            self.heard = []

        def register_event_listener(self, f):
            self.heard.append(f)

        register_event_duration_secs_listener = register_event_listener

    stats, older = _CompileStats(), Older()
    stats.listen(older)
    assert older.heard == [stats.on_event, stats.on_duration]
    t0 = time.perf_counter()
    for kind in EVENTS:
        stats.on_duration(EVENTS[kind], 0.5)
    stats.on_duration("/jax/compilation_cache/compile_time_saved_sec", 9.0)
    for kind in EVENTS:
        (a, b), = stats.phases[kind].intervals
        assert b - a == pytest.approx(0.5) and b == pytest.approx(t0, abs=0.5)
        assert stats.phases[kind].by_name == {}
    assert stats.programs == 1


def test_the_process_listens_once_and_only_through_compile_stats():
    from jax._src import monitoring

    stats = telemetry.compile_stats()
    assert monitoring.get_event_time_span_listeners().count(
        stats.on_time_span) == 1
    assert monitoring.get_event_duration_listeners().count(
        stats.on_duration) == 1
    assert monitoring.get_event_listeners().count(stats.on_event) == 1


# ------------------------------------------------------- nested traces, live
def test_a_nested_trace_counts_once_where_the_durations_count_it_twice():
    @jax.jit
    def setup_timeline_inner(x):
        for _ in range(60):
            x = jnp.tanh(x) * 1.5 + 0.5
        return x

    @jax.jit
    def setup_timeline_outer(x):
        # a new shape a call: every inner call is traced anew
        return sum(setup_timeline_inner(x[:n]).sum() for n in range(1, 13))

    stats = telemetry.compile_stats()
    phase = stats.phases["trace"]
    summed = phase.seconds
    t0 = time.perf_counter()
    setup_timeline_outer.trace(jnp.ones(16))
    t1 = time.perf_counter()
    summed = phase.seconds - summed
    union = stats.seconds("trace", t0, t1)
    assert 0 < union <= t1 - t0
    assert summed > t1 - t0          # the inner traces were counted twice
    names = [name for name, _count, _s in stats.slowest("trace", n=10_000)]
    assert "setup_timeline_outer" in names
    inner = phase.by_name["setup_timeline_inner"]
    assert inner[0] == 12 and inner[1] < phase.by_name[
        "setup_timeline_outer"][1]
    slowest = telemetry.setup_timeline()["slowest"]
    assert set(slowest) == set(EVENTS)
    assert all(len(rows) <= 10 for rows in slowest.values())
    assert slowest["trace"] == stats.slowest("trace")
    assert slowest["trace"] == sorted(slowest["trace"], key=lambda r: -r[2])


# ----------------------------------------------------------- the setup spans
@pytest.fixture
def kept():
    """The spans ``setup_timeline()`` gained during the test."""
    before = len(trace._SETUP_TIMELINE.spans)
    return lambda: telemetry.setup_timeline()["spans"][before:]


def test_a_setup_span_outside_a_step_is_kept_with_its_parent(kept):
    with span("setup/outer", what="a"):
        with span("setup/outer/inner") as inner:
            inner.set(rows=3)
        with span("serve/round"):       # any other layer is kept nowhere
            pass
    inner, outer = kept()
    assert (outer["name"], outer["parent"], outer["what"]) == (
        "setup/outer", None, "a")
    assert (inner["name"], inner["parent"], inner["rows"]) == (
        "setup/outer/inner", "setup/outer", 3)
    assert outer["t0"] <= inner["t0"] <= inner["t1"] <= outer["t1"]
    # copies: a reader cannot reach the store
    inner["name"] = "changed"
    assert kept()[0]["name"] == "setup/outer/inner"


def test_a_setup_span_inside_a_step_is_the_steps_own(kept):
    with step_span("train/step", 7, "train_step") as step:
        with span("setup/load_checkpoint"):
            pass
    assert kept() == []
    assert step.record["phases"]["setup/load_checkpoint"][1] == 1


def test_the_timeline_keeps_the_newest_spans_and_knows_the_process(kept):
    cap = trace._SetupTimeline.KEEP
    assert cap == trace._StepTimeline.KEEP
    told = telemetry.setup_timeline()
    assert set(told) == {"process_t0", "spans", "compile", "slowest"}
    imported, = [s for s in told["spans"] if s["name"] == "setup/import"]
    assert imported["parent"] is None and imported["t1"] > imported["t0"]
    # the process began before it imported the package, and not long before
    assert told["process_t0"] < imported["t0"] < time.perf_counter()
    assert imported["t0"] - told["process_t0"] < 3600
    for kind, phase in told["compile"].items():
        assert set(phase) == {"count", "seconds", "wall_s"}
        assert phase["wall_s"] <= phase["seconds"] + 1e-9
    own = trace._SetupTimeline()
    for i in range(cap + 5):
        own.keep("setup/x", float(i), float(i) + 0.5)
    assert len(own.spans) == cap and own.spans[0]["t0"] == 5.0


# ---------------------------------------------------- an engine's first steps
@pytest.fixture(scope="module")
def run():
    """A tiny engine through ``initialize`` and three ``train_batch`` calls
    -> what the program kept of each stage."""
    stats = telemetry.compile_stats()
    model = GPTNeoX(GPTNeoXConfig.tiny())
    spans_before = len(trace._SETUP_TIMELINE.spans)
    engine, _, _, _ = dst.initialize(model=model, config=CONFIG)
    batch = model.example_batch(batch_size=16, seq_len=32)
    seen = []
    for _ in range(3):
        engine.train_batch(batch=batch)
        seen.append({
            "record": telemetry.step_timeline()[-1],
            "spans": len(trace._SETUP_TIMELINE.spans),
            "intervals": {k: p.count for k, p in stats.phases.items()},
            "told": engine.time_to_first_step})
    return {"engine": engine, "steps": seen,
            "spans": telemetry.setup_timeline()["spans"][spans_before:]}


def test_initialize_and_its_children_are_kept_each_inside_its_parent(run):
    whole, = [s for s in run["spans"] if s["name"] == "setup/initialize"]
    assert whole["parent"] is None
    children = [s for s in run["spans"] if s is not whole]
    assert children and len(children) <= 6
    for child in children:
        assert child["name"].startswith("setup/initialize/")
        assert child["parent"] == "setup/initialize"
        assert whole["t0"] <= child["t0"] <= child["t1"] <= whole["t1"]
    # the stretches do not overlap, and the state's is among them
    ordered = sorted(children, key=lambda s: s["t0"])
    assert all(a["t1"] <= b["t0"] for a, b in zip(ordered, ordered[1:]))
    assert "setup/initialize/state" in [s["name"] for s in children]


def test_the_step_that_compiled_says_what_the_compile_was_made_of(run):
    first = run["steps"][0]["record"]
    assert first["step"] == 0 and first["compiled"]
    made = first["compile"]
    assert set(made) == {"trace_s", "lower_s", "backend_compile_s",
                         "cache_load_s", "programs", "cache_hits",
                         "cache_misses"}
    assert made["trace_s"] > 0 and made["lower_s"] > 0
    assert made["programs"] >= 1
    assert made["cache_hits"] + made["cache_misses"] <= made["programs"]
    dispatch_s, _calls = first["phases"]["train/dispatch"]
    unions = [made[k] for k in ("trace_s", "lower_s", "backend_compile_s",
                                "cache_load_s")]
    assert all(0 <= u <= dispatch_s for u in unions)
    assert sum(unions) <= dispatch_s


def test_a_step_that_compiles_nothing_gets_no_key_and_keeps_nothing(run):
    first, second, third = run["steps"]
    for later in (second, third):
        assert not later["record"]["compiled"]
        assert "compile" not in later["record"]
        assert later["spans"] == first["spans"]
        assert later["intervals"] == first["intervals"]


def test_the_time_to_first_step_is_told_once_by_phase(run):
    first, second, third = run["steps"]
    assert first["told"] is None        # its step compiled
    told = second["told"]
    assert third["told"] is told        # once
    parts = (told["before_import_s"] + told["import_s"]
             + told["initialize_s"] + told["compiled_steps_s"]
             + told["other_s"])
    assert parts == pytest.approx(told["total_s"])
    assert all(told[k] >= 0 for k in ("before_import_s", "import_s",
                                      "initialize_s", "compiled_steps_s",
                                      "other_s"))
    assert told["compiled_steps"] == 1
    assert told["compile"] == first["record"]["compile"]
    assert told["total_s"] == pytest.approx(
        second["record"]["t1"] - telemetry.setup_timeline()["process_t0"])
    assert sum(told["initialize"].values()) <= told["initialize_s"]
    assert list(told["initialize"]) == ["state"]
    line = trace.describe_time_to_first_step(told)
    assert line.startswith("time to first step ")
    for word in ("before import", "import", "initialize", "1 compiled step ",
                 "(state ", "trace", "lower", "cache load", "programs ",
                 "from the cache", "written to it", "other"):
        assert word in line


def test_load_checkpoint_is_a_setup_span(run, tmp_path, kept):
    engine = run["engine"]
    engine.save_checkpoint(str(tmp_path))
    assert kept() == []
    engine.load_checkpoint(str(tmp_path))
    loaded, = kept()
    assert (loaded["name"], loaded["parent"]) == ("setup/load_checkpoint",
                                                  None)


# ------------------------------------------------- on the profiler's timeline
def test_the_setup_spans_reach_the_profilers_timeline(tmp_path):
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        model = GPTNeoX(GPTNeoXConfig.tiny())
        dst.initialize(model=model, config=CONFIG)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path) + "/plugins/profile/*/*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("dst:setup/")}
    kept = {"dst:" + s["name"] for s in telemetry.setup_timeline()["spans"]
            if s["name"].startswith("setup/initialize")}
    assert "dst:setup/initialize" in names and names == kept

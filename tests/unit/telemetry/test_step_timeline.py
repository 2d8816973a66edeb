"""The step timeline (``telemetry/trace.py::step_timeline``): one record a
``train_batch``, kept by the program with no tracer and no profiler on: the
step's number, its clocks (wall, process CPU, thread CPU), its host phases by
wall time, and the model's counters as the device arrays they are.
"""

import os
import threading

import jax
import jax.numpy as jnp
import pytest

import deeperspeed_tpu as dst
from deeperspeed_tpu import telemetry
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.telemetry import trace
from deeperspeed_tpu.telemetry.trace import get_tracer, span, step_span



class Counting(GPTNeoX):
    """A model that counts beside its loss, as a looped or routed one does:
    ``rows`` is the batch's rows, ``twice`` a list."""

    def loss_fn(self):
        plain = super().loss_fn()

        def loss(params, batch, rng=None, **kwargs):
            rows = jnp.float32(batch["input_ids"].shape[0])
            return plain(params, batch, rng, **kwargs), {
                "rows": rows, "twice": jnp.stack([rows, 2 * rows])}

        return loss


def tiny_engine(model_class=GPTNeoX):
    model = model_class(GPTNeoXConfig.tiny())
    engine, _, _, _ = dst.initialize(model=model, config={
        "train_batch_size": 8, "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0}, "bf16": {"enabled": True},
        "steps_per_print": 10 ** 9})
    return engine, model.example_batch(batch_size=8, seq_len=32)


@pytest.fixture
def timeline():
    """An empty timeline for the test (``tests/conftest.py`` empties it
    after every test)."""
    trace._STEP_TIMELINE.clear()
    return trace._STEP_TIMELINE


@pytest.fixture
def ten_steps(timeline, tmp_path, monkeypatch):
    """Ten steps of a tiny engine whose model counts, run from an empty
    directory -> (the engine's step numbers, the records)."""
    monkeypatch.chdir(tmp_path)
    engine, batch = tiny_engine(Counting)
    numbers = []
    for _ in range(10):
        numbers.append(engine.global_steps)
        engine.train_batch(batch=batch)
    return numbers, telemetry.step_timeline()


# ----------------------------------------------------------- a step's record
def test_one_record_a_train_batch_numbered_as_its_annotation(ten_steps):
    numbers, records = ten_steps
    assert [r["step"] for r in records] == numbers == list(range(10))
    assert {r["program"] for r in records} == {"train_step"}
    # the first step compiled its program, the others found it
    assert [r["compiled"] for r in records] == [True] + [False] * 9
    assert not any(r["profiled"] for r in records)


@pytest.mark.parametrize("clock", ["wall", "process_cpu", "thread_cpu"])
def test_clocks_run_forward_and_phases_lie_inside_the_step(ten_steps, clock):
    _, records = ten_steps
    for before, r in zip([None] + records[:-1], records):
        # the throughput timer fences from its third step on, twice a step
        fenced = r["step"] >= 2
        assert {k: p[1] for k, p in r["phases"].items()} == dict(
            {"train/input": 1, "train/dispatch": 1, "train/report": 2},
            **({"train/fence": 2} if fenced else {}))
        if clock == "wall":
            assert r["t1"] > r["t0"]
            assert before is None or r["t0"] >= before["t1"]
            # every phase's wall is inside [t0, t1], and so is their sum:
            # on one thread they follow each other
            assert all(0 < wall <= r["t1"] - r["t0"]
                       for wall, _n in r["phases"].values())
            assert sum(p[0] for p in r["phases"].values()) \
                <= r["t1"] - r["t0"]
        elif clock == "process_cpu":
            assert r["cpu1"] >= r["cpu0"]
            assert before is None or r["cpu0"] >= before["cpu1"]
        else:
            # one thread's time is no more than the process's
            assert 0 <= r["thread_cpu1"] - r["thread_cpu0"] \
                <= r["cpu1"] - r["cpu0"] + 1e-6


def test_nothing_is_read_written_or_switched_on(ten_steps, tmp_path):
    """The acceptance criterion: after ten steps the counters of every record
    are still the device's arrays, no file appeared and the tracer is off."""
    _, records = ten_steps
    for r in records:
        assert set(r["counters"]) == {"rows", "twice"}
        assert all(isinstance(v, jax.Array) for v in r["counters"].values())
    assert os.listdir(tmp_path) == []
    assert not get_tracer().enabled and get_tracer().spans() == []


@pytest.mark.parametrize("asked,want", [(None, list(range(10))),
                                        ([2, 7], [2, 7]), ([99], [])])
def test_read_turns_the_asked_steps_counters_into_numbers(ten_steps, asked,
                                                          want):
    read = telemetry.step_timeline(read=True, steps=asked)
    assert [r["step"] for r in read] == want
    for r in read:
        assert r["counters"] == {"rows": 8.0, "twice": [8.0, 16.0]}
    # the kept records still hold the arrays
    assert all(isinstance(r["counters"]["rows"], jax.Array)
               for r in telemetry.step_timeline(steps=asked))


def test_step_counters_is_the_newest_records(ten_steps):
    assert telemetry.step_counters() == {
        "train_step": {"rows": 8.0, "twice": [8.0, 16.0]}}
    unread = telemetry.step_counters(read=False)["train_step"]
    newest = telemetry.step_timeline()[-1]["counters"]
    assert all(unread[k] is newest[k] for k in newest)
    # a model that counts nothing leaves counters to nobody: the newest
    # record of the program is then its own
    engine, batch = tiny_engine()
    engine.train_batch(batch=batch)
    assert telemetry.step_counters() == {}
    assert telemetry.step_timeline()[-1]["counters"] == {}


def test_counters_published_outside_a_step_get_a_record_of_their_own(timeline):
    trace.publish_step_counters("train_step", {"n": jnp.float32(3)})
    record, = telemetry.step_timeline()
    assert record["step"] is None and record["t0"] is None
    assert telemetry.step_counters() == {"train_step": {"n": 3.0}}
    trace._STEP_COUNTERS.clear()        # the name older tests empty it by
    assert telemetry.step_counters() == {} == dict(timeline.newest)
    assert telemetry.step_timeline() == []


# ------------------------------------------------------------------ the ring
def test_the_ring_holds_1024_steps_and_drops_the_oldest(timeline):
    assert timeline.KEEP == 1024
    for n in range(1030):
        with step_span("train/step", n, "train_step"):
            with span("train/input"):
                pass
    records = telemetry.step_timeline()
    assert len(records) == 1024
    assert [records[0]["step"], records[-1]["step"]] == [6, 1029]
    assert records[0]["phases"]["train/input"][1] == 1


def test_a_span_outside_a_step_or_on_another_thread_lands_in_no_record(
        timeline):
    def elsewhere():
        with span("train/prefetch"):
            pass

    with span("train/input"):           # no step is open: nowhere to land
        pass
    with step_span("train/step", 0, "train_step") as step:
        other = threading.Thread(target=elsewhere)
        other.start()
        other.join(timeout=30)
        assert not other.is_alive()
        with span("train/input"):
            with span("train/prefetch"):    # nested: under its own name too
                pass
        with span("train/input"):
            pass
        assert step.elapsed() > 0
    record, = telemetry.step_timeline()
    assert {k: v[1] for k, v in record["phases"].items()} == {
        "train/input": 2, "train/prefetch": 1}
    assert record["phases"]["train/input"][0] \
        >= record["phases"]["train/prefetch"][0]
    # the record is closed: a later span adds nothing to it
    with span("train/input"):
        pass
    assert telemetry.step_timeline()[0]["phases"]["train/input"][1] == 2


def test_a_step_that_raises_still_leaves_its_record(timeline):
    with pytest.raises(RuntimeError):
        with step_span("train/step", 5, "train_step"):
            with span("train/dispatch"):
                raise RuntimeError("the step failed")
    record, = telemetry.step_timeline()
    assert record["step"] == 5 and record["t1"] >= record["t0"]
    assert record["phases"]["train/dispatch"][1] == 1
    assert trace._THREAD.step is None

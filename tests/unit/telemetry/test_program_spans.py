"""The program's spans and scopes on the profiler's clock
(``telemetry/trace.py::span`` and what goes through it): the primitive off
and on and under a ``jax.profiler`` session, the train step's host phases,
the scopes of its compiled program, the serve round's counters and the
compile counters.
"""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deeperspeed_tpu as dst
from deeperspeed_tpu import telemetry
from deeperspeed_tpu.inference.v2 import InferenceEngineV2
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.telemetry.trace import (Tracer, TraceSessionWatch,
                                             count_kernel_passes, get_tracer,
                                             instruction_scopes, set_tracer,
                                             span)

TRAIN_PHASES = {"dst:train/input", "dst:train/dispatch", "dst:train/fence",
                "dst:train/readback", "dst:train/report"}
#: what the train step's program is read by (PERF.md section 3)
STEP_SCOPES = ("attention", "attention_layout", "mlp", "head_ce", "optimizer",
               "grad_norm_clip")


class Session:
    """A ``jax.profiler`` session -> the ``dst:`` events it collected, as
    (name, start_ns, end_ns, stats)."""

    def __init__(self, directory):
        self.dir = str(directory)

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        return False

    def events(self):
        from jax.profiler import ProfileData

        path, = glob.glob(self.dir + "/plugins/profile/*/*.xplane.pb")
        return sorted(
            ((ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
              {k: str(v) for k, v in ev.stats})
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("dst:")),
            key=lambda e: (e[1], -e[2]))


@pytest.fixture
def ring(tmp_path):
    """An enabled process tracer for the test, the null one after it."""
    before = get_tracer()
    tracer = set_tracer(Tracer(enabled=True, run_dir=str(tmp_path),
                               job_name="t", jsonl=False))
    yield tracer
    set_tracer(before)


# ------------------------------------------------------------- the primitive
def test_span_off_leaves_no_ring_record():
    assert not get_tracer().enabled
    before = get_tracer().span_count
    with span("train/input", rows=4) as s:
        s.set(late=1)
    assert get_tracer().span_count == before and get_tracer().spans() == []
    assert s.trace_id is None and s.span_id is None


def test_span_on_is_one_ring_record_with_name_attributes_and_parent(ring):
    with span("serve/round", dispatch=7) as outer:
        with span("serve/round/plan", rows=3) as inner:
            inner.set(n_pad=4)
    recs = {r["name"]: r for r in ring.spans()}
    assert sorted(recs) == ["serve/round", "serve/round/plan"]
    assert recs["serve/round"]["dispatch"] == 7
    assert recs["serve/round"]["parent_id"] is None
    plan = recs["serve/round/plan"]
    assert plan["rows"] == 3 and plan["n_pad"] == 4
    assert plan["parent_id"] == outer.span_id
    assert plan["trace_id"] == outer.trace_id
    # an explicit parent wins over the enclosing span
    with span("a"):
        with span("b", trace_id="t", parent_id="p"):
            pass
    b, = ring.spans(name="b")
    assert (b["trace_id"], b["parent_id"]) == ("t", "p")


def test_span_is_a_profiler_event_with_its_attributes_as_stats(tmp_path):
    with Session(tmp_path) as session:
        with span("train/fence", who="wall_clock") as s:
            s.set(late=3)
    (name, start, end, stats), = session.events()
    assert name == "dst:train/fence" and end > start
    assert stats["who"] == "wall_clock" and stats["late"] == "3"


def test_tracer_span_and_context_span_go_through_the_primitive(tmp_path, ring):
    ctx = telemetry.TraceContext.root(ring, "request")
    with Session(tmp_path) as session:
        with ring.span("outer") as outer:
            with ctx.span("inner", step=2):
                pass
    assert [e[0] for e in session.events()] == ["dst:outer", "dst:inner"]
    inner, = ring.spans(name="inner")
    assert inner["trace_id"] == ctx.trace_id and inner["step"] == 2
    assert inner["parent_id"] == ctx.span_id != outer.span_id


def test_session_watch_fires_once_after_a_session_that_covered_a_step(tmp_path):
    watch = TraceSessionWatch()
    assert [watch.ended(), watch.ended()] == [False, False]
    with Session(tmp_path):
        assert watch.ended() is False
    assert [watch.ended(), watch.ended()] == [True, False]


# --------------------------------------------------------- the train step
def tiny_engine(**precision):
    model = GPTNeoX(GPTNeoXConfig.tiny())
    engine, _, _, _ = dst.initialize(model=model, config={
        "train_batch_size": 8, "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0}, "gradient_clipping": 1.0,
        "steps_per_print": 10 ** 9, **precision})
    return engine, model.example_batch(batch_size=8, seq_len=32)


def test_train_batch_emits_its_five_phases_inside_the_step(tmp_path):
    engine, batch = tiny_engine(fp16={"enabled": True})
    for _ in range(3):      # the throughput timer fences from its third step
        engine.train_batch(batch=batch)
    telemetry.step_scopes().clear()
    with Session(tmp_path) as session:
        for _ in range(2):
            engine.train_batch(batch=batch)
    # nothing was published while no session had ended ...
    assert telemetry.step_scopes() == {}
    events = session.events()
    steps = [e for e in events if e[0] == "dst:train/step"]
    assert [e[3]["step_num"] for e in steps] == ["3", "4"]
    # the step's record carries that number, and says a session was on
    assert [(r["step"], r["profiled"]) for r in telemetry.step_timeline()
            if r["program"] == "train_step"][-5:] == [
        (0, False), (1, False), (2, False), (3, True), (4, True)]
    for _name, lo, hi, _stats in steps:
        inside = [e for e in events if lo <= e[1] and e[2] <= hi
                  and e[0] != "dst:train/step"]
        assert {e[0] for e in inside} == TRAIN_PHASES
        fences = [e[3]["who"] for e in inside if e[0] == "dst:train/fence"]
        assert fences == ["throughput_timer.start", "throughput_timer.stop"]
    # ... and the step after the session publishes the step program's
    # scopes, from the executable jit already holds: nothing compiles
    compiled = telemetry.compile_stats().programs
    engine.train_batch(batch=batch)
    assert telemetry.compile_stats().programs == compiled
    scopes = telemetry.step_scopes()
    assert list(scopes) == ["jit_train_step"]
    assert all(isinstance(k, str) and isinstance(v, str)
               for k, v in scopes["jit_train_step"].items())
    engine.train_batch(batch=batch)          # once per session
    assert list(telemetry.step_scopes()) == ["jit_train_step"]
    telemetry.step_scopes().clear()


def test_bf16_step_reads_nothing_back(ring):
    """Without fp16 and without the sentinel no device value is read on the
    step path: the phases are the other four."""
    engine, batch = tiny_engine(bf16={"enabled": True})
    for _ in range(3):
        engine.train_batch(batch=batch)
    # (``setup/initialize`` and its children are the engine's making, in
    # the ring like any span, and no step's)
    assert {r["name"] for r in ring.spans() if r["name"] != "compile"
            and not r["name"].startswith("setup/")} == {
        "train/input", "train/dispatch", "train/fence", "train/report"}


_SKIPPED = re.compile(r" = \S+ (parameter|constant|get-tuple-element|tuple|"
                      r"bitcast|broadcast|iota)\(")


def test_step_program_carries_the_scopes_it_is_read_by():
    engine, batch = tiny_engine(bf16={"enabled": True})
    engine.train_batch(batch=batch)
    text = engine._get_train_step(None).lower(
        engine.state, engine._stack_microbatches(batch),
        engine._next_rng()).compile().as_text()
    for scope in STEP_SCOPES:
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)"]', text), scope
    # of the instructions that compute something (reduction bodies are
    # scalar adds no device event shows) under 5 % are under no scope
    scoped = instruction_scopes(text)
    known = set(STEP_SCOPES) | {"embed", "grad_accumulate"}
    counted = bare = 0
    inside = ""
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            inside = head.group(1)
            continue
        made = re.match(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", line)
        if not made or _SKIPPED.search(line) or inside.startswith("region"):
            continue
        counted += 1
        parts = re.findall(r"\w+", scoped.get(made.group(1), ""))
        bare += not known.intersection(parts)
    assert counted > 1000 and bare / counted < 0.05, (bare, counted)


def test_instruction_scopes_inherits_where_the_compiler_left_none():
    text = """HloModule jit_f, entry_computation_layout={()}

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %m = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(f)/mlp/mul"}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %dot.2 = f32[4]{0} add(%a, %a), metadata={op_name="jit(f)/head_ce/add"}
  %convert.3 = f32[4]{0} convert(%dot.2)
  ROOT %copy.4 = f32[4]{0} copy(%a)
}
"""
    assert instruction_scopes(text) == {
        "m": "jit(f)/mlp/mul", "fusion.1": "jit(f)/mlp/mul",
        "dot.2": "jit(f)/head_ce/add", "convert.3": "jit(f)/head_ce/add"}


# ---------------------------------------------------------- the serve round
def _kernel_call(name, op_name):
    """A Pallas kernel's instruction as the TPU compiler prints it (a
    two-layer GPT-NeoX's gradient compiled for a described v5e), its
    operands and the kernel's body cut."""
    return (f'  %{name} = (bf16[2,256,256]{{2,1,0:T(8,128)(2,1)S(1)}}, '
            f'f32[4,2,256]{{2,1,0:T(2,128)S(1)}}) custom-call(%copy.58, '
            f'%copy.59, %copy.60), custom_call_target="tpu_custom_call", '
            f'operand_layout_constraints={{bf16[2,256,256]{{2,1,0}}}}, '
            f'frontend_attributes={{kernel_metadata={{}}}}, '
            f'metadata={{op_name="{op_name}" stack_frame_id=59}}, '
            f'backend_config={{"flag_configs":[]}}')


_ATTENTION = ("attention/attention/jit(flash_attention)/flash_attention/"
              "pallas_call")
_FORWARD = _kernel_call(
    "flash_attention.6", f"jit(loss)/jvp(GPTNeoX)/layers_0/{_ATTENTION}")
_RECOMPUTED = _kernel_call(
    "flash_attention.8", "jit(loss)/transpose(jvp(GPTNeoX))/jvp(GPTNeoX)/"
    f"checkpoint/rematted_computation/layers_1/{_ATTENTION}")
_BACKWARD = _kernel_call(
    "flash_attention.9", "jit(loss)/transpose(jvp(GPTNeoX))/jvp(GPTNeoX)/"
    f"checkpoint/layers_1/{_ATTENTION}")
_NORM = _kernel_call(
    "fused_norm.2", "jit(loss)/jvp(GPTNeoX)/layers_0/attention/"
    "input_layernorm/fused_norm/pallas_call")


def _program(entry, *computations):
    return "\n".join(
        ["HloModule jit_train_step, entry_computation_layout={()}", ""]
        + [text + "\n" for text in computations]
        + ["ENTRY %main.1 (a: f32[4]) -> f32[4] {",
           "  %a = f32[4]{0} parameter(0)", *entry,
           "  ROOT %copy.4 = f32[4]{0} copy(%a)", "}", ""])


# the passes of a scan: its condition counts up to a constant
_SCAN = """%cond.1 (t: (s32[], f32[4])) -> pred[] {
  %constant.7 = s32[]{:T(128)} constant(4)
  %t = (s32[]{:T(128)}, f32[4]{0}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%t), index=0
  ROOT %lt.6 = pred[]{:T(512)} compare(%i, %constant.7), direction=LT
}

%body.1 (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[]{:T(128)}, f32[4]{0}) parameter(0)
BODY
  ROOT %r = (s32[]{:T(128)}, f32[4]{0}) tuple(%t)
}"""
_WHILE = ("  %while.3 = (s32[]{:T(128)}, f32[4]{0}) while(%tuple.2), "
          "condition=%cond.1, body=%body.1")


# a kernel in each branch of a conditional: each counts once
_BRANCH = """%%branch.%d (p: f32[4]) -> f32[4] {
  %%p = f32[4]{0} parameter(0)
%s
  ROOT %%c = f32[4]{0} copy(%%p)
}"""
_CONDITIONAL = ("  %conditional.5 = f32[4]{0} conditional(%i, %a, %a), "
                "branch_computations={%branch.0, %branch.1}")


@pytest.mark.parametrize("text,passes", [
    (_program([_FORWARD]), dict(forward=1, recomputed=0, backward=0)),
    (_program([_RECOMPUTED]), dict(forward=0, recomputed=1, backward=0)),
    (_program([_BACKWARD]), dict(forward=0, recomputed=0, backward=1)),
    # a recomputed layer as it was before the remat wrap saved the kernel's
    # residuals, and as it is
    (_program([_FORWARD, _RECOMPUTED, _BACKWARD]),
     dict(forward=1, recomputed=1, backward=1)),
    (_program([_FORWARD, _FORWARD, _BACKWARD, _BACKWARD]),
     dict(forward=2, recomputed=0, backward=2)),
    # two layers in the body of a scan over four passes
    (_program([_WHILE], _SCAN.replace(
        "BODY", "\n".join([_RECOMPUTED, _BACKWARD] * 2))),
     dict(forward=0, recomputed=8, backward=8)),
    (_program([_CONDITIONAL], _BRANCH % (0, _FORWARD), _BRANCH % (1, _FORWARD)),
     dict(forward=2, recomputed=0, backward=0)),
], ids=["forward", "recomputed", "backward", "layer_recomputed", "two_layers",
        "scan", "conditional"])
def test_kernel_passes_from_a_compiled_programs_text(text, passes):
    assert count_kernel_passes(text) == {"flash_attention": passes}


def test_kernel_passes_are_by_kernel_and_published_by_the_step():
    text = _program([_NORM, _FORWARD, _BACKWARD])
    assert count_kernel_passes(text) == {
        "fused_norm": dict(forward=1, recomputed=0, backward=0),
        "flash_attention": dict(forward=1, recomputed=0, backward=1)}
    assert count_kernel_passes(_program([])) == {}
    from deeperspeed_tpu.telemetry.trace import publish_kernel_passes
    publish_kernel_passes(text)
    assert telemetry.kernel_passes() == count_kernel_passes(text)
    publish_kernel_passes(_program([]))    # the step program published last
    assert telemetry.kernel_passes() == {}


def test_round_stats_against_a_hand_counted_schedule(ring):
    engine = InferenceEngineV2(
        GPTNeoX(GPTNeoXConfig.tiny(max_seq_len=64)),
        config={"dtype": "float32",
                "kv_cache": {"num_blocks": 64, "block_size": 8},
                "state_manager": {"max_context": 64, "max_decode_batch": 4}})
    assert engine.round_stats()["rounds"] == 0
    prompts = {1: np.arange(13), 2: np.arange(5), 3: np.arange(20)}
    # round 1: two prompts, 13 + 5 tokens -> 2 rows x 16
    engine.put_round([1, 2], [prompts[1], prompts[2]])
    # round 2: one decode each and a third prompt of 20 -> 4 rows x 32
    engine.put_round([1, 2, 3], [[7], [9], prompts[3]])
    # round 3: three decodes -> 4 rows x 1
    engine.put_round([1, 2, 3], [[1], [2], [3]])
    assert engine.round_stats() == {
        "rounds": 3, "fed_tokens": 18 + 22 + 3,
        "padded_tokens": 2 * 16 + 4 * 32 + 4 * 1, "decode_rows": 2 + 3,
        "rounds_by_bucket": {(2, 16, 1): 1, (4, 32, 1): 1, (4, 1, 1): 1},
        "step_programs_built": 3}
    rounds = ring.spans(name="serve/round")
    assert [r["dispatch"] for r in rounds] == [0, 1, 2]
    assert [(r["n_pad"], r["s_pad"], r["n_tokens"], r["decodes"])
            for r in rounds] == [(2, 16, 18, 0), (4, 32, 22, 2), (4, 1, 3, 3)]
    children = [r["name"] for r in ring.spans()
                if r["parent_id"] == rounds[0]["span_id"]]
    assert children == ["serve/round/plan", "serve/round/upload",
                        "serve/round/dispatch", "serve/round/harvest",
                        "serve/round/commit"]
    assert ring.spans(name="engine_round") == []


# ------------------------------------------------------- the compile counters
def test_compile_stats_counts_programs_not_calls(ring):
    f = jax.jit(lambda x: jnp.tanh(x) * 3.0 + x.shape[0])
    a, b = np.ones(7, np.float32), np.ones(11, np.float32)
    stats = telemetry.compile_stats()
    start = stats.programs
    f(a), f(a)
    assert stats.programs - start == 1
    f(b), f(a)
    assert stats.programs - start == 2
    at, seconds = stats.compiles[-1]
    assert seconds > 0 and at > 0
    compiled = ring.spans(name="compile")
    assert len(compiled) == 2
    assert {r["kind"] for r in compiled} == {"backend_compile"}
    assert all(r["fun_name"].startswith("jit(") and not r["from_cache"]
               for r in compiled)

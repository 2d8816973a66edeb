"""What the readers of the program's step timeline share.

The program keeps one record a train step whether or not a profiler is on
(``deeperspeed_tpu/telemetry/trace.py::step_timeline``): the step's number,
``time.perf_counter()``, ``time.process_time()`` and ``time.thread_time()``
where it opened and closed, the wall seconds of every ``dst:train/<phase>``
span closed inside it, whether a profiler session was on, and the model's
counters of that step.  The readers run in the measuring process after the
window, so they ask the program for what it kept: the records inside the
window, apart by ``profiled``.  Every per-layer metric before these reads a
profiler slice; these read the forty-odd seconds beside it, where a process
shows which of its two speeds it drew (PERF.md section 7).  Against a program
that keeps no timeline every reader gets ``None``.

Wall time is a median over the steps and CPU time a MEAN.  The chip
machine's CPU clocks tick every 10 ms (twenty values in 200 ms; my chip runs,
PR 42): a step of 27 ms of CPU reads 20 or 30, so its median is one of the
two and its mean over the window's steps is the window's CPU time a step.
For the same reason there is no CPU time by phase: a read there updates
what accrued up to 10 ms before it, so a phase of 3 ms is charged its
neighbours' (``train/dispatch`` read 10 ms of CPU in 3 ms of wall), and it
costs 6-18 us of the host's path between two steps (PERF.md section 6).
"""

import functools

from benchmarks import core, program_trace, trace_reduce

PROGRAM = "train_step"
#: fewer steps of a kind than this give no number
FEWEST = 3
#: the spans a phase's wall time is the sum of (``train/readback`` is a value
#: read for the report); ``train/fence`` is none: a fence's wall is the
#: device's step, which ``train.step_ms`` reads
PHASES = {"input": ("train/input",), "dispatch": ("train/dispatch",),
          "report": ("train/report", "train/readback")}


def program_timeline(read=False, steps=None):
    """The program's step records, oldest first, or None from a program
    that keeps none."""
    try:
        from deeperspeed_tpu import telemetry
    except ImportError:
        return None
    kept = getattr(telemetry, "step_timeline", None)
    return kept(read=read, steps=steps) if kept is not None else None


def window_steps(record):
    """The window's steps as the program timed them -> [{"profiled",
    "wall_ms", "cpu_ms", "thread_cpu_ms", "host_ms": {phase: ms}}], one for
    each record of the step program inside ``[record["t0"], record["t1"]]``
    whose next step is inside it too: a step runs from its ``t0`` to the next
    step's, so that the caller's code between two ``train_batch`` calls is in
    it.  ``cpu_ms`` is the whole process's over that stretch,
    ``thread_cpu_ms`` the step's own thread's inside ``train_batch``,
    ``host_ms`` the wall inside each phase's spans and, under ``outside``,
    between the step's end and the next one's start.  None without a
    window's record or without a timeline."""
    if "t0" not in record or "t1" not in record:
        return None
    kept = program_timeline()
    if kept is None:
        return None
    inside = [r for r in kept if r["program"] == PROGRAM
              and r["t0"] is not None and record["t0"] <= r["t0"]
              and r["t1"] <= record["t1"]]
    steps = []
    for a, b in zip(inside[:-1], inside[1:]):
        if b["step"] != a["step"] + 1:
            continue
        host_ms = {phase: 1e3 * sum(a["phases"].get(s, (0.0,))[0]
                                    for s in spans)
                   for phase, spans in PHASES.items()}
        host_ms["outside"] = 1e3 * (b["t0"] - a["t1"])
        steps.append({
            "profiled": bool(a["profiled"]),
            "wall_ms": 1e3 * (b["t0"] - a["t0"]),
            "cpu_ms": 1e3 * (b["cpu0"] - a["cpu0"]),
            "thread_cpu_ms": 1e3 * (a["thread_cpu1"] - a["thread_cpu0"]),
            "host_ms": host_ms})
    return steps


def over_steps(record, value, reduce, profiled=False):
    """``reduce`` (``core.median``, ``mean``) of ``value(step)`` over the
    window's steps of one kind (without a profiler session unless
    ``profiled``); None with fewer than ``FEWEST`` of them."""
    steps = window_steps(record)
    chosen = [value(s) for s in steps or () if s["profiled"] == profiled]
    return reduce(chosen) if len(chosen) >= FEWEST else None


def mean(values):
    return sum(values) / len(values)


def host_cpu_ms(record, which):
    """CPU ms a step outside a profiler session, the mean over the window's
    unprofiled steps: ``step`` is the whole process's from a step's start to
    the next one's; ``outside`` is that less the step's own thread's inside
    ``train_batch``: every other thread of the process (the runtime's) and
    the caller between two ``train_batch`` calls."""
    if which == "step":
        return over_steps(record, lambda s: s["cpu_ms"], mean)
    return over_steps(record, lambda s: s["cpu_ms"] - s["thread_cpu_ms"],
                      mean)


def host_ms(record, phase):
    """Wall ms a step the host spent in a phase of its own (a key of
    ``PHASES``, or ``outside``: between two ``train_batch`` calls), the
    median over the window's unprofiled steps.  Under two fences a step the
    device waits through every one of them."""
    return over_steps(record, lambda s: s["host_ms"][phase], core.median)


def unprofiled_less_profiled_ms(record):
    """Median step ms (start to next start) outside the profiler's slice
    less that inside it: about nothing in a run at the fast speed, the
    distance between the two speeds in a slow one, which is fast exactly
    while the profiler is on.  The slice's last step holds the session's
    end; the median leaves it out."""
    outside = over_steps(record, lambda s: s["wall_ms"], core.median)
    inside = over_steps(record, lambda s: s["wall_ms"], core.median,
                        profiled=True)
    if outside is None or inside is None:
        return None
    return outside - inside


# ------------------------------------------------------ the slice's own steps
@functools.lru_cache(maxsize=1)
def slice_rows(directory=program_trace.TRACE_DIR):
    """The run's own trace as ``program_trace.read_rows`` gives it, read once
    (``ProgramTrace`` counts the step annotations and drops them, and with
    them their ``step_num``), or None where there is no trace to read.  The
    readers run before ``run.py`` removes the directory."""
    try:
        path = trace_reduce.find_xplane(directory)
    except FileNotFoundError:
        return None
    return program_trace.read_rows(path, {})


def kernel_ns_by_step(rows, kernel):
    """-> {step_num: device ns of the events named ``<kernel>.<n>`` that
    started inside that step's ``dst:train/step`` annotation}.  The engine
    fences the device at the head and the tail of every step, so a step's
    operations lie inside its annotation on the trace's one clock."""
    steps = [(h[1], h[1] + h[2], int(h[3]["step_num"]))
             for h in rows["host"]
             if h[0] == program_trace.ProgramTrace.STEP
             and "step_num" in h[3]]
    out = {number: 0 for _, _, number in steps}
    for op in rows["ops"]:
        if trace_reduce.instruction_kind(op[0]) != kernel:
            continue
        for start, end, number in steps:
            if start <= op[1] < end:
                out[number] += op[2]
                break
    return out

"""What the readers of the program's set-up timeline share.

``setup_s`` is the harness's clock from the command's start to the window's
``t0``, one number from outside.  The program times the same stretch from
the inside (``deeperspeed_tpu/telemetry/trace.py``): ``setup_timeline()``
keeps the ``dst:setup/*`` spans closed outside a step (``setup/import``: the
package's own import; ``setup/initialize``: ``dst.initialize`` whole) and the
process's start on ``time.perf_counter()``; ``step_timeline()`` keeps the
steps run before the window (the first, which compiles, and the warm-up);
``compile_stats().seconds(kind, t0, t1)`` gives the wall time of a compile
phase as the UNION of its intervals, so that a trace nested in a trace counts
once.  The readers run in the measuring process after the window and ask the
program for what it kept of ``[process start, record["t0"]]``; against a
program without ``setup_timeline`` every one gets ``None``.

Two cuts through the same seconds.  ``import_s + initialize_s +
first_steps_s + outside_program_s`` is that stretch whole (the last is what
is left: Python's and jax's start, ``jax.devices()``, the runner's seeded
weights and its host copy for the check).  The four compile phases lie
INSIDE ``initialize_s``, ``first_steps_s`` and (what the harness jits of its
own) ``outside_program_s``, and are added to nothing.  A ``prime`` child's
life (a checkout's first run) ended before this process began and is in none
of them; nor is the supervising process's own start, which ``setup_s`` has.
"""

from benchmarks.layer_metrics import _step_timeline

IMPORT, INITIALIZE = "setup/import", "setup/initialize"


def program_setup():
    """The program's ``(setup_timeline(), compile_stats())``, or None from a
    program that keeps no set-up timeline."""
    try:
        from deeperspeed_tpu import telemetry
    except ImportError:
        return None
    kept = getattr(telemetry, "setup_timeline", None)
    return (kept(), telemetry.compile_stats()) if kept is not None else None


def stretch(record):
    """-> (process start, the window's opening, the set-up timeline, the
    compile statistics), or None without a window's record, without a
    timeline or where the process's start cannot be had."""
    if "t0" not in record:
        return None
    kept = program_setup()
    if kept is None or kept[0]["process_t0"] is None:
        return None
    return kept[0]["process_t0"], record["t0"], kept[0], kept[1]


def covered(intervals, t0, t1):
    """Seconds of ``[t0, t1]`` inside ``intervals`` (which do not overlap)."""
    return sum(max(0.0, min(b, t1) - max(a, t0)) for a, b in intervals)


def _span_s(found, name):
    t0, t1, timeline, _ = found
    return covered([(s["t0"], s["t1"]) for s in timeline["spans"]
                    if s["name"] == name and s["parent"] is None], t0, t1)


def _first_steps_s(found):
    steps = _step_timeline.program_timeline()
    if steps is None:
        return None
    t0, t1 = found[:2]
    return covered([(r["t0"], r["t1"]) for r in steps
                    if r["t0"] is not None and r["t1"] <= t1], t0, t1)


def span_s(record, name):
    """Seconds of set-up inside the top-level ``setup/`` spans of a name."""
    found = stretch(record)
    return None if found is None else _span_s(found, name)


def first_steps_s(record):
    """Wall seconds of the step records closed before the window opened:
    the first step, whose dispatch traces, lowers and loads or compiles the
    step program, and the warm-up step."""
    found = stretch(record)
    return None if found is None else _first_steps_s(found)


def compile_s(record, kind):
    """Wall seconds of set-up inside a compile phase (``trace``, ``lower``,
    ``backend_compile`` less the cache loads inside it, ``cache_load``): a
    union of intervals."""
    found = stretch(record)
    if found is None:
        return None
    t0, t1, _, stats = found
    return stats.seconds(kind, t0, t1)


def outside_program_s(record):
    """The stretch less the program's three parts."""
    found = stretch(record)
    if found is None:
        return None
    parts = [_span_s(found, IMPORT), _span_s(found, INITIALIZE),
             _first_steps_s(found)]
    if None in parts:
        return None
    return found[1] - found[0] - sum(parts)

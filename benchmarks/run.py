"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` and everything else by name
(``core.find_cell``), has the cell's runner set up (weights from the seed,
warm-up of every shape: all of it counted as ``setup_s``), measures a window
of ``--seconds`` seconds in which nothing may compile, compares the
program's outputs with the plain reference, and prints progress lines and
then ONE JSON object as the last line of its output: with ``--trace 0`` the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from the run's record and from a profiler trace of a slice of the window.

The command itself never touches JAX: it runs the cell in a child process
(``supervise``).  A child whose set-up had to compile and write programs to
the persistent cache stops before its window and a second child, which
finds them there, measures: a window is always measured by a process that
loaded its programs, as every later run of the cell is (of seven runs that
compiled in their own process two read 4 % low, PERF.md section 6).

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.  ``--rehearse`` is the CPU rehearsal: the tiny preset
through the same code, counts only, no metric of time.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import ctypes  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import core  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")
#: exit code of a child that compiled its programs and left the window to
#: the next child (sysexits' EX_TEMPFAIL)
PRIMED = 75


class SliceTracer:
    """Profiles a slice at the start of the window: started by the runner's
    first ``tick()``, stopped by the first ``tick()`` after ``seconds``."""

    def __init__(self, enabled, seconds, directory=TRACE_DIR):
        self.enabled, self.seconds, self.dir = enabled, seconds, directory
        self.t_start = self.t_stop = None

    def tick(self):
        if not self.enabled or self.t_stop is not None:
            return
        import jax

        now = time.perf_counter()
        if self.t_start is None:
            shutil.rmtree(self.dir, ignore_errors=True)
            # no Python call tracing: it slows the host loop it would watch;
            # the harness's own TraceAnnotations are host TraceMe events
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.t_start = time.perf_counter()
        elif now - self.t_start >= self.seconds:
            self.stop()

    def stop(self):
        if not self.enabled or self.t_start is None or self.t_stop is not None:
            return
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()


class Heartbeat:
    """A thread that wakes every 50 ms and keeps the instants.  Its longest
    silence inside the window tells a stall of the whole process or machine
    (the silence is as long as the stall) from a wait on the device (the main
    thread waits without the interpreter lock, the heartbeat goes on)."""

    def __init__(self, period=0.05):
        self.period, self.beats = period, []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.wait(self.period):
            self.beats.append(time.perf_counter())

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        return False

    def silences(self, t0, t1):
        beats = [t0] + [b for b in self.beats if t0 < b < t1] + [t1]
        return [b - a for a, b in zip(beats[:-1], beats[1:])]

    def longest_silence(self, t0, t1):
        return max(self.silences(t0, t1))


def cpu_seconds():
    """Processor time this process has used so far (user, system).  Over a
    window it tells a run whose host worked more (a GIL-holding pause, the
    profiler) from one that waited on the device."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return {"cpu_user_s": ru.ru_utime, "cpu_sys_s": ru.ru_stime}


class Context:
    def __init__(self, args, cell, config, traffic):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds = args.seed, args.seconds
        self.rehearse = args.rehearse
        self.spans = core.Spans()
        self.compiles = core.CompileCounter()
        self.trace = SliceTracer(bool(args.trace) and not args.rehearse,
                                 float(traffic.get("trace_seconds", 3.0)))

    def log(self, what, **fields):
        """A progress line; ``at`` is seconds since the process started."""
        at = round(time.perf_counter() - T_PROCESS_START, 2)
        print(json.dumps({"progress": what, "at": at, **fields}), flush=True)


def device_facts():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips):
    """Peak on the fullest chip, from the runtime's own counters, read when
    the window closes.  ``peak_bytes_in_use`` counts live arrays and leaves
    out what a running program holds in temporaries (it reads 16 bytes a
    parameter in training, to the byte); those sit in ``peak_bytes_reserved``
    (equal to the step program's ``memory_analysis()`` temporaries).  So the
    peak is the arrays in use beside the reservation, or the arrays' own
    peak where that is larger."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peaks.append(max(st.get("peak_bytes_in_use", 0),
                         st.get("bytes_in_use", 0)
                         + st.get("peak_bytes_reserved", 0)))
    return int(max(peaks))


def enable_cache():
    """The program's own choice of compile cache (``JAX_COMPILATION_CACHE_DIR``
    if set, else ``.jax_cache`` in the checkout) -> its directory."""
    import jax

    from deeperspeed_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # a cell's programs stay resident whatever cap the machine sets: under
    # LRU eviction a run that writes more than the cap never hits again
    jax.config.update("jax_compilation_cache_max_size", -1)
    return cache_dir


def rehearsal_overrides(traffic, bench_dir=core.BENCH_DIR):
    """The tiny preset for a CPU rehearsal: the mix's own ``rehearsal``
    block over its parameters, and the tiny configuration it names ->
    (configuration, traffic)."""
    small = dict(traffic, **traffic.get("rehearsal", {}))
    tiny = core.load_json(os.path.join(
        bench_dir, "configs", small.get("config", "tiny-rehearsal") + ".json"))
    return tiny, small


def run_child(command):
    """One child process to its end -> its exit code.  The child is told to
    die with this process, and is ended and waited for on every way out."""
    def die_with_parent():
        ctypes.CDLL(None).prctl(1, signal.SIGKILL)  # PR_SET_PDEATHSIG

    def ended(signum, _frame):
        sys.exit(128 + signum)

    previous = signal.signal(signal.SIGTERM, ended)
    child = subprocess.Popen(command, preexec_fn=die_with_parent)
    try:
        rc = child.wait()
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        signal.signal(signal.SIGTERM, previous)
    return rc if rc >= 0 else 128 - rc


def supervise(argv, spawn=run_child):
    """The cell in a child process, so that this one never holds the chip:
    a ``prime`` child, which measures unless its set-up wrote programs to
    the compile cache, and after one that did a ``measure`` child.
    ``setup_s`` counts from this process's start either way."""
    started = time.time() - (time.perf_counter() - T_PROCESS_START)
    for stage in ("prime", "measure"):
        rc = spawn([sys.executable, os.path.abspath(__file__), *argv,
                    "--stage", stage, "--started", repr(started)])
        if rc != PRIMED:
            break
    return rc


def main(argv=None):
    global T_PROCESS_START

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the tiny preset: counts only")
    ap.add_argument("--stage", choices=("prime", "measure"),
                    help="set by the command itself for its child processes")
    ap.add_argument("--started", type=float,
                    help="time.time() at which the command started")
    args = ap.parse_args(argv)
    if args.stage is None:
        return supervise(sys.argv[1:] if argv is None else list(argv))
    if args.started is not None:
        T_PROCESS_START = time.perf_counter() - (time.time() - args.started)

    manifest = core.load_manifest()
    cell, config, traffic = core.find_cell(manifest, args.workload)
    if args.rehearse:
        config, traffic = rehearsal_overrides(traffic)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax

    dev = device_facts()
    if not args.rehearse and (dev["platform"] != "tpu"
                              or dev["count"] < cell["chips"]):
        print(json.dumps({"progress": "device", "ok": False,
                          "wanted": f"{cell['chips']} x tpu", **dev}),
              file=sys.stderr, flush=True)
        return 1
    if args.rehearse and dev["platform"] == "tpu":
        print("--rehearse is the CPU path; run the cell itself on a TPU",
              file=sys.stderr)
        return 1

    cache_dir = enable_cache()
    ctx = Context(args, cell, config, traffic)
    ctx.compiles.install()
    ctx.log("device", compile_cache=cache_dir, jax=jax.__version__,
            workload=args.workload, seed=args.seed, **dev)
    runner = core.load_runner(traffic["runner"])

    state = runner.setup(ctx)
    setup_compiles = ctx.compiles.count
    ctx.log("warm", seconds=time.perf_counter() - T_PROCESS_START,
            compiles=setup_compiles)

    if args.stage == "prime" and ctx.compiles.cache_writes:
        ctx.log("primed", programs_written=ctx.compiles.cache_writes)
        return PRIMED

    # the runner opens the window itself: set-up runs until it does
    before = cpu_seconds()
    with Heartbeat() as heartbeat:
        record = runner.window(ctx, state)
    ctx.trace.stop()
    spent = {k: v - before[k] for k, v in cpu_seconds().items()}
    setup_s = record["t0"] - T_PROCESS_START
    window_compiles = ctx.compiles.between(record["t0"], record["t1"])
    record["device_kind"] = dev["kind"]
    record["model_config"] = config
    record["spans"] = ctx.spans

    memory_peak = memory_peak_bytes(cell["chips"])
    silences = heartbeat.silences(record["t0"], record["t1"])
    if not args.rehearse:
        ctx.log("host", longest_silence_ms=1e3 * max(silences),
                silences_over_250ms=sum(1 for s in silences if s > 0.25),
                **spent)
    ctx.log("memory", memory_peak_bytes=memory_peak,
            **{k: v for k, v in (jax.devices()[0].memory_stats() or {}).items()
               if k.startswith(("bytes_", "peak_bytes_"))})
    checks = runner.check(ctx, state, record)
    checks.append(core.check("compiles_in_window", window_compiles, 0))
    for c in checks:
        print(json.dumps(c), flush=True)
    correct = all(c["ok"] for c in checks)

    device = dict(dev, count=cell["chips"] if not args.rehearse
                  else dev["count"],
                  memory_peak_bytes=memory_peak)
    result = {"correct": correct, "attempted": int(record["attempted"]),
              "failed": int(record["failed"]), "metrics": {}, "device": device}
    if args.rehearse:
        # counts only: a CPU run is never written under a device metric
        result["rehearsal"] = True
        result["counts"] = record.get("counts", {})
    elif not args.trace:
        values = dict(record["end_to_end"], setup_s=setup_s)
        for m in core.metrics_for(manifest, args.workload, "end_to_end"):
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        from benchmarks import trace_reduce

        trace = trace_reduce.reduce_dir(TRACE_DIR, chips=cell["chips"])
        ctx.log("trace", **trace.summary())
        for m in core.metrics_for(manifest, args.workload, "per_layer"):
            value = core.layer_metric_reader(m["name"]).compute(record, trace)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        device["busy_s"], device["window_s"] = trace.busy_s, trace.window_s
        result["breakdown"] = trace.breakdown()
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

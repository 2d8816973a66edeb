"""What every runner and metric reader shares: finding a cell's files by
name, the arithmetic of the yardstick (percentiles, model FLOPs, MFU), the
table of peaks, the compile counter and the host spans.

Nothing here knows a workload by name: a cell is whatever ``BENCHMARK.json``
says it is, and everything belonging to one configuration, traffic mix or
per-layer metric sits in a file of its own found by that name.
"""

import importlib.util
import json
import math
import os
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


# ------------------------------------------------------------- the manifest
def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_manifest(root=ROOT):
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(manifest, workload, root=ROOT):
    """-> (cell entry, configuration dict, traffic dict) for a workload
    name.  The configuration's file is named in the manifest; the traffic
    mix is ``<benchmark dir>/traffic/<traffic>.json``."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"there are {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    bench_dir = os.path.dirname(os.path.join(root, manifest["command"][1]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic


def load_module(path):
    """Import a python file by path (metric readers have dots in their
    names, so they are not importable as modules by name)."""
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_").replace(
        "-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_runner(name, bench_dir=BENCH_DIR):
    return load_module(os.path.join(bench_dir, "runners", name + ".py"))


def load_kernel_cost(name, bench_dir=BENCH_DIR):
    return load_module(os.path.join(bench_dir, "kernel_costs", name + ".py"))


def limits_path(workload, rehearse=False, bench_dir=BENCH_DIR):
    """Where a cell's own limits of the output comparison are kept; a CPU
    rehearsal at the tiny preset has one file of its own."""
    return os.path.join(bench_dir, "limits",
                        ("rehearsal" if rehearse else workload) + ".json")


def load_limits(workload, rehearse=False, bench_dir=BENCH_DIR):
    return load_json(limits_path(workload, rehearse, bench_dir))


def metrics_for(manifest, workload, kind):
    """The ``end_to_end`` or ``per_layer`` entries a cell reports.  An entry
    with a ``workloads`` key belongs to the cells it lists.  Without one, an
    end-to-end metric belongs to every cell, and a per-layer metric to every
    cell that reports the end-to-end metric it ``moves``."""
    def listed(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in manifest["end_to_end"] if listed(m)]
    if kind == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def layer_metric_reader(metric_name, bench_dir=BENCH_DIR):
    return load_module(os.path.join(bench_dir, "layer_metrics",
                                    metric_name + ".py"))


# ------------------------------------------------------------------- peaks
def device_peaks(kind, bench_dir=BENCH_DIR):
    """Published peaks of one chip by ``device_kind``; unknown is an error."""
    table = load_json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in benchmarks/peaks.json "
                       f"({sorted(table)}); add it with its source")
    return table[kind]


# -------------------------------------------------------------- arithmetic
def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default), on a plain list."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values):
    return percentile(values, 50.0)


def model_flops_per_token(n_params_no_embed, num_layers, hidden, seq_len):
    """Forward + backward FLOPs one trained token needs: ``6 N`` for the
    matmuls (input embedding excluded: it is a gather) plus the attention
    scores and values, ``12 L H S``.  The attention term is the customary
    full-square count (PaLM appendix B), not halved for causality.
    Recomputed operations do not count."""
    return 6 * n_params_no_embed + 12 * num_layers * hidden * seq_len


def mfu_pct(flops_per_token, tokens_per_s, chips, peak_flops_per_chip):
    return 100.0 * flops_per_token * tokens_per_s / (chips * peak_flops_per_chip)


def roofline_pct(flops, bytes_moved, seconds, peak_flops, peak_bytes_per_s):
    """Share of the roofline a kernel reached: the least time the chip could
    take (the larger of operations over peak FLOP/s and bytes over peak
    bytes/s) over the time it took.  -> (percent, which bound applies)."""
    t_flops = flops / peak_flops
    t_bytes = bytes_moved / peak_bytes_per_s
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound


# --------------------------------------------------------- compile counter
class CompileCounter:
    """Counts programs handed to the backend compiler, through
    ``jax.monitoring`` (as ``chip_smoke.py`` counts them), and keeps the
    instant each was done, so that a window can be asked for its own.
    ``cache_writes`` counts those that took long enough to be written to the
    persistent cache: a later process loads them instead of compiling."""

    def __init__(self):
        self.done_at = []
        self.cache_writes = 0

    def install(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_cache_event)
        return self

    def _on_cache_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def _on_event(self, event, _seconds, **_kw):
        if event.endswith("backend_compile_duration"):
            self.done_at.append(time.perf_counter())

    @property
    def count(self):
        return len(self.done_at)

    def between(self, t0, t1):
        return sum(1 for t in self.done_at if t0 <= t <= t1)


# -------------------------------------------------------------- host spans
class Spans:
    """Host spans kept in memory: (name, start, end) on ``perf_counter``.
    Each is also a ``jax.profiler.TraceAnnotation`` named ``bench:<name>``,
    so that a device trace carries them on its own clock."""

    def __init__(self):
        self.records = []

    def span(self, name):
        return _Span(self, name)

    def seconds_by_name(self, t0, t1):
        """How much of [t0, t1] each span name covers."""
        out = {}
        for name, start, end in self.records:
            cover = min(end, t1) - max(start, t0)
            if cover > 0:
                out[name] = out.get(name, 0.0) + cover
        return out


class _Span:
    def __init__(self, owner, name):
        self.owner, self.name = owner, name

    def __enter__(self):
        import jax

        self.annotation = jax.profiler.TraceAnnotation("bench:" + self.name)
        self.annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        self.annotation.__exit__(*exc)
        self.owner.records.append((self.name, self.start, end))
        return False


def check(name, value, limit, ok=None, better="lower"):
    """One line of the output comparison: the number beside its limit."""
    if ok is None:
        ok = value <= limit if better == "lower" else value >= limit
    return {"check": name, "value": value, "limit": limit, "ok": bool(ok)}

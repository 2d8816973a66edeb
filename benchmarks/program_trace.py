"""What the program itself says of a traced run: device time by the
``jax.named_scope`` each operation was traced under, and each idle gap of
the device cut along the program's own host spans.

The program (``deeperspeed_tpu/telemetry/trace.py``) wraps its host phases in
``jax.profiler.TraceAnnotation``s named ``dst:<layer>/<phase>`` and its
model parts in named scopes (PERF.md section 3 lists both).  A v5e trace
names a device event by its instruction's HLO text and carries no scope
(looked at by hand, PR 27: no ``metadata=`` in the name, no ``op_name``
stat), so the program publishes, per step program, the scope of every
instruction (``telemetry.step_scopes()``) once a profiler session that
covered a step has ended.  This module reads the run's own ``.xplane.pb`` a
second time -- ``trace_reduce.read_xplane`` keeps short names and the
harness's ``bench:`` spans only -- and joins the two.  Against a program
that has no such spans or registry every reader gets ``None``.
"""

import bisect
import functools
import json
import os
import re
import time

from benchmarks import core, trace_reduce

TRACE_DIR = os.path.join(core.ROOT, ".bench_out", "trace")
PROGRAM_PREFIX = "dst:"
HARNESS_PREFIX = trace_reduce.HOST_SPAN_PREFIX
#: where an idle stretch goes that no span of the program covers: the
#: caller's code between two calls into the engine
OUTSIDE = "outside"
#: every scope the program names (PERF.md section 3); device time under none
#: of them is ``unattributed``
SCOPES = frozenset((
    "embed", "attention", "attention_layout", "mlp", "head_ce",
    "grad_accumulate", "grad_norm_clip", "optimizer", "zero3_gather",
    "zero3_reduce", "flash_attention", "fused_norm", "prefill_gather",
    "kv_scatter", "sample", "paged_decode_attention",
    "paged_spec_decode_attention", "sorted_topk"))
_WRAPPED = re.compile(
    r"^(?:transpose|jvp|vmap|remat|checkpoint|custom_jvp|custom_vjp)"
    r"\((.*)\)$")


def scopes_of(op_name):
    """``jit(train_step)/transpose(jvp(GPTNeoX))/layers_0/attention/mul`` ->
    ("attention",): the program's scopes on an instruction's path, outermost
    first.  A component counts whole, or inside the wrappers differentiation
    puts around it; ``jit(flash_attention)`` is a function's name, not a
    scope."""
    found = []
    for part in op_name.split("/"):
        while (inner := _WRAPPED.match(part)) is not None:
            part = inner.group(1)
        if part in SCOPES:
            found.append(part)
    return tuple(found)


def instruction_name(raw):
    """``%fusion.6 = bf16[8,2048]{...} fusion(...)`` -> ``fusion.6``."""
    return raw.partition(" = ")[0].strip().lstrip("%")


def program_name(module_event):
    """``jit_train_step(15941212418636761065)`` -> ``jit_train_step``."""
    return module_event.partition("(")[0]


# ------------------------------------------------------------ reading a trace
def read_rows(path, scopes):
    """The first chip's operations with the program each ran in, the host's
    ``dst:`` and ``bench:`` spans with their stats, and the scopes the
    program published -> plain rows (the tests' fixture is such a dict):
    ``ops`` [instruction, start_ns, dur_ns, program], ``host`` [name,
    start_ns, dur_ns, stats], ``scopes`` {program: {instruction: op_name}}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    device = min((p.name for p in data.planes
                  if p.name.startswith(trace_reduce.DEVICE_PLANE_PREFIX)),
                 default=None)
    for plane in data.planes:
        if plane.name == device:
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    ops = [[instruction_name(ev.name), int(ev.start_ns),
                            int(ev.duration_ns)] for ev in line.events]
                elif line.name == trace_reduce.MODULES_LINE:
                    modules = [(int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns),
                                program_name(ev.name)) for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith((PROGRAM_PREFIX, HARNESS_PREFIX)):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns),
                                     {k: str(v) for k, v in ev.stats
                                      if not k.startswith("_")}])
    modules.sort()
    m = 0
    for op in sorted(ops, key=lambda op: op[1]):
        while m < len(modules) and modules[m][1] <= op[1]:
            m += 1
        inside = m < len(modules) and modules[m][0] <= op[1]
        op.append(modules[m][2] if inside else "")
    return {"ops": ops, "host": host, "scopes": scopes}


def slice_rows(rows, steps):
    """The first ``steps`` whole steps of a trace: the operations and host
    spans up to the end of that step's annotation, and only the scopes those
    operations need: a fixture's size."""
    ends = sorted(h[1] + h[2] for h in rows["host"]
                  if h[0] == ProgramTrace.STEP)
    end = ends[steps - 1]
    ops = [op for op in rows["ops"] if op[1] < end]
    used = {(op[3], op[0]) for op in ops}
    return {"ops": ops,
            "host": [h for h in rows["host"] if h[1] + h[2] <= end],
            "scopes": {prog: {i: name for i, name in table.items()
                              if (prog, i) in used}
                       for prog, table in rows["scopes"].items()}}


# -------------------------------------------------------------- the analysis
def self_times(ops):
    """Events of one device line nest or follow each other (a ``while``
    spans its body's operations): the time of each that no event inside it
    covers -> [(op, self_ns)], which add up to the line's busy time."""
    out, stack = [], []          # stack: [op, end_ns, ns covered by children]

    def close():
        op, _end, covered = stack.pop()
        out.append((op, op[2] - covered))

    for op in sorted(ops, key=lambda op: (op[1], -op[2])):
        while stack and stack[-1][1] <= op[1]:
            close()
        if stack:
            stack[-1][2] += min(op[2], stack[-1][1] - op[1])
        stack.append([op, op[1] + op[2], 0])
    while stack:
        close()
    return out


def timeline(spans):
    """Host spans [name, start, dur, ...], nested or overlapping -> sorted
    disjoint segments (start, end, name): at every instant the innermost of
    the spans open then (of two, the one that started later)."""
    events = sorted([(s[1], 1, i) for i, s in enumerate(spans)]
                    + [(s[1] + s[2], 0, i) for i, s in enumerate(spans)])
    out, open_now, before = [], set(), None
    for at, opens, i in events:
        if open_now and at > before:
            inner = max(open_now, key=lambda j: spans[j][1])
            out.append((before, at, spans[inner][0]))
        (open_now.add if opens else open_now.discard)(i)
        before = at
    return out


def cut_along(gap, timelines):
    """One idle gap (start, end) cut along ``timelines`` (``timeline(...)``
    of the program's spans, then of the harness's): each part of it goes to
    the segment of the first timeline that covers that part, and what none
    covers to ``OUTSIDE`` -> {name: ns}."""
    out, pieces = {}, [gap]
    for segments in timelines:
        starts = [seg[0] for seg in segments]
        left = []
        for lo, hi in pieces:
            first = max(bisect.bisect_right(starts, lo) - 1, 0)
            over = []
            for start, end, name in segments[first:]:
                if start >= hi:
                    break
                a, b = max(start, lo), min(end, hi)
                if b > a:
                    out[name] = out.get(name, 0) + (b - a)
                    over.append((a, b))
            left += trace_reduce.gaps(over, lo, hi)
        pieces = left
    for lo, hi in pieces:
        out[OUTSIDE] = out.get(OUTSIDE, 0) + (hi - lo)
    return out


#: the host phases of a train step that have an idle metric of their own
#: (``train/prefetch`` runs inside ``train/input``); every other stretch is
#: ``train.idle_ms.outside``
IDLE_PHASES = {"fence": ("train/fence",),
               "input": ("train/input", "train/prefetch"),
               "dispatch": ("train/dispatch",)}


class ProgramTrace:
    """A traced slice by the program's scopes and spans; times in ns."""

    STEP = PROGRAM_PREFIX + "train/step"

    def __init__(self, rows):
        self.ops, self.scopes = rows["ops"], rows["scopes"]
        program = [h for h in rows["host"] if h[0].startswith(PROGRAM_PREFIX)]
        # a step is whole in the slice (the engine fences every step): the
        # step annotations count them, and, covering every phase, name none
        self.steps = sum(1 for h in program if h[0] == self.STEP)
        self.spans = [h for h in program if h[0] != self.STEP]
        self.harness = [h for h in rows["host"]
                        if h[0].startswith(HARNESS_PREFIX)]
        self.busy_ns = self.unattributed_ns = 0
        self.by_scope = {}           # scope -> ns under it, nested or not
        self.unattributed_ops = {}
        for op, ns in self_times(self.ops):
            self.busy_ns += ns
            under = scopes_of(self.scopes.get(op[3], {}).get(op[0], ""))
            for scope in set(under):
                self.by_scope[scope] = self.by_scope.get(scope, 0) + ns
            if not under:
                self.unattributed_ns += ns
                key = op[3] + ":" + trace_reduce.instruction_kind(op[0])
                self.unattributed_ops[key] = \
                    self.unattributed_ops.get(key, 0) + ns

    def scope_ms_per_step(self, *scopes):
        """Device ms a step under any of ``scopes`` (which must not nest in
        each other); None where the program published no scope."""
        if not self.steps or not self.by_scope:
            return None
        return sum(self.by_scope.get(s, 0) for s in scopes) / self.steps / 1e6

    def unattributed_pct(self):
        """Share of the device's busy time under none of ``SCOPES``."""
        if not self.busy_ns or not self.by_scope:
            return None
        return 100.0 * self.unattributed_ns / self.busy_ns

    @functools.cached_property
    def idle_by_span(self):
        """Device-idle ns of the slice by the span the host was in: the
        program's (``train/fence`` ...), else the harness's (``bench:...``),
        else ``OUTSIDE``."""
        busy = [(op[1], op[1] + op[2]) for op in self.ops]
        lo, hi = min(s for s, _ in busy), max(e for _, e in busy)
        out, timelines = {}, [timeline(self.spans), timeline(self.harness)]
        for gap in trace_reduce.gaps(busy, lo, hi):
            for name, ns in cut_along(gap, timelines).items():
                if name.startswith(PROGRAM_PREFIX):
                    name = name[len(PROGRAM_PREFIX):]
                out[name] = out.get(name, 0) + ns
        return out

    def idle_ms_per_step(self, phase):
        """Idle ms a step while the host was in a span of ``phase`` (a key
        of ``IDLE_PHASES``), or, for ``OUTSIDE``, in no span of any of them;
        None where the program has no spans."""
        if not self.steps or not self.spans:
            return None
        if phase == OUTSIDE:
            named = {n for names in IDLE_PHASES.values() for n in names}
            ns = sum(v for n, v in self.idle_by_span.items() if n not in named)
        else:
            ns = sum(self.idle_by_span.get(n, 0) for n in IDLE_PHASES[phase])
        return ns / self.steps / 1e6

    def summary(self, k=6):
        ms = 1e-6 / max(self.steps, 1)
        top = sorted(self.unattributed_ops.items(), key=lambda kv: -kv[1])[:k]
        return {"steps": self.steps, "busy_ms_per_step": self.busy_ns * ms,
                "scope_ms_per_step": {s: ns * ms for s, ns in
                                      sorted(self.by_scope.items())},
                "unattributed_ms_per_step": self.unattributed_ns * ms,
                "unattributed_top": [[n, ns * ms] for n, ns in top],
                "idle_ms_per_step": {n: ns * ms for n, ns in
                                     sorted(self.idle_by_span.items())}}


def published_scopes():
    """The program's registry, or None from a program that has none."""
    try:
        from deeperspeed_tpu import telemetry
    except ImportError:
        return None
    published = getattr(telemetry, "step_scopes", None)
    return published() if published is not None else None


@functools.lru_cache(maxsize=1)
def of_run(directory=TRACE_DIR):
    """The run's own trace, read once for all readers -> ``ProgramTrace``,
    or None where there is nothing to read (no trace, or a program without
    the registry).  Prints one progress line: what the ten readers share."""
    scopes = published_scopes()
    if scopes is None:
        return None
    try:
        path = trace_reduce.find_xplane(directory)
    except FileNotFoundError:
        return None
    t0 = time.perf_counter()
    found = ProgramTrace(read_rows(path, scopes))
    print(json.dumps({"progress": "program_trace",
                      "read_s": time.perf_counter() - t0,
                      "programs": {p: len(t) for p, t in scopes.items()},
                      **found.summary()}), flush=True)
    return found


def for_reader(record, trace):
    """What every reader of this PR's metrics starts with: the run's
    ``ProgramTrace``, or None outside a traced training run."""
    if trace is None or "losses" not in record:
        return None
    return of_run()

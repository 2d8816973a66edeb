"""Request-path tracing: spans, trace contexts, and a flight recorder.

The scalar channels in :mod:`.registry` answer "how many / how fast on
average"; this module answers "where did *this* request's time go".  A
:class:`Span` is one timed interval (trace_id / span_id / parent_id, a
monotonic-clock duration anchored to a wall-clock start, explicit
attributes).  A :class:`TraceContext` is the handle a request carries
through the stack -- the frontend opens the root ``request`` span, every
layer underneath (routing, scheduler rounds, KV migration, fabric hops)
attaches children to it, and the two-field ``wire()`` payload rides an
optional ``trace`` key on ``wire_proto`` control frames so spans stitch
across process boundaries.

Ownership is the exactly-once rule: only the context created by the
outermost ``submit`` has ``owns=True``; replayed pool attempts and
fabric-host shadows adopt the trace with ``owns=False``, so token events
and the terminal SLO record are emitted once per request no matter how
many times the stream is re-placed.

Finished spans land in a bounded in-memory ring, an optional rank-0
``trace.jsonl`` (reusing :class:`~.registry.JsonlSink`), and the
:class:`FlightRecorder` -- a smaller ring that ``flight_dump`` snapshots
to disk whenever failover, circuit-break, drain-past-grace, wire
corruption, or the stall watchdog fires.  ``export_chrome`` renders the
ring as Chrome-trace / Perfetto JSON (one ``tid`` lane per trace).

The hot-path contract: a disabled tracer costs one attribute read
(``get_tracer().enabled``) per call site and zero per-token work -- call
sites must check ``enabled`` before building spans, exactly like the
``reg.enabled`` idiom in :mod:`.serving`.

Live work is wrapped in :func:`span` (also behind ``tracer.span`` and
``ctx.span``): every span is a ``jax.profiler.TraceAnnotation`` named
``dst:<layer>/<phase>`` first, so it sits on the device trace's clock in any
profiler session, and a ring record second, when the tracer is enabled.
The module also keeps the two things only the program can tell a trace
reader: what was compiled (:func:`compile_stats`) and which scope each
instruction of a step program was traced under (:func:`step_scopes`).

And one record a train step, always on, profiler or not
(:func:`step_timeline`): :func:`step_span` opens it and reads the process's
and the thread's CPU clocks at both ends, every span closed inside the step
on the step's thread adds its wall time to it, and the model's counters of
that step go with it (:func:`step_counters` is the
newest record's).  Its ``step`` is the ``step_num`` the step's annotation
carries, so a device trace joins it.

Set-up has the same, beside it (:func:`setup_timeline`): the ``setup/*``
spans closed outside any step (``setup/import``, ``setup/initialize`` and
its children, ``setup/load_checkpoint``) and the compile phases
:func:`compile_stats` keeps by interval (trace, lower, backend compile,
cache load), so that the time to the first step can be told by phase.
"""

import functools
import json
import os
import re
import threading
import time
import uuid
from collections import deque

import jax
import numpy as np

from ..utils.logging import logger
from .registry import JsonlSink, _is_rank0, get_registry


#: every program span is ``dst:<layer>/<phase>`` on the profiler's timeline
#: (a harness's own are ``bench:<span>``)
SPAN_PREFIX = "dst:"
#: a span of this layer, closed outside a step, is kept (``setup_timeline``)
SETUP_PREFIX = "setup/"


class _ThreadState(threading.local):
    #: the step record this thread has open (``step_span``); a class default,
    #: so that a thread outside any step reads it at an attribute's price
    step = None


_THREAD = _ThreadState()


def _open_spans():
    """The ring spans this thread has open, outermost first."""
    try:
        return _THREAD.open
    except AttributeError:
        _THREAD.open = []
        return _THREAD.open


def new_id():
    """16-hex-char random id (trace or span)."""
    return uuid.uuid4().hex[:16]


def quantile(sorted_samples, q):
    """Linear-interpolated quantile of an already-sorted sample list.

    ``q`` in [0, 1].  Replaces the round-to-nearest-index pick that made
    small-sample percentiles land on arbitrary observations.
    """
    if not sorted_samples:
        return None
    if len(sorted_samples) == 1:
        return sorted_samples[0]
    pos = min(max(q, 0.0), 1.0) * (len(sorted_samples) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_samples) - 1)
    frac = pos - lo
    return sorted_samples[lo] * (1.0 - frac) + sorted_samples[hi] * frac


class Span:
    """One open timed interval.  Closed via ``Tracer.end_span`` (which
    turns it into a plain record dict); cheap on purpose -- slots, two
    clock reads, no allocation beyond the attrs dict."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "start_unix",
                 "_t0", "attrs")

    def __init__(self, trace_id, span_id, parent_id, name, attrs):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.attrs = attrs
        self.start_unix = time.time()
        self._t0 = time.monotonic()


class _SpanScope:
    """One live program span (``span(...)`` / ``tracer.span(...)`` /
    ``ctx.span(...)``): a ``jax.profiler.TraceAnnotation`` named
    ``dst:<name>`` on the profiler's clock and, when its tracer is enabled,
    the same interval as a ring :class:`Span` (same name, attributes and
    parent).  With no ``parent_id`` given the ring span nests under the
    innermost span this thread has open.

    Inside a step (``step_span`` open on this thread) the interval's wall
    time is also added to the step's record under the span's own name: a
    span nested in another (``train/prefetch`` in ``train/input``) is kept
    under its name and lies inside the outer one's interval too.  A span on
    another thread lands in no record.  No CPU clock is read here: on the
    chip machine's host such a read is a system call of 6-18 us and the
    clock ticks every 10 ms, so a phase of a few ms cannot be told from
    its neighbours (PERF.md section 6, PR 42); the step reads them, twice.

    Outside a step a span named ``setup/...`` is kept whole, with the
    ``setup/`` span it lies in (:func:`setup_timeline`); any other span
    outside a step is kept nowhere but in the ring."""

    __slots__ = ("_tracer", "name", "attrs", "_ids", "_annotation", "span",
                 "_step", "entered", "_setup")

    def __init__(self, tracer, name, trace_id, parent_id, attrs):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self._ids = (trace_id, parent_id)
        self.span = None

    def __enter__(self):
        self._annotation = jax.profiler.TraceAnnotation(
            SPAN_PREFIX + self.name, **self.attrs)
        self._annotation.__enter__()
        self._step = _THREAD.step
        if self._step is not None:
            #: ``time.perf_counter()`` where a span inside a step opened
            self.entered = time.perf_counter()
        else:
            self._setup = (_SETUP_TIMELINE.open(self.name, self.attrs)
                           if self.name.startswith(SETUP_PREFIX) else None)
        if self._tracer.enabled:
            trace_id, parent_id = self._ids
            stack = _open_spans()
            if stack and parent_id is None:
                trace_id = trace_id or stack[-1].trace_id
                parent_id = stack[-1].span_id
            self.span = self._tracer.start_span(
                self.name, trace_id=trace_id, parent_id=parent_id,
                **self.attrs)
            stack.append(self.span)
        return self

    @property
    def trace_id(self):
        return self.span.trace_id if self.span is not None else None

    @property
    def span_id(self):
        return self.span.span_id if self.span is not None else None

    def set(self, **attrs):
        """Attributes known only once the work is under way."""
        self._annotation.set_metadata(**attrs)
        if self.span is not None:
            self.span.attrs.update(attrs)
        if self._step is None and self._setup is not None:
            self._setup.update(attrs)

    def __exit__(self, exc_type, exc, tb):
        if self._step is not None:
            wall = time.perf_counter() - self.entered
            phase = self._step["phases"].get(self.name)
            if phase is None:
                self._step["phases"][self.name] = [wall, 1]
            else:
                phase[0] += wall
                phase[1] += 1
        elif self._setup is not None:
            _SETUP_TIMELINE.close(self._setup)
        if self.span is not None:
            if exc_type is not None:
                self.span.attrs["error"] = exc_type.__name__
            _open_spans().pop()       # spans of one thread close in order
            self._tracer.end_span(self.span)
        self._annotation.__exit__(exc_type, exc, tb)
        return False


class FlightRecorder:
    """Bounded ring of the most recent span/event records plus postmortem
    dumps: ``dump(reason)`` snapshots the ring to a ``flight_*.json`` file
    so the evidence survives the crash that triggered it.  Dump count is
    capped -- a flapping replica must not fill the disk -- but the cap
    *rotates*: once ``max_dumps`` is reached the oldest dump is deleted to
    make room, because the most recent incident is the one an operator
    actually wants (dropping new dumps would lose exactly that one)."""

    def __init__(self, dump_dir, capacity=256, max_dumps=64):
        self.dump_dir = dump_dir
        self._ring = deque(maxlen=max(int(capacity), 1))
        self.max_dumps = int(max_dumps)
        self.dumps = []          # paths currently on disk, oldest first
        self.rotated_dumps = 0   # oldest dumps deleted to admit new ones
        self._seq = 0            # monotonic dump number (survives rotation)

    def record(self, rec):
        self._ring.append(rec)

    def recent(self, n=None):
        out = list(self._ring)
        return out if n is None else out[-n:]

    def dump(self, reason, extra=None):
        while len(self.dumps) >= max(self.max_dumps, 1):
            oldest = self.dumps.pop(0)
            try:
                os.remove(oldest)
            except OSError:
                pass
            self.rotated_dumps += 1
            reg = get_registry()
            if reg.enabled:   # imported from .registry -- no serving dep
                reg.counter("trace/flight_dumps_rotated").inc()
        snap = {"ts": time.time(), "reason": str(reason),
                "extra": dict(extra) if extra else {},
                "spans": list(self._ring)}
        safe = "".join(c if (c.isalnum() or c in "-_") else "_"
                       for c in str(reason)) or "dump"
        os.makedirs(self.dump_dir, exist_ok=True)
        self._seq += 1
        path = os.path.join(
            self.dump_dir, f"flight_{safe}_{self._seq}.json")
        with open(path, "w") as f:
            json.dump(snap, f, indent=2, default=str)
        self.dumps.append(path)
        return path


class Tracer:
    """Span sink + flight recorder.  ``enabled=False`` (the process-global
    default) builds a null tracer: no directories, no files, every method
    an early-out -- but call sites still must gate on ``enabled`` so the
    traced hot path pays nothing when tracing is off."""

    def __init__(self, enabled=False, run_dir="telemetry", job_name="run",
                 jsonl=True, rank0_only=True, buffer_spans=2048,
                 flight_spans=256, max_dumps=64):
        self.enabled = bool(enabled)
        self.run_dir = os.path.join(run_dir or "telemetry", job_name or "run")
        self._lock = threading.Lock()
        self._spans = deque(maxlen=max(int(buffer_spans), 1))
        self.recorder = FlightRecorder(self.run_dir, capacity=flight_spans,
                                       max_dumps=max_dumps)
        self.jsonl_path = None
        self._jsonl = None
        self.span_count = 0
        if self.enabled and jsonl and ((not rank0_only) or _is_rank0()):
            self.jsonl_path = os.path.join(self.run_dir, "trace.jsonl")
            self._jsonl = JsonlSink(self.jsonl_path)

    # ------------------------------------------------------------- spans
    def start_span(self, name, trace_id=None, parent_id=None, **attrs):
        return Span(trace_id or new_id(), new_id(), parent_id, name, attrs)

    def end_span(self, span, **attrs):
        """Close ``span`` and record it; returns the record dict."""
        if attrs:
            span.attrs.update(attrs)
        rec = {"kind": "span", "name": span.name, "trace_id": span.trace_id,
               "span_id": span.span_id, "parent_id": span.parent_id,
               "ts": span.start_unix,
               "dur_s": time.monotonic() - span._t0}
        rec.update(span.attrs)
        self._record(rec)
        return rec

    def span(self, name, trace_id=None, parent_id=None, **attrs):
        return _SpanScope(self, name, trace_id, parent_id, attrs)

    def record_span(self, name, trace_id, parent_id=None, start_unix=None,
                    dur_s=0.0, **attrs):
        """Record an already-elapsed interval (e.g. queue wait measured
        from a stored enqueue stamp) without open-span bookkeeping."""
        rec = {"kind": "span", "name": name, "trace_id": trace_id,
               "span_id": new_id(), "parent_id": parent_id,
               "ts": (time.time() - dur_s) if start_unix is None
               else start_unix,
               "dur_s": float(dur_s)}
        rec.update(attrs)
        self._record(rec)
        return rec

    def event(self, name, trace_id, parent_id=None, **attrs):
        """Instantaneous marker (token arrival, fallback decision...)."""
        rec = {"kind": "event", "name": name, "trace_id": trace_id,
               "span_id": new_id(), "parent_id": parent_id,
               "ts": time.time(), "dur_s": 0.0}
        rec.update(attrs)
        self._record(rec)
        return rec

    def _record(self, rec):
        if not self.enabled:
            return
        with self._lock:
            self.span_count += 1
            self._spans.append(rec)
            self.recorder.record(rec)
            if self._jsonl is not None:
                self._jsonl.write(rec)

    def reset(self):
        """Drop buffered spans (bench arms call this between warm-up and
        measurement so percentile tables cover only measured work); the
        flight ring and jsonl stream are untouched."""
        with self._lock:
            self._spans.clear()

    # ----------------------------------------------------------- readers
    def spans(self, trace_id=None, name=None):
        with self._lock:
            out = list(self._spans)
        if trace_id is not None:
            out = [r for r in out if r["trace_id"] == trace_id]
        if name is not None:
            out = [r for r in out if r["name"] == name]
        return out

    def recent(self, n=None):
        """Flight-recorder view: the last ``n`` records (watchdog hook)."""
        with self._lock:
            return self.recorder.recent(n)

    @property
    def flight_dumps(self):
        return list(self.recorder.dumps)

    # ----------------------------------------------------- flight dumps
    def flight_dump(self, reason, extra=None):
        """Snapshot the flight ring to disk; never raises into the serving
        path (a postmortem helper must not cause the mortem)."""
        if not self.enabled:
            return None
        try:
            with self._lock:
                path = self.recorder.dump(reason, extra=extra)
            if path is not None:
                logger.warning(f"flight recorder dump ({reason}) -> {path}")
            return path
        except Exception as e:
            logger.warning(f"flight recorder dump failed: {e}")
            return None

    # ----------------------------------------------------------- export
    def export_chrome(self, path, trace_id=None):
        """Write the span ring as Chrome-trace JSON (``chrome://tracing``
        / Perfetto 'trace event' format): one tid lane per trace_id so
        each request reads as a waterfall."""
        recs = self.spans(trace_id=trace_id)
        lanes = {}
        events = []
        for r in recs:
            tid = lanes.setdefault(r["trace_id"], len(lanes) + 1)
            args = {k: v for k, v in r.items()
                    if k not in ("kind", "name", "trace_id", "span_id",
                                 "parent_id", "ts", "dur_s")}
            args["trace_id"] = r["trace_id"]
            args["span_id"] = r["span_id"]
            if r.get("parent_id"):
                args["parent_id"] = r["parent_id"]
            ev = {"name": r["name"], "cat": "request", "pid": 0, "tid": tid,
                  "ts": r["ts"] * 1e6, "args": args}
            if r["kind"] == "event":
                ev.update(ph="i", s="t")
            else:
                ev.update(ph="X", dur=r["dur_s"] * 1e6)
            events.append(ev)
        meta = [{"name": "thread_name", "ph": "M", "pid": 0, "tid": tid,
                 "args": {"name": f"trace {tid_name[:8]}"}}
                for tid_name, tid in lanes.items()]
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump({"traceEvents": meta + events,
                       "displayTimeUnit": "ms"}, f)
        return path

    def flush(self):
        if self._jsonl is not None:
            with self._lock:
                self._jsonl.flush()

    def close(self):
        if self._jsonl is not None:
            with self._lock:
                self._jsonl.close()


class TraceContext:
    """The handle a request carries: (trace_id, span_id-to-parent-under,
    ownership).  ``root`` starts a new trace and owns it; ``adopt`` joins
    an existing trace (wire payload or an outer ticket's context) without
    ownership, optionally opening a local scope span that ``close()``
    finishes at the adopter's terminal transition."""

    __slots__ = ("tracer", "trace_id", "span_id", "owns", "_open")

    def __init__(self, tracer, trace_id, span_id, owns, open_span=None):
        self.tracer = tracer
        self.trace_id = trace_id
        self.span_id = span_id
        self.owns = owns
        self._open = open_span

    # ------------------------------------------------------ constructors
    @classmethod
    def root(cls, tracer, name="request", **attrs):
        span = tracer.start_span(name, **attrs)
        return cls(tracer, span.trace_id, span.span_id, True, span)

    @classmethod
    def adopt(cls, tracer, payload, scope=None, **attrs):
        """Join the trace described by ``payload`` (a ``wire()`` dict).
        Returns None for a missing/foreign payload so call sites can fall
        back to an untraced request."""
        if not payload or not isinstance(payload, dict):
            return None
        trace_id = payload.get("trace_id")
        if not trace_id:
            return None
        parent = payload.get("span_id")
        if scope is None:
            return cls(tracer, trace_id, parent, False, None)
        span = tracer.start_span(scope, trace_id=trace_id, parent_id=parent,
                                 **attrs)
        return cls(tracer, trace_id, span.span_id, False, span)

    def fork(self, name, **attrs):
        """Child context under this one (a pool placement attempt, a
        fabric shadow): same trace, new open scope span, never owning."""
        span = self.tracer.start_span(name, trace_id=self.trace_id,
                                      parent_id=self.span_id, **attrs)
        return TraceContext(self.tracer, self.trace_id, span.span_id, False,
                            span)

    # ------------------------------------------------------------ wire
    def wire(self):
        """The two fields that cross a process boundary."""
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    # ---------------------------------------------------------- emitters
    def span(self, name, **attrs):
        return self.tracer.span(name, trace_id=self.trace_id,
                                parent_id=self.span_id, **attrs)

    def record(self, name, start_unix=None, dur_s=0.0, **attrs):
        return self.tracer.record_span(name, self.trace_id,
                                       parent_id=self.span_id,
                                       start_unix=start_unix, dur_s=dur_s,
                                       **attrs)

    def event(self, name, **attrs):
        return self.tracer.event(name, self.trace_id,
                                 parent_id=self.span_id, **attrs)

    def annotate(self, **attrs):
        if self._open is not None:
            self._open.attrs.update(attrs)

    def close(self, **attrs):
        """Finish this context's open scope span (idempotent)."""
        span, self._open = self._open, None
        if span is not None:
            self.tracer.end_span(span, **attrs)


# ------------------------------------------------------- flight reasons
# The known ``flight_dump(reason)`` vocabulary, so postmortem tooling (and
# the chaos harness's dump assertions) match against one registry instead
# of scattered string literals.  ``stall_*`` reasons from the watchdog are
# prefixed per trigger and not enumerated here.
FLIGHT_REASONS = {
    "quarantine": "request exhausted its step-failure retries",
    "circuit_break": "scheduler quarantined a request mid-round",
    "replica_eject": "pool ejected a replica (health breaker / gossip)",
    "failover": "in-flight request re-placed off a dead replica",
    "drain_past_grace": "drain grace expired; survivors migrated",
    "recompute_fallback": "KV migration written off; prompt recomputed",
    "kv_corrupt": "host-tier block failed its digest check",
    "wire_corruption": "fabric frame failed checksum/decode",
    # PR 14: elasticity + multi-tenant isolation
    "scale_out": "autoscaler added a warm replica to the pool",
    "scale_in": "autoscaler drained a replica out of the pool",
    "tenant_throttle": "tenant token bucket rejected admission",
    "preempt_best_effort": "best-effort decodes evicted for a "
                           "near-deadline latency tenant",
    # PR 17: pool-global observability plane
    "slo_burn": "fast-window SLO burn-rate alert fired on pool-aggregated "
                "latency percentiles",
    # PR 18: rolling weight hot-swap
    "deploy_abort": "rolling update aborted (stream verification failure "
                    "or canary divergence); old weights kept/restored",
}


# --------------------------------------------------------------- SLO math
def slo_percentiles(records, quantiles=(0.5, 0.95, 0.99)):
    """Per-SLO-class latency percentiles from closed root ``request``
    spans.  Returns ``{slo: {metric: {p50: ..., p95: ...}, count: n}}``
    for the metrics the terminal transition stamps (ttft_s, tpot_s,
    e2e_s, queue_wait_s)."""
    by_slo = {}
    for r in records:
        if r.get("kind") != "span" or r.get("name") != "request":
            continue
        slo = r.get("slo", "standard")
        by_slo.setdefault(slo, []).append(r)
    out = {}
    for slo, recs in sorted(by_slo.items()):
        table = {"count": len(recs)}
        for metric in ("ttft_s", "tpot_s", "e2e_s", "queue_wait_s"):
            samples = sorted(r[metric] for r in recs
                             if isinstance(r.get(metric), (int, float)))
            if not samples:
                continue
            table[metric] = {f"p{int(q * 100)}": quantile(samples, q)
                             for q in quantiles}
        out[slo] = table
    return out


def tenant_percentiles(records, quantiles=(0.5, 0.95, 0.99)):
    """Per-tenant latency percentiles from closed root ``request`` spans
    that carry a ``tenant`` attribute (stamped by the multi-tenant
    frontend).  Same table shape as :func:`slo_percentiles`, keyed by
    tenant; requests without the attribute are excluded rather than
    lumped, so single-tenant traffic yields an empty table."""
    by_tenant = {}
    for r in records:
        if r.get("kind") != "span" or r.get("name") != "request":
            continue
        tenant = r.get("tenant")
        if tenant is None:
            continue
        by_tenant.setdefault(str(tenant), []).append(r)
    out = {}
    for tenant, recs in sorted(by_tenant.items()):
        table = {"count": len(recs)}
        for metric in ("ttft_s", "tpot_s", "e2e_s", "queue_wait_s"):
            samples = sorted(r[metric] for r in recs
                             if isinstance(r.get(metric), (int, float)))
            if not samples:
                continue
            table[metric] = {f"p{int(q * 100)}": quantile(samples, q)
                             for q in quantiles}
        out[tenant] = table
    return out


# ------------------------------------------------------------ compile stats
def _merged(intervals, t0=None, t1=None):
    """``intervals`` clipped to ``[t0, t1]`` as disjoint ones, in order."""
    out = []
    for a, b in sorted(intervals):
        a = a if t0 is None else max(a, t0)
        b = b if t1 is None else min(b, t1)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _overlap(xs, ys):
    """Seconds that two lists of disjoint ordered intervals share."""
    shared, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        shared += max(0.0, min(xs[i][1], ys[j][1]) - max(xs[i][0], ys[j][0]))
        if xs[i][1] <= ys[j][1]:
            i += 1
        else:
            j += 1
    return shared


class _CompilePhase:
    """One kind of compile work: how often, the sum of its seconds, its
    intervals on ``time.perf_counter()`` and ``{fun_name: [count, s]}``."""

    __slots__ = ("count", "seconds", "intervals", "by_name")

    def __init__(self, keep):
        self.count, self.seconds = 0, 0.0
        self.intervals = deque(maxlen=keep)
        self.by_name = {}


class _CompileStats:
    """What this process compiled, from ``jax.monitoring``: programs handed
    to the backend compiler, persistent-cache hits (programs loaded instead)
    and misses (programs compiled and written to it), with the instant
    (``time.perf_counter``) and the seconds of each compile and load.

    And where compiling's time went, by interval: every jaxpr trace, every
    lowering to MLIR, every backend compile and every load from the cache
    (``phases``, a :class:`_CompilePhase` each).  Traces nest (a jitted call
    inside a traced function fires inside the outer one's interval) and jax
    wraps the cache's lookup in its backend-compile event, so sums of
    durations count seconds twice; :meth:`seconds` gives unions.  A listener
    is a few appends: no lock, nothing read from a device."""

    KEEP = 4096   # instants kept; the counts go on
    #: intervals kept a kind: a set-up traces 16 thousand functions
    KEEP_INTERVALS = 1 << 16
    #: kind -> the ``jax.monitoring`` event it is read from
    EVENTS = {
        "trace": "/jax/core/compile/jaxpr_trace_duration",
        "lower": "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "backend_compile": "/jax/core/compile/backend_compile_duration",
        "cache_load": "/jax/compilation_cache/cache_retrieval_time_sec",
    }

    def __init__(self):
        self.programs = self.cache_hits = self.cache_misses = 0
        self.compiles = deque(maxlen=self.KEEP)      # (done at, seconds)
        self.cache_loads = deque(maxlen=self.KEEP)   # (done at, seconds)
        self.hits_at = deque(maxlen=self.KEEP)       # instants of the hits
        self.misses_at = deque(maxlen=self.KEEP)     # ... and of the misses
        self.phases = {kind: _CompilePhase(self.KEEP_INTERVALS)
                       for kind in self.EVENTS}
        self._kind_of = {event: kind for kind, event in self.EVENTS.items()}
        #: the kinds whose interval is ``[now - seconds, now]`` of a
        #: duration: the cache's load has no other, and all of them where
        #: jax has no time-span listener (:meth:`listen`)
        self._by_duration = {"cache_load"}

    def listen(self, monitoring):
        """Register with ``jax.monitoring``: the time spans
        (``record_event_time_span(event, start, end, fun_name=...)``, which
        jax 0.9.0 fires beside every duration of ``log_elapsed_time``) where
        it has them, the durations alone where not (no names then)."""
        monitoring.register_event_listener(self.on_event)
        monitoring.register_event_duration_secs_listener(self.on_duration)
        register = getattr(monitoring, "register_event_time_span_listener",
                           None)
        if register is not None:
            register(self.on_time_span)
        else:
            self._by_duration = set(self.EVENTS)

    def on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
            self.hits_at.append(time.perf_counter())
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1
            self.misses_at.append(time.perf_counter())

    def on_duration(self, event, seconds, **kw):
        kind = self._kind_of.get(event)
        if kind in self._by_duration:
            now = time.perf_counter()
            self._keep(kind, now - float(seconds), now, kw.get("fun_name"))

    def on_time_span(self, event, start, end, **kw):
        kind = self._kind_of.get(event)
        if kind is not None and kind not in self._by_duration:
            # the events come on time.time(): one conversion a callback
            shift = time.perf_counter() - time.time()
            self._keep(kind, start + shift, end + shift, kw.get("fun_name"))

    def _keep(self, kind, t0, t1, fun_name):
        phase = self.phases[kind]
        phase.count += 1
        phase.seconds += t1 - t0
        phase.intervals.append((t0, t1))
        if fun_name is not None:
            named = phase.by_name.get(fun_name)
            if named is None:
                phase.by_name[fun_name] = [1, t1 - t0]
            else:
                named[0] += 1
                named[1] += t1 - t0
        if kind == "backend_compile":
            self.programs += 1
            self.compiles.append((t1, t1 - t0))
        elif kind == "cache_load":
            self.cache_loads.append((t1, t1 - t0))
        else:
            return
        tracer = get_tracer()
        if tracer.enabled:
            tracer.record_span("compile", "compile", dur_s=t1 - t0,
                               from_cache=kind == "cache_load", kind=kind,
                               fun_name=fun_name)

    def seconds(self, kind, t0=None, t1=None):
        """The UNION of a kind's intervals (``trace``, ``lower``,
        ``backend_compile``, ``cache_load``) clipped to ``[t0, t1]`` on
        ``time.perf_counter()``: nested and repeated work counts once, so a
        phase never exceeds the wall time that holds it.  ``backend_compile``
        is that union less the cache loads inside it (jax's event wraps the
        cache's lookup): the key's hashing, a true compile, the write."""
        union = _merged(self.phases[kind].intervals, t0, t1)
        total = sum((b - a for a, b in union), 0.0)
        if kind == "backend_compile":
            total -= _overlap(union, _merged(
                self.phases["cache_load"].intervals, t0, t1))
        return total

    def between(self, t0, t1):
        """What was compiled inside ``[t0, t1]``: the four unions as
        ``<kind>_s`` and the programs, cache hits and misses counted there
        (a step record's ``compile``)."""
        found = {kind + "_s": self.seconds(kind, t0, t1)
                 for kind in self.EVENTS}
        for name, instants in (("programs", (at for at, _ in self.compiles)),
                               ("cache_hits", self.hits_at),
                               ("cache_misses", self.misses_at)):
            found[name] = sum(1 for at in instants if t0 <= at <= t1)
        return found

    def slowest(self, kind, n=10):
        """``[[fun_name, count, seconds]]`` of the ``n`` names with most
        seconds of a kind (sums by name: a name traced inside itself counts
        twice here, not in :meth:`seconds`)."""
        ranked = sorted(self.phases[kind].by_name.items(),
                        key=lambda item: -item[1][1])
        return [[name, count, seconds] for name, (count, seconds)
                in ranked[:n]]


_COMPILE_STATS = _CompileStats()
_COMPILE_STATS.listen(jax.monitoring)


def compile_stats():
    """The process's compile counters, live (see :class:`_CompileStats`):
    read ``.programs`` before and after a call to know whether it compiled,
    ``.seconds(kind, t0, t1)`` for the wall time a compile phase took."""
    return _COMPILE_STATS


# ------------------------------------------------------------- step scopes
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_STEP_SCOPES = {}


def instruction_scopes(hlo_text):
    """``{instruction name: op_name}`` of a compiled program's text.  An
    instruction the compiler made without metadata (a fusion it cut out, a
    convert it split off a matmul) takes the commonest ``op_name`` of the
    computation it calls, else that of its first operand that has one: it
    goes with what it was cut from."""
    scopes, by_computation, inside = {}, {}, None
    for line in hlo_text.splitlines():
        found = _INSTRUCTION.match(line)
        if found is None:
            head = _COMPUTATION.match(line)
            if head is not None:
                inside = by_computation.setdefault(head.group(1), {})
            continue
        name, rest = found.groups()
        own = _OP_NAME.search(rest)
        op_name = own.group(1) if own else None
        if op_name is None:
            called = _CALLED.search(rest)
            counts = by_computation.get(called.group(1)) if called else None
            if counts:
                op_name = max(counts, key=counts.get)
            else:
                op_name = next((scopes[o] for o in _OPERAND.findall(rest)
                                if o in scopes), None)
        if op_name is not None:
            scopes[name] = op_name
            if inside is not None:
                inside[op_name] = inside.get(op_name, 0) + 1
    return scopes


def step_scopes():
    """``{program name: {instruction name: op_name}}`` of the step programs
    published so far: what puts a device trace's events (a v5e trace names
    an event by its instruction and carries no scope) under the
    ``jax.named_scope`` they were traced in.  Empty in a process that no
    profiler session has covered."""
    return _STEP_SCOPES


def publish_step_scopes(hlo_text):
    """Keep the scope of every instruction of a compiled program's text
    under the program's name -> that name."""
    head = re.match(r"HloModule ([^\s,]+)", hlo_text)
    name = head.group(1) if head else "unknown"
    _STEP_SCOPES[name] = instruction_scopes(hlo_text)
    return name


# ----------------------------------------------------------- step timeline
class _StepTimeline:
    """The last ``KEEP`` step records, oldest first, and the newest of each
    program.  One writer a program (its train loop's thread) and no lock: a
    ``deque.append`` and a ``dict`` store are each atomic."""

    KEEP = 1024

    def __init__(self):
        self.records = deque(maxlen=self.KEEP)
        self.newest = {}

    def push(self, record):
        self.records.append(record)
        self.newest[record["program"]] = record

    def clear(self):
        self.records.clear()
        self.newest.clear()


_STEP_TIMELINE = _StepTimeline()
#: the one store under the name it had while it held the last step's counters
#: alone (tests empty it by this name)
_STEP_COUNTERS = _STEP_TIMELINE


def _step_record(program, step=None, profiled=None):
    return {"step": step, "program": program, "t0": None, "t1": None,
            "cpu0": None, "cpu1": None, "thread_cpu0": None,
            "thread_cpu1": None, "phases": {}, "compiled": False,
            "profiled": profiled, "counters": {}}


class _StepScope:
    """One live step (``step_span``): the profiler's step annotation and the
    step's record, open on this thread until the step closes."""

    __slots__ = ("record", "_annotation", "_outer")

    def __init__(self, name, step_num, program, profiled):
        self._annotation = jax.profiler.StepTraceAnnotation(
            SPAN_PREFIX + name, step_num=step_num)
        self.record = _step_record(program, step_num, profiled)

    def __enter__(self):
        self._annotation.__enter__()
        self._outer = _THREAD.step
        _THREAD.step = record = self.record
        record["cpu0"] = time.process_time()
        record["thread_cpu0"] = time.thread_time()
        record["t0"] = time.perf_counter()
        return self

    def elapsed(self):
        """Seconds since the step opened, on the record's clock."""
        return time.perf_counter() - self.record["t0"]

    def __exit__(self, exc_type, exc, tb):
        record = self.record
        record["t1"] = time.perf_counter()
        record["thread_cpu1"] = time.thread_time()
        record["cpu1"] = time.process_time()
        _THREAD.step = self._outer
        _STEP_TIMELINE.push(record)
        self._annotation.__exit__(exc_type, exc, tb)
        return False


def _read_counters(records):
    """Copies of ``records`` with their counters as numbers: one batched
    fetch, which waits for the newest of those steps."""
    told = jax.device_get([r["counters"] for r in records])
    return [dict(r, counters={k: np.asarray(v).tolist()
                              for k, v in counters.items()})
            for r, counters in zip(records, told)]


def step_timeline(read=False, steps=None):
    """The records of the last 1,024 steps (``_StepTimeline.KEEP``), oldest
    first, kept whether or not a profiler or the tracer is on; ``steps``
    picks those whose ``step`` is among them.  A record:

    * ``step``: the ``step_num`` of the step's ``dst:train/step`` annotation
      (what joins it to a device trace), and ``program``, the step program's
      name (``train_step``);
    * ``t0``, ``t1``: ``time.perf_counter()`` where the step opened and
      closed; ``cpu0``, ``cpu1``: ``time.process_time()`` there (every thread
      of the process); ``thread_cpu0``, ``thread_cpu1``: the step's own
      thread (``time.thread_time()``);
    * ``phases``: ``{span name: [wall s, count]}`` of the spans closed inside
      the step on its thread (``train/input``, ``train/dispatch``,
      ``train/fence`` ...);
    * ``compiled``: a dispatch of the step compiled something; ``profiled``:
      a profiler session was on when the step began;
    * ``counters``: what the step's model counted (``{name: device array}``).

    Nothing is read from the device unless ``read`` is true: then the
    counters come as numbers, after a wait for the newest step asked for.
    The records are copies; the counters' arrays are the step's own."""
    records = list(_STEP_TIMELINE.records)
    if steps is not None:
        wanted = set(steps)
        records = [r for r in records if r["step"] in wanted]
    return _read_counters(records) if read else [dict(r) for r in records]


def publish_step_counters(program, counters):
    """Keep what a step program's model reported of its own step
    (``{name: device array}``: a looped model's layer and head applications,
    its exits' shares) in the step's record; called outside a step, in a
    record of its own.  Nothing is read here: the arrays stay on the device
    until :func:`step_counters` or :func:`step_timeline` is asked."""
    record = _THREAD.step
    if record is not None and record["program"] == program:
        record["counters"] = counters
        # the open record is the program's newest from here on
        _STEP_TIMELINE.newest[program] = record
    else:
        record = _step_record(program)
        record["counters"] = counters
        _STEP_TIMELINE.push(record)


def step_counters(read=True):
    """``{program name: {counter: number or list}}`` of the newest step each
    program ran, where its model counted anything: the counters a model
    returns beside its loss (``(loss, {name: value})``), averaged over the
    step's microbatches.  A view of :func:`step_timeline`'s newest records.
    Reading waits for that step; ``read=False`` gives the device arrays as
    they are and waits for nothing."""
    newest = [r for r in _STEP_TIMELINE.newest.values() if r["counters"]]
    if read:
        newest = _read_counters(newest)
    return {r["program"]: dict(r["counters"]) for r in newest}


# ---------------------------------------------------------- set-up timeline
class _SetupTimeline:
    """The ``setup/*`` spans closed outside a step, oldest first (the last
    ``KEEP``), each with the ``setup/`` span it lay in on its thread.  Like
    the step timeline: no lock, a ``deque.append`` is atomic."""

    KEEP = _StepTimeline.KEEP

    def __init__(self):
        self.spans = deque(maxlen=self.KEEP)

    def open(self, name, attrs):
        """A span's record, open on this thread until :meth:`close`."""
        try:
            stack = _THREAD.setup
        except AttributeError:
            stack = _THREAD.setup = []
        record = dict(attrs, name=name, t0=None, t1=None,
                      parent=stack[-1]["name"] if stack else None)
        stack.append(record)
        record["t0"] = time.perf_counter()
        return record

    def close(self, record):
        record["t1"] = time.perf_counter()
        _THREAD.setup.pop()           # spans of one thread close in order
        self.spans.append(record)

    def keep(self, name, t0, t1, **attrs):
        """An interval that was over before a span could be opened."""
        self.spans.append(dict(attrs, name=name, t0=t0, t1=t1, parent=None))


@functools.lru_cache(maxsize=None)
def _process_t0():
    """The process's start on ``time.perf_counter()``, from its start time
    in ``/proc/self/stat`` (clock ticks since the boot) against the boot
    clock now; None where that cannot be had.  Read once."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.perf_counter() - age


_SETUP_TIMELINE = _SetupTimeline()


def keep_setup_span(name, t0, t1, **attrs):
    """Keep ``[t0, t1]`` (``time.perf_counter()``) as the ``setup/`` span
    ``name``: for what ends before :func:`span` can be had, the package's own
    import (``deeperspeed_tpu/__init__.py``)."""
    _SETUP_TIMELINE.keep(name, t0, t1, **attrs)


def setup_timeline():
    """Set-up as the program timed it, the step timeline's sibling: always
    on, copies, nothing read from a device.

    * ``process_t0``: the process's start on ``time.perf_counter()`` (None
      where ``/proc/self/stat`` cannot tell);
    * ``spans``: the ``setup/*`` spans closed so far outside any step, oldest
      first (the last 1,024): ``{"name", "t0", "t1", "parent", **attributes}``
      with ``parent`` the name of the ``setup/`` span it lay in
      (``setup/import``; ``setup/initialize`` and the engine's stretches
      under it; ``setup/load_checkpoint``);
    * ``compile``: ``{kind: {"count", "seconds", "wall_s"}}`` of the compile
      phases (:class:`_CompileStats`): how many, the SUM of their durations,
      and the union ``compile_stats().seconds(kind)``;
    * ``slowest``: ``{kind: [[fun_name, count, seconds]]}``, the ten names
      with most seconds of each kind.

    The first steps are not here: they are records of
    :func:`step_timeline`, and one whose dispatch compiled has ``compile``."""
    stats = _COMPILE_STATS
    return {
        "process_t0": _process_t0(),
        "spans": [dict(s) for s in _SETUP_TIMELINE.spans],
        "compile": {kind: {"count": phase.count, "seconds": phase.seconds,
                           "wall_s": stats.seconds(kind)}
                    for kind, phase in stats.phases.items()},
        "slowest": {kind: stats.slowest(kind) for kind in stats.phases},
    }


def time_to_first_step(t_end):
    """From the process's start to ``t_end`` (``time.perf_counter()``, the
    end of the first step that compiled nothing) by phase, in seconds:
    ``before_import_s`` (the interpreter's start, whatever was imported
    first), ``import_s`` (``setup/import``), ``initialize_s`` with
    ``initialize`` (``{child: s}`` of the newest ``setup/initialize``),
    ``compiled_steps`` and ``compiled_steps_s`` (the step records since then
    whose dispatch compiled) with ``compile`` (the sum of those records'
    own), and ``other_s``, what is left of ``total_s``: the caller's own
    work before and between, and the step that compiled nothing."""
    spans = [s for s in _SETUP_TIMELINE.spans if s["t1"] <= t_end]
    imported = [s for s in spans if s["name"] == "setup/import"]
    initialized = [s for s in spans if s["name"] == "setup/initialize"]
    start = _process_t0()
    if start is None:
        start = min([s["t0"] for s in spans], default=t_end)
    told = {"total_s": t_end - start, "before_import_s": 0.0,
            "import_s": 0.0, "initialize_s": 0.0, "initialize": {}}
    if imported:
        told["before_import_s"] = imported[0]["t0"] - start
        told["import_s"] = imported[0]["t1"] - imported[0]["t0"]
    since = start
    if initialized:
        whole = initialized[-1]
        since = whole["t1"]
        told["initialize_s"] = whole["t1"] - whole["t0"]
        told["initialize"] = {
            s["name"].rsplit("/", 1)[1]: s["t1"] - s["t0"] for s in spans
            if s["parent"] == whole["name"] and whole["t0"] <= s["t0"]
            and s["t1"] <= whole["t1"]}
    compiled = [r for r in _STEP_TIMELINE.records if "compile" in r
                and since <= r["t0"] and r["t1"] <= t_end]
    told["compiled_steps"] = len(compiled)
    told["compiled_steps_s"] = sum(r["t1"] - r["t0"] for r in compiled)
    told["compile"] = {key: sum(r["compile"][key] for r in compiled)
                       for key in (compiled[0]["compile"] if compiled
                                   else ())}
    told["other_s"] = told["total_s"] - sum(
        told[k] for k in ("before_import_s", "import_s", "initialize_s",
                          "compiled_steps_s"))
    return told


def describe_time_to_first_step(told):
    """:func:`time_to_first_step`'s answer as the operator's one line."""
    parts = [f"before import {told['before_import_s']:.1f}",
             f"import {told['import_s']:.1f}"]
    children = ", ".join(f"{name} {s:.1f}"
                         for name, s in told["initialize"].items())
    parts.append(f"initialize {told['initialize_s']:.1f}"
                 + (f" ({children})" if children else ""))
    steps = (f"{told['compiled_steps']} compiled step"
             f"{'' if told['compiled_steps'] == 1 else 's'} "
             f"{told['compiled_steps_s']:.1f}")
    c = told["compile"]
    if c:
        steps += (f" (trace {c['trace_s']:.1f}, lower {c['lower_s']:.1f}, "
                  f"cache load {c['cache_load_s']:.1f}, compile "
                  f"{c['backend_compile_s']:.1f}; programs {c['programs']}, "
                  f"from the cache {c['cache_hits']}, written to it "
                  f"{c['cache_misses']})")
    parts += [steps, f"other {told['other_s']:.1f}"]
    return (f"time to first step {told['total_s']:.1f} s: "
            + " | ".join(parts))


_KERNEL_PATHS = {}


def count_kernel_path(kernel, path):
    """Count one traced call of ``kernel`` on ``path``, where a kernel picks
    between forms from its operands' shapes (flash attention: ``in_place_2``
    = two heads to a lane block of ``[B, S, N*D]``, ``in_place_1``,
    ``folded``; under ``<kernel>_kv_heads`` a call on grouped-query heads:
    ``grouped_<rep>`` | ``copied_<rep>``).  Counted when a program is
    traced, not when it runs."""
    paths = _KERNEL_PATHS.setdefault(kernel, {})
    paths[path] = paths.get(path, 0) + 1


def kernel_paths():
    """``{kernel: {path: traced calls}}`` of this process so far: says
    which form of a kernel the programs traced here hold."""
    return {kernel: dict(paths) for kernel, paths in _KERNEL_PATHS.items()}


# ---------------------------------------------------------- kernel passes
# ``kernel_paths`` counts at trace time and cannot see a recomputation: a
# remat wrap re-runs a jaxpr, it does not re-trace the kernel's caller.  The
# compiled program can: each Pallas kernel is one ``tpu_custom_call`` whose
# ``op_name`` says which pass it was traced for.
_KERNEL_CALL = 'custom_call_target="tpu_custom_call"'
_KERNEL_OP_NAME = re.compile(r'\bop_name="([^"]*?(\w+)/pallas_call)"')
_WHILE = re.compile(r"\bwhile\(.*\bcondition=%?([\w.\-]+), body=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\b(?:true_computation|false_computation)=%?([\w.\-]+)"
                       r"|\bbranch_computations=\{([^}]*)\}")
_LIMIT = re.compile(r"= s32\[\][^ ]* constant\((\d+)\)")
_COUNTS_UP = re.compile(r"^\s+ROOT .* compare\(.*direction=LT")
_KERNEL_PASSES = {}


def _pass_of(op_name):
    if "rematted_computation" in op_name:
        return "recomputed"
    return "backward" if "transpose(jvp(" in op_name else "forward"


def count_kernel_passes(hlo_text):
    """``{kernel: {"forward": n, "recomputed": n, "backward": n}}``: the
    Pallas kernel calls a compiled program's text makes in one run, by the
    scope each kernel is dispatched under (``flash_attention``,
    ``fused_norm``) and by the pass its ``op_name`` places it in: under a
    remat wrap's ``rematted_computation`` it is the forward pass run again
    for the backward's sake, under ``transpose(jvp(...))`` alone the
    backward, else the forward.  A kernel in the body of a ``while`` whose
    condition is ``counter < constant`` (what a ``lax.scan`` compiles to)
    counts that many times; under any other loop, and in each branch of a
    conditional, once."""
    kernels, children, limits, counts_up = {}, {}, {}, set()
    inside = entry = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head is not None:
            inside = head.group(1)
            if line.startswith("ENTRY"):
                entry = inside
            continue
        if inside is None:
            continue
        if _KERNEL_CALL in line:
            named = _KERNEL_OP_NAME.search(line)
            key = ((named.group(2), _pass_of(named.group(1))) if named
                   else ("?", "forward"))
            own = kernels.setdefault(inside, {})
            own[key] = own.get(key, 0) + 1
            continue
        loop = _WHILE.search(line)
        if loop is not None:
            children.setdefault(inside, []).append(loop.groups())
            continue
        for found in _CALLED.finditer(line):
            children.setdefault(inside, []).append((None, found.group(1)))
        for one, many in _BRANCHES.findall(line):
            for name in [one] if one else re.findall(r"[\w.\-]+", many):
                children.setdefault(inside, []).append((None, name))
        limit = _LIMIT.search(line)
        if limit is not None:
            limits.setdefault(inside, []).append(int(limit.group(1)))
        if _COUNTS_UP.match(line):
            counts_up.add(inside)

    def trips(condition):
        if condition in counts_up and len(limits.get(condition, ())) == 1:
            return limits[condition][0]
        return 1

    totals = {}

    def total(computation):
        if computation not in totals:
            counts = dict(kernels.get(computation, {}))
            for condition, callee in children.get(computation, ()):
                times = trips(condition) if condition else 1
                for key, n in total(callee).items():
                    counts[key] = counts.get(key, 0) + times * n
            totals[computation] = counts
        return totals[computation]

    passes = {}
    for (kernel, which), n in total(entry).items():
        passes.setdefault(kernel, dict(forward=0, recomputed=0, backward=0))[
            which] = n
    return passes


def publish_kernel_passes(hlo_text):
    """Keep :func:`count_kernel_passes` of a step program's compiled text."""
    _KERNEL_PASSES.clear()
    _KERNEL_PASSES.update(count_kernel_passes(hlo_text))


def kernel_passes():
    """``{kernel: {"forward": n, "recomputed": n, "backward": n}}`` of the
    step program published last (with :func:`step_scopes`, by the first
    ``train_batch`` after a profiler session): Pythia-410M with remat reads
    ``{"flash_attention": {"forward": 24, "recomputed": 0, "backward":
    24}}``, since its recomputed blocks keep the kernel's output and lse."""
    return {kernel: dict(passes) for kernel, passes in _KERNEL_PASSES.items()}


class TraceSessionWatch:
    """Tells a loop when a profiler session that covered one of its steps
    has ended: ``ended()`` is one ``TraceAnnotation.is_enabled()`` a step,
    true once per session, on the first step after it."""

    __slots__ = ("_covered", "profiled")

    def __init__(self):
        self._covered = False
        #: what ``ended()`` last read: a session is on
        self.profiled = False

    def ended(self):
        self.profiled = jax.profiler.TraceAnnotation.is_enabled()
        if self.profiled:
            self._covered = True
            return False
        covered, self._covered = self._covered, False
        return covered


# ------------------------------------------------------------ program spans
def span(name, trace_id=None, parent_id=None, **attrs):
    """The program's one span primitive: ``with span("train/input"): ...``
    is a ``dst:train/input`` event on the profiler's timeline (collected by
    whoever has a ``jax.profiler`` session open) and, when the process
    tracer is enabled, the same interval in its ring; inside a step
    (:func:`step_span`) its wall time also goes to the step's record.
    Attributes arrive as the event's stats.  With no session and no tracer
    an enter and exit cost 1.2-1.3 us outside a step and 1.6-1.7 us inside
    one on the chip machine's host (PERF.md section 6, PR 42)."""
    return _SpanScope(get_tracer(), name, trace_id, parent_id, attrs)


def step_span(name, step_num, program, profiled=None):
    """A whole step of ``program``: a ``jax.profiler.StepTraceAnnotation``,
    so that the profiler's own tools group device work by step, and the
    step's record (:func:`step_timeline`), open on the calling thread until
    the step closes; ``profiled`` is the caller's own reading of whether a
    profiler session is on."""
    return _StepScope(name, step_num, program, profiled)


# ------------------------------------------------------------- process glue
_TRACER = Tracer(enabled=False)


def get_tracer():
    """Process-global tracer (a disabled null tracer until configured)."""
    return _TRACER


def set_tracer(tracer):
    global _TRACER
    _TRACER = tracer
    return tracer


def tracer_from_config(cfg, job_name=None):
    """Build a tracer from a ``TelemetryConfig`` block (its ``trace``
    sub-block) and install it as the process-global default when enabled.
    Mirrors :func:`~.registry.registry_from_config`."""
    tr = cfg.trace
    tracer = Tracer(
        enabled=cfg.enabled and tr.enabled,
        run_dir=cfg.output_path or "telemetry",
        job_name=job_name or cfg.job_name or "run",
        jsonl=tr.jsonl,
        rank0_only=cfg.rank0_only,
        buffer_spans=tr.buffer_spans,
        flight_spans=tr.flight_spans,
        max_dumps=tr.max_dumps,
    )
    if tracer.enabled:
        set_tracer(tracer)
    return tracer

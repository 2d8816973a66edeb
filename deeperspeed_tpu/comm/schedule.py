"""Compiler-driven collective scheduling: plan the step, don't hand-place it.

PR 4 hand-hoisted ppermutes and hand-built the deferred grad reduction, and
bailed to the per-microbatch path with a warning whenever tp/sp/pp/ep > 1.
This module is the general move (DeepCompile / T3, PAPERS.md): operate on the
*traced* step.

Three layers, bottom to top:

* :func:`find_collectives` -- walk a (closed) jaxpr recursively (pjit / scan /
  while / cond / shard_map / custom_* sub-jaxprs), returning one
  :class:`CollectiveSite` per collective eqn -- psum / reduce_scatter /
  all_gather / all_to_all / ppermute, with int8 payloads (the qgZ two-level
  and MoE a2a facades) tagged by dtype -- plus ``sharding_constraint`` eqns,
  the *implicit* sites where GSPMD will place a collective at compile time.
* :func:`hoist_collectives` -- a dependence-preserving reschedule of every
  (sub-)jaxpr's eqn list: a two-queue Kahn topological sort that issues any
  *ready* collective before the next compute eqn, so each collective starts
  as early as its data dependencies allow and XLA's async runtime gets the
  whole downstream independent-compute window to hide it in.  Pure dataflow
  reorder -- the emitted program is bit-exact.
* :func:`plan_schedule` + :class:`ScheduledStepFn` -- choose the grad-reduce
  schedule (deferred vs per-microbatch issue, bucket size, qgZ on/off)
  by scoring candidates with the telemetry cost model
  (``telemetry/wire.py`` ``plain_wire_bytes``/``ici_bandwidth``/
  ``overlap_estimate``), then trace the engine's step once, run the hoist
  pass over the jaxpr, and jit the rewritten program.

Wired behind ``comm.overlap.schedule: {"mode": "auto"|"manual"|"off"}``
(``runtime/engine.py``): ``manual`` keeps PR 4's hand-placed path as the
parity baseline, ``auto`` supersedes the tp/sp/pp/ep fallback -- those
regimes get a *planned* schedule (per-microbatch issue + jaxpr-level
hoisting) instead of a warning.  The same scorer drives the profile-once
autotuner (``autotuning/autotuner.py``).
"""

import dataclasses
import math

import jax
from jax.extend import core as jax_core

try:  # reorder-safety guard: axis-name tracking is not an ordering effect
    from jax._src.core import NamedAxisEffect
except ImportError:  # pragma: no cover - future jax relocations
    NamedAxisEffect = ()

from ..utils.logging import logger
from .overlap import bucketize  # noqa: F401  (re-exported for planners)

# primitive name -> wire-model collective kind (telemetry/wire.py convention)
# (psum_invariant is psum as traced inside check_vma=True shard_map bodies)
COLLECTIVE_PRIMS = {
    "psum": "all_reduce",
    "psum_invariant": "all_reduce",
    "reduce_scatter": "reduce_scatter",
    "all_gather": "all_gather",
    "all_to_all": "all_to_all",
    "ppermute": "ppermute",
}

# eqn params that hold sub-jaxprs to recurse into (anything Jaxpr-valued is
# picked up generically; this list is only documentation of the usual keys:
# pjit/scan 'jaxpr', while 'cond_jaxpr'/'body_jaxpr', cond 'branches',
# shard_map 'jaxpr', custom_jvp/vjp 'call_jaxpr'/'fun_jaxpr'/'jvp_jaxpr_fun').


# ---------------------------------------------------------------- discovery

@dataclasses.dataclass
class CollectiveSite:
    """One collective eqn found in the traced step."""

    path: tuple          # enclosing-eqn primitive names, outermost first
    index: int           # position in its (sub-)jaxpr's eqn list
    primitive: str       # jax primitive name
    kind: str            # wire-model kind ("all_reduce", ...) or "implicit"
    dtype: str           # payload dtype name (int8/float8_* tag the quantized wire)
    n_elems: int         # payload element count (static shapes)
    repeats: int         # trace-to-execution multiplier (scan lengths)
    axes: tuple          # named axes the collective runs over (or ())
    # implicit sites only: the collective the SPMD partitioner will
    # materialize at this sharding_constraint, classified from the
    # layout transition between the var's previous constraint and this
    # one -- "all_gather" (axes dropped), "shard" (axes added: a free
    # dynamic-slice), "all_to_all" (axes exchanged), "noop" (same
    # layout), "reshard" (no prior constraint seen; T3's fine-grained
    # fusion target).  Empty for explicit-collective sites.
    gspmd_kind: str = ""

    @property
    def quantized(self):
        return (self.dtype in ("int8", "uint8")
                or self.dtype.startswith("float8_"))


def _eqn_axes(eqn):
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if axes is None:
        return ()
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, (str, int)))


def _sub_jaxprs(params):
    """Yield (key, sub) for every Jaxpr/ClosedJaxpr value in eqn params."""
    for key, val in params.items():
        if isinstance(val, (jax_core.Jaxpr, jax_core.ClosedJaxpr)):
            yield key, val
        elif isinstance(val, (tuple, list)):
            for i, item in enumerate(val):
                if isinstance(item, (jax_core.Jaxpr, jax_core.ClosedJaxpr)):
                    yield (key, i), item


def _constraint_axes(eqn):
    """Mesh axes (size > 1) the sharding_constraint's target layout uses."""
    sharding = eqn.params.get("sharding")
    spec = getattr(sharding, "spec", None)
    sizes = dict(getattr(getattr(sharding, "mesh", None), "shape", {}) or {})
    axes = set()
    for entry in (tuple(spec) if spec is not None else ()):
        if entry is None:
            continue
        for a in (entry if isinstance(entry, (tuple, list)) else (entry,)):
            if sizes.get(a, 1) > 1:
                axes.add(a)
    return frozenset(axes)


def _classify_gspmd(prev_axes, tgt_axes):
    """The collective the partitioner materializes for a layout transition
    (what GSPMD decides at compile time, reconstructed at jaxpr level so
    the planner can see and score it -- T3's fine-grained fusion sites)."""
    if prev_axes is None:
        return "reshard"
    removed, added = prev_axes - tgt_axes, tgt_axes - prev_axes
    if removed and added:
        return "all_to_all"
    if removed:
        return "all_gather"
    if added:
        return "shard"
    return "noop"


def find_collectives(jaxpr, repeats=1, path=(), include_implicit=True):
    """All collective sites in ``jaxpr`` (a Jaxpr or ClosedJaxpr), recursing
    into sub-jaxprs.  ``repeats`` multiplies through ``scan`` lengths so a
    site's execution count is ``site.repeats`` per step.  With
    ``include_implicit`` sharding_constraint eqns are reported too (kind
    ``implicit``): they are where the SPMD partitioner will materialize a
    collective for GSPMD-auto regimes (tp/sp), invisible at jaxpr level --
    each classified (``gspmd_kind``) from the constraint-to-constraint
    layout transition of the var it pins, with ``axes`` naming the target
    layout's mesh axes."""
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    sites = []
    var_axes = {}  # constraint-pinned vars -> their layout's mesh axes
    for i, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            leaf = eqn.invars[0]
            aval = getattr(leaf, "aval", None)
            n_elems = int(math.prod(getattr(aval, "shape", ()) or ()))
            dtype = str(getattr(aval, "dtype", "")) or "unknown"
            sites.append(CollectiveSite(
                path=path, index=i, primitive=name,
                kind=COLLECTIVE_PRIMS[name], dtype=dtype, n_elems=n_elems,
                repeats=repeats, axes=_eqn_axes(eqn)))
        elif name == "sharding_constraint":
            invar = eqn.invars[0]
            prev = var_axes.get(invar) \
                if not isinstance(invar, jax_core.Literal) else None
            tgt = _constraint_axes(eqn)
            if include_implicit:
                aval = getattr(invar, "aval", None)
                sites.append(CollectiveSite(
                    path=path, index=i, primitive=name, kind="implicit",
                    dtype=str(getattr(aval, "dtype", "")) or "unknown",
                    n_elems=int(math.prod(getattr(aval, "shape", ()) or ())),
                    repeats=repeats, axes=tuple(sorted(tgt, key=str)),
                    gspmd_kind=_classify_gspmd(prev, tgt)))
            for ov in eqn.outvars:
                var_axes[ov] = tgt
        else:
            # propagate the pinned layout through shape-preserving eqns
            # (elementwise chains, converts) so the next constraint on the
            # same value classifies against its real prior layout instead
            # of degrading to "reshard"
            tracked = [v for v in eqn.invars
                       if not isinstance(v, jax_core.Literal)
                       and v in var_axes]
            if tracked:
                shape = getattr(getattr(tracked[0], "aval", None),
                                "shape", None)
                for ov in eqn.outvars:
                    if getattr(getattr(ov, "aval", None),
                               "shape", None) == shape:
                        var_axes[ov] = var_axes[tracked[0]]
        sub_repeats = repeats
        if name == "scan":
            sub_repeats = repeats * int(eqn.params.get("length", 1) or 1)
        for _, sub in _sub_jaxprs(eqn.params):
            sites.extend(find_collectives(
                sub, repeats=sub_repeats, path=path + (name,),
                include_implicit=include_implicit))
    return sites


def implicit_wire_summary(sites, axis_sizes=None):
    """Aggregate the GSPMD-materialized (implicit) sites for telemetry:
    ``(count, est_per_device_wire_bytes)``.

    ``axis_sizes`` maps mesh axis name -> size (the constraint sites only
    record axis *names*); unknown axes count as size 1.  Layout-preserving
    transitions (``noop``) and shard-introducing ones (``shard`` -- a free
    dynamic-slice, no wire traffic) cost nothing; ``all_gather`` /
    ``all_to_all`` are priced at the ring convention
    (``telemetry/wire.py``); an unwitnessed ``reshard`` is priced as one
    full-payload move (broadcast-equivalent upper bound for one device).
    """
    from ..telemetry.wire import plain_wire_bytes

    sizes = dict(axis_sizes or {})
    count, total = 0, 0.0
    for s in sites:
        if s.kind != "implicit":
            continue
        count += 1
        if s.gspmd_kind in ("noop", "shard", ""):
            continue
        n = 1
        for a in s.axes:
            n *= sizes.get(a, 1)
        if n <= 1:
            continue
        try:
            import numpy as _np

            itemsize = _np.dtype(s.dtype).itemsize
        except TypeError:
            itemsize = 4
        payload = s.n_elems * itemsize
        if s.gspmd_kind == "all_gather":
            wire = plain_wire_bytes("all_gather", payload // n, n)
        elif s.gspmd_kind == "all_to_all":
            wire = plain_wire_bytes("all_to_all", payload, n)
        else:  # reshard: no witnessed source layout; one payload move
            wire = float(payload)
        total += s.repeats * wire
    return count, total


# ------------------------------------------------------------------- hoist

def _benign_effects(effects):
    """True when every effect is axis-name bookkeeping (NamedAxisEffect):
    collectives inside shard_map bodies carry it, and it orders nothing."""
    return all(isinstance(e, NamedAxisEffect) for e in effects)


def _reorder_eqns(eqns):
    """Dependence-preserving early-issue reorder of one eqn list.

    Two-queue Kahn topological sort: whenever a collective eqn's inputs are
    all produced, it is emitted before any further compute eqn -- i.e. every
    collective moves to its earliest dataflow-legal issue point, maximizing
    the independent-compute window behind it.  Queues pop in original-index
    order, so the compute schedule (and any eqn with a non-benign effect,
    which is chained in program order) is otherwise stable.  Returns
    ``(new_eqns, n_hoisted)`` where ``n_hoisted`` counts collectives that
    moved earlier."""
    n = len(eqns)
    if n < 3:
        return list(eqns), 0

    producer = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.outvars:
            producer[v] = i
    deps = [set() for _ in range(n)]
    last_stateful = None
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not isinstance(v, jax_core.Literal) and v in producer:
                deps[i].add(producer[v])
        if not _benign_effects(eqn.effects):
            # conservative: stateful eqns keep their program order
            if last_stateful is not None:
                deps[i].add(last_stateful)
            last_stateful = i

    indegree = [len(d) for d in deps]
    dependents = [[] for _ in range(n)]
    for i, d in enumerate(deps):
        for j in d:
            dependents[j].append(i)

    is_coll = [eqn.primitive.name in COLLECTIVE_PRIMS and
               _benign_effects(eqn.effects) for eqn in eqns]
    import heapq

    coll_q, comp_q = [], []
    for i in range(n):
        if indegree[i] == 0:
            heapq.heappush(coll_q if is_coll[i] else comp_q, i)

    order = []
    while coll_q or comp_q:
        # drain every ready collective first, then ONE compute eqn (which
        # may unlock further collectives)
        while coll_q:
            order.append(heapq.heappop(coll_q))
            for j in dependents[order[-1]]:
                indegree[j] -= 1
                if indegree[j] == 0:
                    heapq.heappush(coll_q if is_coll[j] else comp_q, j)
        if comp_q:
            order.append(heapq.heappop(comp_q))
            for j in dependents[order[-1]]:
                indegree[j] -= 1
                if indegree[j] == 0:
                    heapq.heappush(coll_q if is_coll[j] else comp_q, j)
    if len(order) != n:  # pragma: no cover - cycle cannot happen in a jaxpr
        return list(eqns), 0

    n_hoisted = sum(1 for new_pos, old in enumerate(order)
                    if is_coll[old] and new_pos < old)
    return [eqns[i] for i in order], n_hoisted


def _rewrite_jaxpr(jaxpr):
    """Recursively apply :func:`_reorder_eqns` to ``jaxpr`` and every
    sub-jaxpr.  Returns ``(new_jaxpr, total_hoisted)``."""
    closed_consts = None
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        closed_consts = jaxpr.consts
        jaxpr = jaxpr.jaxpr

    total = 0
    new_eqns = []
    for eqn in jaxpr.eqns:
        new_params = None
        for key, sub in _sub_jaxprs(eqn.params):
            new_sub, n = _rewrite_jaxpr(sub)
            total += n
            if n:
                if new_params is None:
                    new_params = dict(eqn.params)
                if isinstance(key, tuple):  # ('branches', i)-style
                    pkey, idx = key
                    seq = list(new_params[pkey])
                    seq[idx] = new_sub
                    new_params[pkey] = tuple(seq)
                else:
                    new_params[key] = new_sub
        new_eqns.append(eqn.replace(params=new_params)
                        if new_params is not None else eqn)

    new_eqns, n = _reorder_eqns(new_eqns)
    total += n
    new_jaxpr = jaxpr.replace(eqns=new_eqns)
    if closed_consts is not None:
        return jax_core.ClosedJaxpr(new_jaxpr, closed_consts), total
    return new_jaxpr, total


def hoist_collectives(closed_jaxpr):
    """Early-issue every collective in a traced step (recursively, including
    shard_map / scan / pjit bodies).  Pure dataflow reorder: the rewritten
    program computes bit-identical results.  Returns
    ``(new_closed_jaxpr, n_hoisted)``."""
    return _rewrite_jaxpr(closed_jaxpr)


# ------------------------------------------------------------------ planner

@dataclasses.dataclass
class SchedulePlan:
    """The pass's decision for one engine's grad-reduce + issue schedule."""

    mode: str                  # "auto" (planned) -- manual/off never plan
    grad_schedule: str         # "deferred" | "per_microbatch"
    bucket_mb: float           # chosen bucket size (deferred only)
    hoist: bool                # run the jaxpr hoist pass over the step
    qgz: bool                  # quantized (qgZ/1-bit) reduce owns the wire
    fallback: bool             # False: every regime here is *planned*
    reason: str                # one-line human-readable rationale
    wire_bytes: float          # predicted per-step grad-reduce wire bytes
    est_exposed_s: float       # predicted exposed (unhidden) comm seconds
    candidates: tuple = ()     # (name, est_exposed_s, wire_bytes) per option
    # GSPMD-materialized (sharding_constraint) sites witnessed in the
    # traced step -- filled in after the first trace by the engine's
    # telemetry pass (the planner scores them; rewriting them is T3's
    # follow-on work)
    implicit_sites: int = 0
    implicit_wire_bytes: float = 0.0

    @property
    def tag(self):
        """Telemetry label for the chosen schedule."""
        base = self.grad_schedule
        if self.qgz:
            base = "quantized"
        if self.grad_schedule == "deferred" and self.bucket_mb > 0:
            base += f"[b{self.bucket_mb:g}mb]"
        return base + ("+hoist" if self.hoist else "")

    def describe(self):
        out = (f"{self.tag} (wire {self.wire_bytes / 2**20:.2f} MiB/step, "
               f"est exposed {self.est_exposed_s * 1e3:.3f} ms) -- "
               f"{self.reason}")
        if self.implicit_sites:
            out += (f"; {self.implicit_sites} gspmd site"
                    f"{'s' if self.implicit_sites != 1 else ''} "
                    f"(~{self.implicit_wire_bytes / 2**20:.2f} MiB/step)")
        return out


# per-issue dispatch latency: penalizes pathological bucket counts in the
# scorer; coarse by design (the score only ranks candidates under one
# topology, cf. wire.ICI_BANDWIDTH_SPECS accuracy note)
_ISSUE_LATENCY_S = 5e-6


def _bucket_count(grad_bytes, bucket_mb):
    if bucket_mb <= 0:
        return 1
    return max(1, math.ceil(grad_bytes / (bucket_mb * 2**20)))


def plan_schedule(*, grad_bytes, gas, n_ranks, deferred_allowed,
                  blockers=(), bucket_mb=0.0, qgz=False,
                  device_kind=None, compute_s=None):
    """Score grad-reduce schedule candidates with the telemetry cost model
    and return the winning :class:`SchedulePlan`.

    ``grad_bytes`` is the full gradient payload in wire dtype; ``n_ranks``
    the reduction group size.  ``deferred_allowed`` is False for regimes
    whose compute cannot run in the manual-dp shard_map (tp/sp/pp/ep,
    compression, qwZ) -- those get a *planned* per-microbatch issue with
    jaxpr-level hoisting, not a fallback.  ``compute_s``, when known (one
    profiled step), bounds how much comm each candidate can hide via
    ``overlap_estimate``; without it the scorer uses the bucket-pipelining
    exposure model alone.
    """
    from ..telemetry.hlo_cost import device_peaks
    from ..telemetry.wire import (ici_bandwidth, overlap_estimate,
                                  plain_wire_bytes)

    if device_kind is None:
        device_kind = device_peaks()[2]
    bw = ici_bandwidth(device_kind)

    def exposed(wire, n_issues):
        """Predicted unhidden comm time: every issue but the last can
        overlap the compute still in flight behind it, so exposure shrinks
        with issue count; a known compute budget caps the hideable part."""
        est = wire / bw
        exp = est / max(n_issues, 1) + _ISSUE_LATENCY_S * n_issues
        if compute_s is not None:
            # comm the profiled compute cannot absorb is exposed no matter
            # how the issues pipeline: step time is bounded below by
            # max(compute, comm), so the floor is est - compute_s
            exp = max(exp, overlap_estimate(wire, max(compute_s, est),
                                            compute_s, bw)["exposed_s"])
        return exp

    if qgz:
        # the quantized (qgZ / 1-bit) engines already issue one fused
        # once-per-batch reduction; the pass only adds hoisting
        wire = plain_wire_bytes("all_reduce", grad_bytes / 4, n_ranks)
        return SchedulePlan(
            mode="auto", grad_schedule="deferred", bucket_mb=bucket_mb,
            hoist=True, qgz=True, fallback=False,
            reason="quantized reduce already deferred; jaxpr hoist only",
            wire_bytes=wire, est_exposed_s=exposed(wire, 1))

    candidates = []
    # per-microbatch: GSPMD issues one reduction per scan step -- gas
    # issues, gas x the wire bytes, each overlappable with the next
    # microbatch's backward except the last
    per_mb_wire = plain_wire_bytes("all_reduce", grad_bytes, n_ranks) * gas
    candidates.append(("per_microbatch", exposed(per_mb_wire, gas),
                       per_mb_wire))
    if deferred_allowed:
        one_issue_wire = plain_wire_bytes("all_reduce", grad_bytes, n_ranks)
        options = {0.0, 4.0, 16.0}
        if bucket_mb > 0:
            options.add(float(bucket_mb))
        for bmb in sorted(options):
            k = _bucket_count(grad_bytes, bmb)
            candidates.append((f"deferred[bucket_mb={bmb:g}]",
                               exposed(one_issue_wire, k), one_issue_wire))

    # least exposed comm wins; wire bytes break ties, then deferred beats
    # per-microbatch (at gas=1 the two are identical -- planning deferred
    # keeps auto on the manual path's exact schedule)
    best = min(candidates, key=lambda c: (
        c[1], c[2], 0 if c[0].startswith("deferred") else 1))
    name, est_exp, wire = best
    if name.startswith("deferred"):
        chosen_bmb = float(name.split("=", 1)[1].rstrip("]"))
        return SchedulePlan(
            mode="auto", grad_schedule="deferred", bucket_mb=chosen_bmb,
            hoist=True, qgz=False, fallback=False,
            reason=f"deferred issue cuts wire bytes {gas}x vs per-microbatch",
            wire_bytes=wire, est_exposed_s=est_exp,
            candidates=tuple(candidates))
    reason = ("per-microbatch issue + jaxpr hoist"
              + (f" (deferred blocked: {'; '.join(blockers)})"
                 if blockers else ""))
    return SchedulePlan(
        mode="auto", grad_schedule="per_microbatch", bucket_mb=0.0,
        hoist=True, qgz=False, fallback=False, reason=reason,
        wire_bytes=wire, est_exposed_s=est_exp, candidates=tuple(candidates))


# --------------------------------------------------------------- step wrap

class ScheduledStepFn:
    """Drop-in replacement for ``jax.jit(step_fn, **jit_kwargs)`` that runs
    the hoist pass over the traced step before compiling.

    Lazy: the first call (or ``.lower``) traces ``fn`` with
    ``jax.make_jaxpr``, rewrites the jaxpr, and jits a replay of the
    rewritten program.  The replay evaluates the *same* eqns in a
    dependence-preserving order, so results are bit-exact vs the unwrapped
    jit.  Exposes ``.lower`` (telemetry HLO cost analysis) and the pass's
    stats (``n_collectives``, ``n_hoisted``, ``sites``).
    """

    def __init__(self, fn, jit_kwargs=None, label="step",
                 plan_memory=False):
        self._fn = fn
        self._jit_kwargs = dict(jit_kwargs or {})
        self._label = label
        self._plan_memory = plan_memory
        self._jitted = None
        self.n_collectives = 0
        self.n_hoisted = 0
        self.sites = ()
        self.move_sites = ()      # comm/memplan.py gather/release plan

    def _build(self, args):
        closed, out_shape = jax.make_jaxpr(
            self._fn, return_shape=True)(*args)
        out_tree = jax.tree_util.tree_structure(out_shape)
        sites = find_collectives(closed)
        new_closed, n_hoisted = hoist_collectives(closed)
        self.sites = tuple(sites)
        self.n_collectives = sum(1 for s in sites if s.kind != "implicit")
        self.n_hoisted = n_hoisted
        if self._plan_memory:
            # memory planner: gather/release point per step input (the
            # ZeRO-3 shards are among them); pure analysis over the same
            # trace -- XLA already places the gathers, the plan makes the
            # placement visible/scoreable (engine telemetry + benches)
            from .memplan import plan_param_movement

            self.move_sites = tuple(plan_param_movement(closed))

        def run(*call_args):
            flat = jax.tree_util.tree_leaves(call_args)
            out_flat = jax.core.eval_jaxpr(
                new_closed.jaxpr, new_closed.consts, *flat)
            return jax.tree_util.tree_unflatten(out_tree, out_flat)

        self._jitted = jax.jit(run, **self._jit_kwargs)
        logger.info(
            f"comm.schedule[{self._label}]: {self.n_collectives} collective "
            f"eqns ({sum(1 for s in sites if s.kind == 'implicit')} implicit "
            f"GSPMD sites), {n_hoisted} hoisted to earliest issue point")

    def __call__(self, *args):
        if self._jitted is None:
            self._build(args)
        return self._jitted(*args)

    def lower(self, *args):
        if self._jitted is None:
            self._build(args)
        return self._jitted.lower(*args)


# ------------------------------------------------------------ process state

# active schedule mode for env_report / tooling (last engine init wins)
_ACTIVE_MODE = None


def set_active_mode(mode):
    global _ACTIVE_MODE
    _ACTIVE_MODE = mode


def get_active_mode():
    """The process's active ``comm.overlap.schedule.mode`` (None before any
    engine initialized)."""
    return _ACTIVE_MODE

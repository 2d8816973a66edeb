"""Whole-graph memory planning: when does every byte of parameter state move.

PR 10's scheduling pass (``comm/schedule.py``) decides when collectives
*issue*; this module extends the same cost-model-driven planning to memory
movement, the DeepCompile move (PAPERS.md): ZeRO-3 param gather/release
placement and the host-offload chunk stream (``runtime/zero/infinity.py``)
are *planned* against the shared ``telemetry/wire.py`` ICI/PCIe model
instead of statically placed.

Three planners, all pure host-side math (safe to call at engine init):

* :func:`plan_param_movement` -- walk a traced step jaxpr and assign each
  parameter input a **gather point** (earliest consumer minus a lookahead
  window, so the gather's collective can issue while upstream compute
  runs) and a **release point** (last consumer -- the eqn after which the
  gathered buffer is dead).  This is the analysis DeepCompile performs on
  the fx graph, re-expressed over jaxpr eqn indices; the GSPMD stage-3
  path consumes it as telemetry/verification (XLA already places the
  gathers -- the plan makes the placement *visible* and scoreable), the
  offload engine consumes it as its actual schedule.
* :func:`plan_chunk_stream` -- the offload planner: given per-chunk byte
  sizes and an HBM budget, choose which chunks stay **resident** on device
  (skipping their per-pass host->device stream entirely) and how deep the
  issue-ahead **prefetch** runs for the rest.  The resident set grows
  greedily -- largest chunk first, each pin saves ``2 x passes`` transfers
  of its bytes -- until the modeled budget binds, then the remainder falls
  back to streaming.  Exposed transfer time is scored with
  ``telemetry/wire.py`` ``stream_exposed_estimate`` at the device's
  host-link bandwidth.
* :func:`assert_hbm_fit` -- the static-placement guard: raises
  :class:`HBMBudgetError` when a static residency requirement exceeds the
  (possibly synthetic) HBM budget -- the config that "OOMs under static
  ZeRO-3" in tests and benches, which the planner then trains via
  planned offload.

Calibration: the profile-once autotuner (``autotuning/autotuner.py``)
persists a measured ``compute_s`` and host-link bandwidth in its results
dir (:func:`save_calibration`); :func:`load_calibration` (path or
``DST_TUNER_CACHE``) feeds them back into ``plan_schedule`` scoring and
the chunk-stream planner, replacing the analytic fallbacks.

Wired behind ``comm.overlap.schedule.memory: "auto"|"static"|"off"``
(``runtime/engine.py``, ``ZeroInfinityEngine(memory_schedule=...)``).
Every planned variant is bit-exact vs the static placement: the plan only
moves *when* bytes move, never what is computed.
"""

import dataclasses
import json
import os
import time

from ..utils.logging import logger

#: default issue-ahead window (eqns) between a planned gather point and the
#: first consumer -- enough independent compute to hide a chunk H2D on the
#: host-link table without pinning more than one extra chunk
DEFAULT_LOOKAHEAD = 8

#: calibration file name inside an autotuner results dir (the tuner cache)
CALIBRATION_FILE = "calibration.json"

#: env var naming the tuner-cache path (file or dir) engines load
#: calibration from
CALIBRATION_ENV = "DST_TUNER_CACHE"


class HBMBudgetError(RuntimeError):
    """A static memory placement does not fit the (synthetic) HBM budget."""


def assert_hbm_fit(what, required_bytes, budget_bytes):
    """Raise :class:`HBMBudgetError` when ``required_bytes`` exceeds the
    budget (no-op for budget None/0: unbounded)."""
    if budget_bytes and required_bytes > budget_bytes:
        raise HBMBudgetError(
            f"{what}: static placement needs "
            f"{required_bytes / 2**20:.1f} MiB resident but the HBM budget "
            f"is {budget_bytes / 2**20:.1f} MiB -- enable the memory "
            f"planner (comm.overlap.schedule.memory: auto) to stream it")


# ------------------------------------------------------- gather/release plan

@dataclasses.dataclass
class MoveSite:
    """One planned parameter movement: gather before first use, release
    after last use."""

    name: str            # input label (flat arg position or leaf path)
    nbytes: int          # gathered (device-resident) byte size
    first_use: int       # eqn index of the earliest consumer
    last_use: int        # eqn index of the last consumer
    gather_at: int       # planned gather issue point (first_use - lookahead)
    release_at: int      # planned release point (== last_use)

    @property
    def live_span(self):
        """Eqn-index span the gathered buffer stays resident."""
        return self.release_at - self.gather_at + 1


def plan_param_movement(closed_jaxpr, param_indices=None,
                        lookahead=DEFAULT_LOOKAHEAD, min_bytes=0):
    """Earliest-use / last-use movement plan for a traced step's inputs.

    Walks the top-level eqn list of ``closed_jaxpr`` (consumption inside a
    sub-jaxpr counts at the enclosing eqn's index -- the issue point XLA
    sees) and returns one :class:`MoveSite` per (selected) input var:
    gather at ``max(0, first_use - lookahead)``, release at ``last_use``.
    ``param_indices`` restricts to those flat input positions (None = all
    array inputs); ``min_bytes`` drops small leaves (persistence-threshold
    analog).  Inputs with no consumer are skipped (nothing to move).
    """
    import numpy as np
    from jax.extend import core as jax_core

    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    first, last = {}, {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, jax_core.Literal):
                continue
            first.setdefault(v, i)
            last[v] = i
    sites = []
    sel = set(param_indices) if param_indices is not None else None
    for pos, v in enumerate(jaxpr.invars):
        if sel is not None and pos not in sel:
            continue
        if v not in first:
            continue
        aval = getattr(v, "aval", None)
        shape = getattr(aval, "shape", ()) or ()
        dtype = getattr(aval, "dtype", None)
        nbytes = int(np.prod(shape, dtype=np.int64)
                     * (np.dtype(dtype).itemsize if dtype is not None else 4))
        if nbytes < min_bytes:
            continue
        sites.append(MoveSite(
            name=f"arg{pos}", nbytes=nbytes,
            first_use=first[v], last_use=last[v],
            gather_at=max(0, first[v] - lookahead), release_at=last[v]))
    return sites


def movement_summary(sites):
    """Aggregate a :func:`plan_param_movement` result for logging/telemetry:
    total gathered bytes, the peak concurrently-live bytes under the
    planned gather/release points, and the mean live span."""
    if not sites:
        return {"n_sites": 0, "gathered_bytes": 0, "peak_live_bytes": 0,
                "mean_live_span": 0.0}
    events = []
    for s in sites:
        events.append((s.gather_at, s.nbytes))
        events.append((s.release_at + 1, -s.nbytes))
    live = peak = 0
    for _, delta in sorted(events, key=lambda e: (e[0], -e[1])):
        live += delta
        peak = max(peak, live)
    return {
        "n_sites": len(sites),
        "gathered_bytes": sum(s.nbytes for s in sites),
        "peak_live_bytes": peak,
        "mean_live_span": sum(s.live_span for s in sites) / len(sites),
    }


# ----------------------------------------------------------- chunk streaming

@dataclasses.dataclass
class MemoryPlan:
    """The planner's decision for one engine's parameter-movement schedule."""

    mode: str                   # "auto" (planned) | "static"
    resident: tuple             # unit names pinned on device across steps
    streamed: tuple             # unit names streamed per use
    prefetch_depth: int         # issue-ahead H2D transfers for streamed units
    resident_bytes: int         # bytes the resident set pins
    peak_bytes: int             # modeled peak device param residency
    hbm_budget_bytes: int       # the budget planned against (0 = unbounded)
    est_exposed_s: float        # modeled exposed (unhidden) transfer seconds
    est_static_exposed_s: float  # same model, static placement (depth 1,
    #                              nothing resident) -- the planned-vs-static
    #                              headroom claim
    reason: str                 # one-line human-readable rationale
    sites: tuple = ()           # optional MoveSites (jaxpr-derived plans)

    @property
    def tag(self):
        return (f"memplan[{len(self.resident)}r/"
                f"{len(self.streamed)}s d{self.prefetch_depth}]")

    def describe(self):
        return (f"{self.tag} resident {self.resident_bytes / 2**20:.2f} MiB, "
                f"peak {self.peak_bytes / 2**20:.2f} MiB"
                + (f" / budget {self.hbm_budget_bytes / 2**20:.2f} MiB"
                   if self.hbm_budget_bytes else "")
                + f", est exposed {self.est_exposed_s * 1e3:.3f} ms "
                f"(static {self.est_static_exposed_s * 1e3:.3f} ms) -- "
                f"{self.reason}")


def plan_chunk_stream(unit_bytes, *, hbm_budget_bytes=None,
                      compute_s_per_chunk=None, h2d_bytes_per_s=None,
                      working_bytes=0, passes=2, max_depth=4,
                      device_kind=None):
    """Plan the offload chunk stream: residency vs streaming vs prefetch.

    ``unit_bytes`` maps unit name -> device byte size (the ZeRO-Infinity
    chunks plus embed/head).  The model: a streamed unit crosses the host
    link ``passes`` times per step (fwd + bwd recompute); a resident unit
    never does but pins its bytes.  Peak residency is

        sum(resident) + (1 + depth) * max(streamed) + working_bytes

    (the unit in use plus ``depth`` issue-ahead transfers in flight).  The
    planner greedily pins the largest streamed unit -- biggest transfer
    saving per pin, and shrinking ``max(streamed)`` compounds the win --
    while that peak fits the budget, then picks the smallest ``depth``
    whose issue-ahead window hides a chunk transfer under the calibrated
    (or analytic) compute time.  No budget (None/0) means plan overlap
    only: nothing resident, depth from the cost model.  Raises
    :class:`HBMBudgetError` when even one streamed chunk with no lookahead
    exceeds the budget.
    """
    from ..telemetry.wire import host_link_bandwidth, stream_exposed_estimate

    units = {str(k): int(v) for k, v in unit_bytes.items()}
    if not units:
        raise ValueError("plan_chunk_stream: no units to plan")
    if h2d_bytes_per_s is None:
        if device_kind is None:
            from ..telemetry.hlo_cost import device_peaks

            device_kind = device_peaks()[2]
        h2d_bytes_per_s = host_link_bandwidth(device_kind)
    budget = int(hbm_budget_bytes or 0)

    def depth_for(streamed_names):
        if not streamed_names:
            return 0
        if compute_s_per_chunk is None or compute_s_per_chunk <= 0:
            return 1
        worst = max(units[n] for n in streamed_names) / h2d_bytes_per_s
        import math

        return max(1, min(max_depth, math.ceil(worst / compute_s_per_chunk)))

    def peak(resident_names, streamed_names, depth):
        worst = max((units[n] for n in streamed_names), default=0)
        return (sum(units[n] for n in resident_names)
                + (1 + depth) * worst + working_bytes)

    # largest-first: both the transfer saving and the max(streamed) shrink
    by_size = sorted(units, key=lambda n: (-units[n], n))
    resident, streamed = [], list(by_size)
    if budget:
        while streamed:
            candidate = streamed[0]  # current largest streamed unit
            trial_res = resident + [candidate]
            trial_str = streamed[1:]
            d = depth_for(trial_str)
            if peak(trial_res, trial_str, d) <= budget:
                resident, streamed = trial_res, trial_str
            else:
                break
    depth = depth_for(streamed)
    # budget binds harder than the overlap-optimal depth: shed lookahead
    while budget and streamed and depth > 0 \
            and peak(resident, streamed, depth) > budget:
        depth -= 1
    pk = peak(resident, streamed, depth)
    if budget and pk > budget:
        raise HBMBudgetError(
            f"offload stream: even one {max(units.values()) / 2**20:.1f} MiB "
            f"chunk (+{working_bytes / 2**20:.1f} MiB working set) exceeds "
            f"the {budget / 2**20:.1f} MiB HBM budget; re-chunk the model")

    streamed_bytes = [units[n] for n in streamed] * max(passes, 1)
    exposed = stream_exposed_estimate(
        streamed_bytes, compute_s_per_chunk, h2d_bytes_per_s,
        depth=max(depth, 1))
    static_exposed = stream_exposed_estimate(
        [b for b in units.values()] * max(passes, 1),
        compute_s_per_chunk, h2d_bytes_per_s, depth=1)
    if not streamed:
        reason = "everything resident: HBM budget never binds"
    elif resident:
        reason = (f"resident set grew to {len(resident)} units before the "
                  f"budget bound; rest streams at depth {depth}")
    elif budget:
        reason = f"budget binds immediately; pure streaming at depth {depth}"
    else:
        reason = f"no budget given: overlap-only plan at depth {depth}"
    plan = MemoryPlan(
        mode="auto", resident=tuple(resident), streamed=tuple(streamed),
        prefetch_depth=depth, resident_bytes=sum(units[n] for n in resident),
        peak_bytes=pk, hbm_budget_bytes=budget, est_exposed_s=exposed,
        est_static_exposed_s=static_exposed, reason=reason)
    logger.info(f"comm.memplan: {plan.describe()}")
    return plan


def static_plan(unit_bytes, working_bytes=0):
    """The static placement expressed as a :class:`MemoryPlan` (everything
    streams, one NVMe prefetch, no issue-ahead H2D) -- the parity baseline
    and the ``describe()`` counterpart for benches."""
    units = {str(k): int(v) for k, v in unit_bytes.items()}
    worst = max(units.values(), default=0)
    return MemoryPlan(
        mode="static", resident=(), streamed=tuple(sorted(units)),
        prefetch_depth=0, resident_bytes=0,
        peak_bytes=2 * worst + working_bytes, hbm_budget_bytes=0,
        est_exposed_s=0.0, est_static_exposed_s=0.0,
        reason="static placement (parity baseline)")


# --------------------------------------------------------------- calibration

@dataclasses.dataclass
class Calibration:
    """One profile-once measurement, persisted in the tuner cache: the
    planner's compute and bandwidth terms, measured instead of analytic."""

    compute_s: float            # measured compute-only step seconds
    h2d_gbps: float = 0.0       # measured host->device GB/s (0 = unknown)
    device_kind: str = ""
    scale: float = 1.0          # measured/analytic step-time ratio
    step_time_s: float = 0.0    # the raw calibration step time
    timestamp: float = 0.0

    @property
    def h2d_bytes_per_s(self):
        return self.h2d_gbps * 1e9 if self.h2d_gbps > 0 else None


def save_calibration(results_dir, **fields):
    """Write the calibration record into the tuner cache (results dir);
    returns the file path."""
    os.makedirs(results_dir, exist_ok=True)
    cal = Calibration(timestamp=time.time(), **fields)
    path = os.path.join(results_dir, CALIBRATION_FILE)
    with open(path, "w") as f:
        json.dump(dataclasses.asdict(cal), f, indent=2)
    return path


def load_calibration(path=None):
    """Load a persisted :class:`Calibration`, or None.

    ``path`` may be the json file or the results dir holding it; default
    is the ``DST_TUNER_CACHE`` env var (unset -> None: engines fall back
    to the analytic model, never to a stale implicit location)."""
    path = path or os.environ.get(CALIBRATION_ENV)
    if not path:
        return None
    if os.path.isdir(path):
        path = os.path.join(path, CALIBRATION_FILE)
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return None
    known = {f.name for f in dataclasses.fields(Calibration)}
    return Calibration(**{k: v for k, v in raw.items() if k in known})


def measure_h2d_bandwidth(nbytes=8 << 20, iters=3):
    """Measured host->device bandwidth (bytes/s): time ``device_put`` of an
    ``nbytes`` buffer.  The autotuner's bandwidth-term calibration."""
    import numpy as np

    import jax

    buf = np.ones(max(int(nbytes), 1 << 16), np.uint8)
    jax.block_until_ready(jax.device_put(buf))  # warm the path
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(jax.device_put(buf))
    dt = (time.perf_counter() - t0) / iters
    return buf.nbytes / max(dt, 1e-9)


# ------------------------------------------------------------ process state

# active memory-schedule mode for env_report / tooling (last engine wins)
_ACTIVE_MEMORY_MODE = None


def set_active_memory_mode(mode):
    global _ACTIVE_MEMORY_MODE
    _ACTIVE_MEMORY_MODE = mode


def get_active_memory_mode():
    """The process's active ``comm.overlap.schedule.memory`` mode (None
    before any engine initialized)."""
    return _ACTIVE_MEMORY_MODE

"""How a batch's gradient is accumulated over its microbatches and reduced
over the data-parallel replicas.

One micro body (``micro_loss_and_grads``), one accumulate skeleton
(``accumulate``), one builder of the manual region the three hand-placed
reductions run in (``manual_region``), and four reductions that are only
their reduction:

=================  ===========================================  ==============
reduction          how the local sum becomes the gradient       carries
=================  ===========================================  ==============
``per_microbatch`` sharding constraint inside the scan; GSPMD   --
                   places the collective
``deferred``       bucketed ``psum`` / ``psum_scatter`` once    --
                   a batch (``comm.overlap``)
``onebit``         ``pmean`` in warm-up, then sign bits +       onebit_error
                   scale with error feedback (1-bit Adam)
``qgz``            hierarchical int8 ``all_reduce_quantized``   --
                   (ZeRO++ qgZ / ``comm.quantized``)
=================  ===========================================  ==============

``select`` makes the choice once, from what the engine can observe, and is
the only place that knows it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import comm as dist
from ..parallel import topology as topo
from ..utils.logging import log_dist, logger
from ..utils.tree import tree_cast, tree_size

BATCH_AXES = topo.BATCH_AXES


def wire_dtype(engine):
    """The dp reduction's dtype: ``communication_data_type``, else accum."""
    return engine.precision.reduce_dtype or engine.precision.accum_dtype


def n_replicas(engine):
    """Replicas a full dp reduction spans (dp x zshard x ep)."""
    return int(np.prod([engine.mesh.sizes[a] for a in BATCH_AXES]))


# --------------------------------------------------------------- micro body
def split_loss(loss):
    """A model's loss is a scalar, or ``(loss, {name: number})`` with what
    its step says of itself (a looped model's exit shares and counters)
    -> (loss, stats)."""
    stats = {}
    if isinstance(loss, tuple):
        if len(loss) > 1 and isinstance(loss[1], dict):
            stats = loss[1]
        loss = loss[0]
    return loss, stats


def micro_loss_and_grads(engine, params, microbatch, rng, scale,
                         ltd_tokens=None, wire=None):
    """One microbatch at compute params ``params`` -> (loss, grads of
    ``loss * scale``, the model's stats).  ``wire``: the dtype the grads
    are cast to here, before the caller's sharding constraint
    (communication_data_type, reference ``engine.py:1142-1144``): XLA places
    the psum/reduce-scatter where the sharded layout is demanded, so this
    cast sets the collective's wire dtype."""
    kw = {} if ltd_tokens is None else {"random_ltd_tokens": ltd_tokens}

    def scaled_loss(p):
        loss, stats = split_loss(engine._loss_fn(p, microbatch, rng, **kw))
        return (loss * scale).astype(jnp.float32), (loss, stats)

    (_, (loss, stats)), grads = jax.value_and_grad(
        scaled_loss, has_aux=True)(params)
    if wire is not None:
        with jax.named_scope("grad_accumulate"):
            grads = tree_cast(grads, wire)
    return loss, grads, stats


# ------------------------------------------------------ accumulate skeleton
def accumulate(engine, compute_params, accum_dtype, master, batch, rng, scale,
               ltd_tokens=None, step=None, carried=None, divisor=None,
               wire=None, constrain=None, record=None):
    """Sum the microbatches' grads in ``accum_dtype`` -> (the sum, over
    ``divisor`` if given; mean loss; the model's stats averaged over the
    microbatches).  ``compute_params(master, step=)`` forms compute params
    from the masters; ``constrain`` (per-microbatch reduction only) demands
    the reduced layout inside the scan; ``record(master)`` notes the wire at
    trace time.  The arguments from ``master`` on are the engine's
    ``_grads_for_batch`` contract, so a reduction that is nothing but this
    loop binds the rest with ``functools.partial`` and adds no Python frame
    between the step and the scan (what such a frame costs: PERF.md §6,
    PR 31)."""
    if record is not None:
        record(master)

    def micro(carry, mb):
        acc, i = carry
        sub_rng = jax.random.fold_in(rng, i)
        loss, grads, stats = micro_loss_and_grads(
            engine, compute_params(master, step=step), mb, sub_rng, scale,
            ltd_tokens=ltd_tokens, wire=wire)
        with jax.named_scope("grad_accumulate"):
            if constrain is not None:
                with jax.named_scope("zero3_reduce"):
                    grads = constrain(grads)
            grads = tree_cast(grads, accum_dtype)
            acc = jax.tree_util.tree_map(jnp.add, acc, grads)
        return (acc, i + 1), (loss, stats)

    with jax.named_scope("grad_accumulate"):
        zeros = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, accum_dtype), master)
        if constrain is not None:
            zeros = constrain(zeros)
    (gsum, _), (losses, stats) = jax.lax.scan(
        micro, (zeros, jnp.int32(0)), batch)
    if divisor is not None:
        with jax.named_scope("grad_accumulate"):
            gsum = jax.tree_util.tree_map(lambda g: g / divisor, gsum)
    stats = jax.tree_util.tree_map(
        lambda s: jnp.mean(s.astype(jnp.float32), axis=0), stats)
    return gsum, jnp.mean(losses), stats


def manual_region(engine, reduce_local, axes, master, batch, rng, scale,
                  accum_dtype, divisor=None, ltd_tokens=None, grad_specs=None,
                  extra=(), extra_specs=(), more_specs=()):
    """Run the skeleton on each replica's shard of the batch, at a plain
    cast of the masters, and hand the local sum to ``reduce_local(gsum,
    *extra) -> (grads, *more)`` -> (grads, loss, stats, *more), loss and
    stats averaged over ``axes``.

    Manual over ALL mesh axes, not just the reduced ones: a >1-size auto
    axis (sp/tp) alongside the manual-dp scan + collectives trips an
    SPMD-partitioner manual-subgroup check in this jax (hard abort).
    Non-dp operands are replicated, so full-manual is semantically
    identical."""
    def local_fn(master_l, batch_l, rng_l, scale_l, *extra_l):
        gsum, loss, stats = accumulate(
            engine, lambda m, step: engine.precision.cast_for_compute(
                m, engine._no_cast),
            accum_dtype, master_l, batch_l, rng_l, scale_l,
            ltd_tokens=ltd_tokens, divisor=divisor)
        grads, *more = reduce_local(gsum, *extra_l)
        return (grads, *jax.lax.pmean((loss, stats), axes), *more)

    def batch_spec(x):
        if x.ndim < 2:  # per-microbatch scalars (e.g. pld_theta)
            return P(*([None] * x.ndim))
        return P(*([None, axes] + [None] * (x.ndim - 2)))

    replicated = jax.tree_util.tree_map(lambda _: P(), master)
    fn = jax.shard_map(
        local_fn, mesh=engine.mesh.mesh,
        in_specs=(replicated, jax.tree_util.tree_map(batch_spec, batch),
                  P(), P(), *extra_specs),
        out_specs=(replicated if grad_specs is None else grad_specs,
                   P(), P(), *more_specs),
        axis_names=set(engine.mesh.mesh.axis_names), check_vma=False)
    return fn(master, batch, rng, scale, *extra)


# ------------------------------------------------------------- wire record
def is_reduce_plan_leaf(x):
    """Leaf predicate for ``zero.sharding.deferred_reduce_plan`` pytrees:
    ``(collective, scatter_dim, axes)`` triples."""
    return (isinstance(x, tuple) and len(x) == 3
            and x[0] in ("all_reduce", "reduce_scatter"))


def reduce_plan(engine, master):
    """Per-leaf (collective, dim, axes) of the dp grad reduction: what the
    deferred reduction executes and the wire record prices."""
    from .zero.sharding import ZERO_AXES, deferred_reduce_plan

    return deferred_reduce_plan(engine.plan.grad_specs, master, engine.mesh,
                                ZERO_AXES)


def record_plain_wire(engine, master, issues, tag, n_buckets=1):
    """Trace-time analytic record of a full-precision dp grad reduction (the
    one collective no ``comm/comm.py`` call mediates: GSPMD places it from a
    sharding constraint, or a manual psum/psum_scatter emits it).  Prices
    the actual schedule: per-leaf all-reduce vs reduce-scatter from the grad
    specs, issued ``issues`` times a step in ``n_buckets`` groups.  No-op
    unless the comms logger is capturing (first train_batch with telemetry
    enabled)."""
    if not dist.comms_logger._capturing:
        return
    n = n_replicas(engine)
    if n <= 1:
        return
    from ..telemetry.wire import plain_wire_bytes

    wire = wire_dtype(engine)
    nbytes = {"reduce_scatter": 0, "all_reduce": 0}
    for (kind, _, _), leaf in zip(
            jax.tree_util.tree_leaves(reduce_plan(engine, master),
                                      is_leaf=is_reduce_plan_leaf),
            jax.tree_util.tree_leaves(master)):
        nbytes[kind] += int(np.prod(leaf.shape)) * jnp.dtype(wire).itemsize
    total = issues * sum(plain_wire_bytes(kind, nb, n)
                         for kind, nb in nbytes.items())
    dist.comms_logger.record_traced(
        "grad_reduce_dp", total, n, variant=jnp.dtype(wire).name,
        count=issues * max(n_buckets, 1), schedule=tag)


# -------------------------------------------------------------- reductions
class Reduction:
    """What every reduction is to the engine: ``grads(master, batch, rng,
    scale, ltd_tokens=, step=, carried=) -> (grads, loss, stats)``, the
    state keys it ``carries`` across steps, and its telemetry ``tag``."""

    name, carries = None, ()

    def __init__(self, engine, plan=None):
        self.engine = engine
        self.plan = plan    # the planner's answer (``schedule.mode: auto``)
        # telemetry label of the grad-reduce schedule in effect
        self.tag = plan.tag if plan is not None else self.name

    def init_carried(self, master):
        """-> {state key: value}: what the reduction carries across steps."""
        return {}


class PerMicrobatch(Reduction):
    """The reduced layout is demanded inside the scan: GSPMD inserts a
    psum/reduce-scatter per microbatch in the wire dtype, and the sum runs
    in ``accum_dtype`` at the ZeRO grad placement, at compute params formed
    by ``engine._compute_params`` (placement, QAT, qwZ).  Carries nothing;
    runs with every feature; what every benchmark cell runs: ``grads`` IS
    the skeleton with its arguments bound."""

    name = "per_microbatch"

    def __init__(self, engine, plan=None):
        super().__init__(engine, plan)
        gas = engine.gradient_accumulation_steps()
        self.grads = functools.partial(
            accumulate, engine, engine._compute_params,
            engine.precision.accum_dtype, divisor=gas,
            wire=wire_dtype(engine),
            constrain=lambda g: jax.lax.with_sharding_constraint(
                g, engine.grad_shardings),
            record=lambda master: record_plain_wire(
                engine, master, gas, self.tag))


class Deferred(Reduction):
    """One dp reduction a batch, not one a microbatch: gas x fewer bytes on
    the wire.  Each replica sums its LOCAL grads in ``accum_dtype``; then
    ``psum_scatter`` (leaves whose grad spec is dp-sharded: stage 2/3
    kernels) and ``psum`` (the rest) realize the ZeRO grad layout in the
    wire dtype, in ``bucket_mb`` leaf groups issued in leaf order so the
    first buckets overlap the tail of backward; a bucket's psum leaves fuse
    into one flattened collective.  The local loss is a mean over the LOCAL
    shard, so dividing by ``gas * n_dp`` before the psum recovers the
    per-microbatch result up to summation order.  Carries nothing; cannot
    run where parallelism lives in GSPMD constraints (``deferred_blockers``)."""

    name = "deferred"

    def grads(self, master, batch, rng, scale, ltd_tokens=None, step=None,
              carried=None):
        from ..comm.overlap import bucketize

        e = self.engine
        reduce_axes = tuple(a for a in BATCH_AXES if e.mesh.sizes[a] > 1)
        inv = 1.0 / (e.gradient_accumulation_steps() * n_replicas(e))
        wire = wire_dtype(e)
        acc_dt = e.precision.accum_dtype
        plan = reduce_plan(e, master)
        plan_flat = jax.tree_util.tree_leaves(plan,
                                              is_leaf=is_reduce_plan_leaf)
        buckets = bucketize(
            [int(np.prod(l.shape)) * jnp.dtype(wire).itemsize
             for l in jax.tree_util.tree_leaves(master)],
            self.plan.bucket_mb if self.plan is not None
            else e.config.comm.overlap.bucket_mb)
        record_plain_wire(e, master, 1, self.tag, n_buckets=len(buckets))

        @jax.named_scope("zero3_reduce")
        def reduce_local(gsum):
            flat, gdef = jax.tree_util.tree_flatten(gsum)
            out = [(g * inv).astype(wire) for g in flat]
            for bucket in buckets:
                ar = [i for i in bucket if plan_flat[i][0] == "all_reduce"]
                if ar:
                    vec = jax.lax.psum(jnp.concatenate(
                        [out[i].reshape(-1) for i in ar]), reduce_axes)
                    splits = np.cumsum([flat[i].size for i in ar])[:-1]
                    for i, piece in zip(ar, jnp.split(vec, splits)):
                        out[i] = piece.reshape(flat[i].shape)
                for i in (i for i in bucket if i not in ar):
                    _, dim, axes = plan_flat[i]
                    out[i] = jax.lax.psum_scatter(
                        out[i], axes if len(axes) > 1 else axes[0],
                        scatter_dimension=dim, tiled=True)
                    # grad-spec axes may be a subgroup (MiCS/hpZ): finish
                    # the reduction over the remaining batch axes
                    rest = tuple(a for a in reduce_axes if a not in axes)
                    if rest:
                        out[i] = jax.lax.psum(out[i], rest)
            return (jax.tree_util.tree_unflatten(
                gdef, [g.astype(acc_dt) for g in out]),)

        def grad_spec(p, leaf):
            kind, dim, axes = p
            if kind != "reduce_scatter":
                return P()
            entry = axes if len(axes) > 1 else axes[0]
            return P(*[entry if d == dim else None for d in range(leaf.ndim)])

        grads, loss, stats = manual_region(
            e, reduce_local, reduce_axes, master, batch, rng, scale, acc_dt,
            ltd_tokens=ltd_tokens, grad_specs=jax.tree_util.tree_map(
                grad_spec, plan, master, is_leaf=is_reduce_plan_leaf))
        # realize the engine's grad layout (free: psum leaves are
        # replicated, scatter leaves already landed sharded)
        return (jax.lax.with_sharding_constraint(grads, e.grad_shardings),
                loss, stats)


class OneBit(Reduction):
    """1-bit Adam (reference ``compressed_allreduce`` ``runtime/comm/
    nccl.py:51`` + ``onebit/adam.py``): the local update stays exact Adam;
    the dp reduction of the float32 local mean is ``lax.pmean`` before
    ``freeze_step`` and ``onebit_all_reduce`` (sign bits + scale) after.
    Carries ``onebit_error``, each replica's error feedback under a leading
    dp axis (volatile: reset on checkpoint resume, like the reference's
    worker/server error buffers); ``comm.compressed`` records its wire.
    Needs replicated masters (ZeRO stage 0) and no fp16 loss scaling."""

    name, carries = "onebit", ("onebit_error",)

    def init_carried(self, master):
        mesh = self.engine.mesh
        return {"onebit_error": jax.tree_util.tree_map(
            lambda p: jax.device_put(
                jnp.zeros((mesh.dp, *p.shape), jnp.float32),
                NamedSharding(mesh.mesh, P(topo.DP_AXIS, *([None] * p.ndim)))),
            master)}

    def grads(self, master, batch, rng, scale, ltd_tokens=None, step=None,
              carried=None):
        from ..comm.compressed import onebit_all_reduce

        e = self.engine
        freeze = e.config.optimizer.params.freeze_step
        error = carried["onebit_error"]

        def reduce_local(gmean, error_l, step_l):
            pairs = jax.tree_util.tree_map(
                lambda g, err: jax.lax.cond(
                    step_l < freeze,
                    lambda a: (jax.lax.pmean(a[0], topo.DP_AXIS), a[1]),
                    lambda a: onebit_all_reduce(a[0], topo.DP_AXIS, a[1]),
                    (g, err[0])), gmean, error_l)
            return tuple(jax.tree_util.tree_map(
                pick, pairs, is_leaf=lambda x: isinstance(x, tuple))
                for pick in (lambda r: r[0], lambda r: r[1][None]))

        err_spec = jax.tree_util.tree_map(
            lambda x: P(topo.DP_AXIS, *([None] * (x.ndim - 1))), error)
        grads, loss, stats, carried["onebit_error"] = manual_region(
            e, reduce_local, (topo.DP_AXIS,), master, batch, rng, scale,
            jnp.float32, divisor=e.gradient_accumulation_steps(),
            ltd_tokens=ltd_tokens, extra=(error, step),
            extra_specs=(err_spec, P()), more_specs=(err_spec,))
        return grads, loss, stats


class Qgz(Reduction):
    """ZeRO++ qgZ (``zero_quantized_gradients`` / ``comm.quantized``): the
    dp mean of the float32 local mean runs ``comm.all_reduce_quantized``'s
    hierarchical int8 schedule (quantize -> intra (zshard) reduce-scatter
    -> requantize -> inter (dp) reduce -> quantized all-gathers), which
    records its wire.  Leaves below one quantization group per participant
    take an exact pmean: their int8 error is largest, their wire cost
    negligible.  Under ``comm.overlap`` the reduces fuse into ``bucket_mb``
    flattened collectives.  Carries nothing; needs replicated masters
    (stage 0) and no fp16 loss scaling; zshard composes (the intra hop)."""

    name = "qgz"

    def grads(self, master, batch, rng, scale, ltd_tokens=None, step=None,
              carried=None):
        from ..comm.comm import CommGroup, ReduceOp, all_reduce_quantized
        from ..comm.overlap import bucketize
        from .zero.quantized import fused_flat_reduce

        e = self.engine
        cq, overlap = e.config.comm.quantized, e.config.comm.overlap
        axes = ((topo.DP_AXIS, topo.ZSHARD_AXIS) if e.mesh.zshard > 1
                else (topo.DP_AXIS,))
        group = CommGroup(axes)
        intra = CommGroup((cq.intra_axis,)) if cq.intra_axis else None
        min_elems = cq.group_size * group.size()

        def exact(v):
            return jax.lax.pmean(v, axes)

        def quantized(v):
            return all_reduce_quantized(
                v, op=ReduceOp.AVG, group=group, intra_group=intra,
                group_size=cq.group_size, impl=cq.impl,
                wire_dtype=cq.wire_dtype)

        def reduce_local(gmean):
            if not overlap.enabled:
                return (jax.tree_util.tree_map(
                    lambda g: (exact if g.size < min_elems else quantized)(g),
                    gmean),)
            flat, gdef = jax.tree_util.tree_flatten(gmean)
            small = [i for i, g in enumerate(flat) if g.size < min_elems]
            large = [i for i, g in enumerate(flat) if g.size >= min_elems]
            # sub-granule leaves fuse into ONE exact pmean
            groups = [(small, exact)] if small else []
            groups += [([large[j] for j in b], quantized) for b in bucketize(
                [flat[i].size * 4 for i in large], overlap.bucket_mb)]
            for idx, fn in groups:
                for i, r in zip(idx, fused_flat_reduce(
                        [flat[i] for i in idx], fn)):
                    flat[i] = r
            return (jax.tree_util.tree_unflatten(gdef, flat),)

        return manual_region(
            e, reduce_local, axes, master, batch, rng, scale, jnp.float32,
            divisor=e.gradient_accumulation_steps(), ltd_tokens=ltd_tokens)


# --------------------------------------------------------------- selection
def _wants_onebit(engine):
    """Like the reference, 1-bit Adam is incompatible with ZeRO (needs
    replicated masters) and fp16 loss scaling; pointless without dp."""
    if engine.optimizer_name != "onebitadam":
        return False
    mesh = engine.mesh
    if engine.config.zero_config.stage > 0:
        raise ValueError("onebitadam requires zero stage 0 "
                         "(reference: 1-bit Adam does not compose "
                         "with ZeRO partitioning)")
    if engine.precision.is_fp16:
        raise ValueError("onebitadam supports fp32/bf16 only")
    # sp OR tp compose (operands replicated over them; only the dp axis --
    # the slow/DCN link 1-bit exists for -- is sign-compressed).  ep/zshard
    # conflict: MoE routing and MiCS/hpZ subgrouping assume the ZeRO
    # reduction paths this loop bypasses.
    if mesh.ep > 1 or mesh.zshard > 1:
        raise ValueError("onebitadam compresses over the dp axis; "
                         "ep/zshard must be 1 (sp or tp compose)")
    if mesh.sp > 1 and mesh.tp > 1:
        # XLA's SPMD partitioner CHECK-fails expanding device groups for a
        # manual-dp region with BOTH sp and tp auto axes
        # (spmd_partitioner_util.cc:495 in this build); each works alone
        raise NotImplementedError(
            "onebitadam supports sp OR tp alongside dp, not both "
            "(XLA SPMD device-group expansion limitation)")
    if mesh.dp == 1:
        logger.warning("onebitadam: dp=1, nothing to compress; "
                       "running plain Adam")
        return False
    return True


def _wants_qgz(engine, onebit):
    cq, zc = engine.config.comm.quantized, engine.config.zero_config
    mesh = engine.mesh
    qgz = bool(cq.enabled)
    if zc.zero_quantized_gradients and not qgz:
        if zc.stage == 0:
            qgz = True
        else:
            # GSPMD emits the stage>=1 grad reduce-scatter itself; the
            # manual qgZ loop needs replicated masters.  Accept the
            # reference flag without failing stage 1-3 configs.
            logger.warning(
                "zero_quantized_gradients: the manual qgZ grad loop "
                "requires stage 0 (stage %d keeps the GSPMD reduction); "
                "ignoring", zc.stage)
    if not qgz:
        return False
    if onebit:
        raise ValueError("comm.quantized and onebitadam are mutually "
                         "exclusive gradient compressions")
    if cq.enabled and zc.stage > 0:
        raise ValueError(
            "comm.quantized requires zero stage 0: the manual "
            "dp-loop needs replicated masters (stage>=1 reductions "
            "are emitted by GSPMD)")
    if engine.precision.is_fp16:
        raise ValueError("comm.quantized supports fp32/bf16 only")
    if mesh.ep > 1:
        raise ValueError("comm.quantized: ep must be 1 (MoE routing "
                         "assumes the GSPMD reduction paths)")
    if mesh.sp > 1 and mesh.tp > 1:
        raise NotImplementedError(
            "comm.quantized supports sp OR tp alongside dp, not both "
            "(XLA SPMD device-group expansion limitation)")
    if mesh.dp * mesh.zshard == 1:
        logger.warning("comm.quantized: dp*zshard=1, nothing to "
                       "quantize; running plain reduction")
        return False
    return True


def deferred_blockers(engine):
    """Why the deferred reduction cannot run here: its loop is manual over
    dp, model compute runs locally per dp shard, so any axis whose
    parallelism lives in GSPMD sharding constraints (tp/sp/ep/pp) would
    silently replicate compute instead."""
    mesh, blockers = engine.mesh, []
    if mesh.tp > 1 or mesh.sp > 1 or mesh.pp > 1:
        blockers.append("tp/sp/pp > 1 (manual-dp loop would "
                        "replicate model-parallel compute)")
    if mesh.ep > 1:
        blockers.append("ep > 1 (MoE routing needs the GSPMD paths)")
    if engine._compression is not None:
        blockers.append("compression_training (QAT transform runs "
                        "on the GSPMD compute path)")
    if engine._qwz:
        blockers.append("zero_quantized_weights (quantized weight "
                        "regather needs GSPMD resharding)")
    return blockers


def select(engine):
    """The reduction in effect, chosen once from config, mesh sizes,
    precision and -- under ``comm.overlap.schedule.mode: auto`` -- the
    planner's answer (``comm/schedule.py``)."""
    ov = engine.config.comm.overlap
    onebit = _wants_onebit(engine)
    qgz = _wants_qgz(engine, onebit)
    if onebit or qgz:
        # the compressed reductions form compute params with a bare cast and
        # are refused an LTD budget: combining silently would fake those
        # features (the compiled pipeline's NotImplementedErrors likewise)
        which = "onebitadam" if onebit else "comm.quantized"
        if engine._compression is not None:
            raise NotImplementedError(
                f"{which} + compression_training is not supported (the "
                "compressed-reduction path bypasses the QAT transform)")
        if engine.random_ltd_scheduler is not None:
            raise NotImplementedError(
                f"{which} + random-LTD is not supported")
    blockers = deferred_blockers(engine)
    # the 1-bit/qgZ loops already reduce once per batch
    deferrable = (ov.enabled and ov.deferred_reduction
                  and ov.schedule.mode != "off" and not onebit and not qgz)
    eligible = (deferrable and not blockers
                and engine.mesh.dp * engine.mesh.zshard > 1)
    plan = None
    if ov.enabled and ov.schedule.mode == "auto":
        # score the grad-reduce schedule candidates with the wire/ICI cost
        # model; blocked regimes get a PLANNED per-microbatch + jaxpr-hoist
        # schedule, not a fallback warning
        from ..comm import memplan, schedule as comm_schedule

        # one profiled step, persisted by the autotuner in the tuner cache,
        # replaces the planner's analytic compute term when present
        cal = memplan.load_calibration()
        plan = comm_schedule.plan_schedule(
            grad_bytes=(tree_size(engine.state["master_params"])
                        * jnp.dtype(wire_dtype(engine)).itemsize),
            gas=engine.gradient_accumulation_steps(),
            n_ranks=n_replicas(engine), deferred_allowed=eligible,
            blockers=tuple(blockers), bucket_mb=ov.bucket_mb,
            qgz=qgz or onebit,
            compute_s=(cal.compute_s if cal is not None
                       and cal.compute_s > 0 else None))
        eligible = eligible and plan.grad_schedule == "deferred"
        log_dist("comm.schedule[auto]: " + plan.describe(), ranks=[0])
    elif deferrable and blockers:    # manual mode: say so once
        from ..utils.logging import warning_once

        warning_once(
            "comm.overlap.deferred_reduction disabled: "
            + "; ".join(blockers)
            + " -- falling back to the per-microbatch reduction "
            "schedule (comm.overlap.schedule.mode=auto plans these "
            "regimes instead)")
    if onebit:
        return OneBit(engine, plan)
    if qgz:
        return Qgz(engine, plan)
    if eligible:
        return Deferred(engine, plan)
    return PerMicrobatch(engine, plan)

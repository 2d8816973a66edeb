"""DeeperSpeedEngine: the training engine.

Equivalent of reference ``runtime/engine.py:175`` (``DeepSpeedEngine``), but
architected TPU-first: instead of an eager wrapper that hooks autograd and
hand-schedules NCCL, the engine compiles ONE sharded train step --
microbatch ``lax.scan`` (grad accumulation), mixed-precision master update,
on-device dynamic loss scaling, ZeRO placement via sharding specs -- and XLA
schedules every collective over ICI.

API parity with the reference where user-visible:
``forward/backward/step`` (``engine.py:1775,1916,2114``),
``train_batch/eval_batch`` (pipeline engine names, ``pipe/engine.py:312,396``),
``save_checkpoint/load_checkpoint`` (``engine.py:3029,2675``), property
surface (lr, loss scale, batch sizes, counters).
"""

import os
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import comm as dist
from ..accelerator import get_accelerator
from ..monitor.monitor import MonitorMaster
from ..parallel import topology as topo
from ..telemetry.trace import (TraceSessionWatch, compile_stats,
                               describe_time_to_first_step,
                               publish_kernel_passes, publish_step_counters,
                               publish_step_scopes, span, step_scopes,
                               step_span, time_to_first_step)
from ..utils.logging import log_dist, logger
from ..utils.timer import (
    BACKWARD_GLOBAL_TIMER,
    FORWARD_GLOBAL_TIMER,
    STEP_GLOBAL_TIMER,
    TRAIN_BATCH_TIMER,
    SynchronizedWallClockTimer,
    ThroughputTimer,
)
from ..utils.tree import tree_cast, tree_global_norm, tree_size, tree_zeros_like
from . import grad_reduce
from .config import DeeperSpeedConfig
from .lr_schedules import get_lr_schedule_fn
from .optimizers import build_optimizer
from .precision import (
    LossScaleState,
    MixedPrecisionPolicy,
    has_inf_or_nan,
    init_loss_scale,
    update_loss_scale,
)
from .zero.sharding import build_sharding_plan

BATCH_AXES = topo.BATCH_AXES


def _clip_by_global_norm(grads, norm, clip):
    """Scale grads so their global norm is at most ``clip`` (one shared
    definition for the fused, legacy-apply, and host-update paths)."""
    if clip <= 0:
        return grads
    coef = jnp.minimum(1.0, clip / (norm + 1e-6))
    return jax.tree_util.tree_map(lambda g: g * coef, grads)


def _named(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree, is_leaf=lambda x: isinstance(x, P)
    )


class DeeperSpeedEngine:
    def __init__(
        self,
        model,
        config,
        optimizer=None,            # optax GradientTransformation override
        model_parameters=None,     # pre-initialized param pytree
        loss_fn: Optional[Callable] = None,
        training_data=None,
        collate_fn=None,
        lr_scheduler=None,         # schedule fn(step)->lr override
        mesh: Optional[topo.MeshTopology] = None,
        mpu=None,                  # accepted for API parity; mesh supersedes it
        dont_change_device=False,
    ):
        if not isinstance(config, DeeperSpeedConfig):
            config = DeeperSpeedConfig(config, mesh=mesh)
        self.config = config
        self.module = model
        self.accelerator = get_accelerator()

        dist.init_distributed()

        # ---- mesh
        if mesh is None:
            mc = config.mesh_config
            zc = config.zero_config
            # MiCS/hpZ subgroup degree becomes the zshard axis; both features
            # share the axis so conflicting sizes are rejected (the reference
            # keeps distinct groups, but combining them is unsupported there
            # too)
            mics = zc.mics_shard_size if zc.mics_shard_size > 1 else 1
            hpz = (zc.zero_hpz_partition_size
                   if zc.zero_hpz_partition_size > 1 else 1)
            if mics > 1 and hpz > 1 and mics != hpz:
                raise ValueError(
                    f"mics_shard_size={mics} conflicts with "
                    f"zero_hpz_partition_size={hpz}: both map to the zshard "
                    "mesh axis and must agree")
            zshard = max(mics, hpz)
            mesh = topo.MeshTopology(
                pp=mc.pipe_parallel_size, tp=mc.model_parallel_size,
                sp=mc.sequence_parallel_size, ep=mc.expert_parallel_size,
                dp=mc.data_parallel_size, zshard=zshard,
            )
        self.mesh = mesh
        topo.set_mesh(mesh)
        # keep the batch triangle consistent with the actual mesh
        self.config.recompute_batch_params(mesh.data_parallel_size)

        # ---- activation checkpointing (reference
        # ``activation_checkpointing/checkpointing.py``): any requested
        # option turns on block-level rematerialization -- the saved block
        # inputs carry the model's dp/sp sharding constraints, which IS the
        # partitioned-activations memory shape; cpu_checkpointing maps to
        # device remat (recompute beats PCIe round-trips on TPU).
        ac = config.activation_checkpointing
        if ((ac.partition_activations or ac.number_checkpoints
             or ac.cpu_checkpointing)
                and hasattr(model, "config")
                and getattr(model.config, "remat", None) is False):
            import dataclasses as _dc

            if ac.cpu_checkpointing:
                logger.warning("activation_checkpointing.cpu_checkpointing: "
                               "mapped to on-device rematerialization")
            model = model.clone(config=_dc.replace(model.config, remat=True))
            self.module = model
            log_dist("activation checkpointing: block remat enabled",
                     ranks=[0])

        # ---- precision + loss fn
        self.precision = MixedPrecisionPolicy(config)
        if loss_fn is None:
            if hasattr(model, "loss_fn"):
                loss_fn = model.loss_fn()
            elif not self._builds_own_loss():
                raise ValueError("pass loss_fn= or use a model exposing .loss_fn()")
        self._loss_fn = loss_fn

        # ---- init params (master copy, fp32 when mixed)
        self._rng = jax.random.PRNGKey(config.seed)
        master_abstract, self._init_fn, self._init_args = self._make_init(
            model, model_parameters)

        # ---- sharding plan (ZeRO stage -> placement)
        if hasattr(model, "param_specs"):
            base_specs = model.param_specs(master_abstract)
        elif hasattr(model, "param_partition_rules"):
            from ..models.gpt_neox import make_param_specs

            base_specs = make_param_specs(master_abstract, model.param_partition_rules())
        else:
            base_specs = jax.tree_util.tree_map(lambda _: P(), master_abstract)
        self.plan = build_sharding_plan(master_abstract, base_specs, config.zero_config, mesh)
        self._no_cast = self._no_cast_mask(master_abstract)

        self.master_shardings = _named(mesh.mesh, self.plan.master_specs)
        self.param_shardings = _named(mesh.mesh, self.plan.param_specs)
        self.grad_shardings = _named(mesh.mesh, self.plan.grad_specs)
        self._repl = NamedSharding(mesh.mesh, P())

        # ---- host offload (reference ZeRO-Offload, ``offload_optimizer``
        # device=cpu + ``swap_tensor/``): master params + optimizer moments
        # live in pinned host memory; the compiled step device_puts them in,
        # and out_shardings stream the updated state back.  XLA overlaps the
        # H2D/D2H with compute -- the PCIe-overlap role of the reference's
        # async grad copy (``stage_1_and_2.py:1144``).
        offload_dev = config.zero_config.offload_optimizer_device
        # host-update mode (reference ZeRO-Offload's CPU Adam,
        # ``ops/adam/cpu_adam.py:83`` over ``csrc/adam/dst_cpu_adam.cpp``):
        # the update runs on host cores over host-resident fp32 masters +
        # moments; the device holds ONLY the compute-dtype params.  This is
        # the mode that fits optimizer states larger than HBM -- the
        # device-side offload below still materializes fp32 state on device
        # during the step.
        self._host_adam = None
        off_full = config.zero_config.offload_optimizer
        if off_full is not None and off_full.host_update:
            if offload_dev != "cpu":
                raise ValueError(
                    "offload_optimizer.host_update requires device 'cpu' "
                    f"(got {offload_dev!r}); the NVMe tier keeps the "
                    "device-side update")
            self._init_host_update(config)
        self._offload_optimizer = (offload_dev in ("cpu", "nvme")
                                   and self._host_adam is None)
        # NVMe tier (reference ZeRO-Infinity ``runtime/swap_tensor/``,
        # ``stage3.py:576``): optimizer state additionally spills to disk
        # between steps through the native aio pool; the host (pinned)
        # placement below stays the staging buffer.
        self._opt_swapper = None
        if offload_dev == "nvme":
            from .swap_tensor import OptimizerStateSwapper

            nvme_path = config.zero_config.offload_optimizer.nvme_path
            if not nvme_path:
                raise ValueError(
                    "offload_optimizer.device='nvme' requires nvme_path")
            off_cfg = config.zero_config.offload_optimizer
            self._opt_swapper = OptimizerStateSwapper(
                os.path.join(nvme_path, "zero_opt_swap"),
                num_threads=off_cfg.buffer_count,
                pipeline_write=off_cfg.pipeline_write)
        self._master_dev_shardings = self.master_shardings
        if self._offload_optimizer:
            try:
                self.master_shardings = jax.tree_util.tree_map(
                    lambda s: s.with_memory_kind("pinned_host"),
                    self.master_shardings)
            except Exception:
                logger.warning("pinned_host memory kind unavailable; "
                               "optimizer offload disabled")
                self._offload_optimizer = False
                if self._opt_swapper is not None:
                    # the NVMe tier stages through the pinned-host
                    # placement; without it the split step's jit kwargs
                    # disagree with its call arity -- disable the tier
                    # coherently rather than crash on the first step
                    logger.warning("NVMe optimizer swap disabled with it")
                    self._opt_swapper.close()
                    self._opt_swapper = None
        self._qwz = (config.zero_config.stage >= 3
                     and config.zero_config.zero_quantized_weights)
        if self._qwz:
            self._qwz_targets = _named(mesh.mesh, base_specs)

            def _strip(spec):
                t = tuple(spec)
                while t and t[-1] is None:
                    t = t[:-1]
                return t

            # quantize only where the master placement differs from the
            # gather target: leaves kept replicated (persistence threshold)
            # have no dp gather to compress, so int8 round-tripping them is
            # pure precision loss (reference quantizes only the all-gather of
            # partitioned params, ``partition_parameters.py:1101``)
            self._qwz_mask = jax.tree_util.tree_map(
                lambda m, b: _strip(m) != _strip(b),
                self.plan.master_specs, base_specs,
                is_leaf=lambda x: isinstance(x, P))
        else:
            self._qwz_targets = None
            self._qwz_mask = None

        # ---- optimizer
        self.client_optimizer = optimizer
        mup = model.mup_multipliers(master_abstract) if hasattr(model, "mup_multipliers") else None
        # client optax optimizers follow the "updates are added" convention
        # (lr/sign already folded in); config-built ones exclude lr so the
        # on-device schedule applies it.
        self._updates_include_lr = optimizer is not None
        if optimizer is not None:
            self.tx = optimizer
            self.optimizer_name = "client"
            base_lr = 0.0
        elif config.optimizer is not None:
            self.tx = build_optimizer(
                config.optimizer.type, config.optimizer.params, mup_multipliers=mup,
            )
            self.optimizer_name = config.optimizer.type.lower()
            base_lr = config.optimizer.params.lr
        else:
            import optax

            self.tx = optax.identity()
            self.optimizer_name = "none"
            base_lr = 0.0
        self.optimizer = self.tx  # reference name

        # ---- lr schedule
        if lr_scheduler is not None and callable(lr_scheduler):
            self._lr_fn = lr_scheduler
        elif config.scheduler is not None:
            self._lr_fn = get_lr_schedule_fn(
                config.scheduler.type, config.scheduler.params, base_lr=base_lr
            )
        else:
            self._lr_fn = lambda step: jnp.asarray(base_lr, jnp.float32)
        self.lr_scheduler = self._lr_fn

        # ---- materialize train state: the one stretch of this method that
        # takes 0.5 s or more on the chip (PERF.md section 5), so the one
        # child of ``setup/initialize``
        with span("setup/initialize/state"):
            self.state = self._build_state()
            self._state_shardings = self._shardings_like_state()
            self._spill_opt()

        # ---- data-efficiency stack (curriculum / random-LTD / PLD /
        # eigenvalue), reference ``engine.py:551-570,1809-1821``.  Must
        # precede the dataloader: deepspeed_io's curriculum-sampling branch
        # reads the schedulers.
        self._init_data_efficiency()

        # ---- compression (reference ``compression/compress.py:100``):
        # masks/bit-widths planned once from the initial masters; applied to
        # the compute weights each step (QAT, straight-through).  Layer
        # reduction is a model-level transform done before initialize()
        # (``compression.init_compression``), like the reference's client-side
        # call.
        self._compression = None
        cc = config.compression_config
        enabled_families = [
            f for f in ("weight_quantization", "sparse_pruning",
                        "row_pruning", "head_pruning")
            if (getattr(cc, f) or {}).get("shared_parameters", {}).get("enabled")
        ]
        if enabled_families:
            from ..compression.compress import init_compression

            _, self._compression = init_compression(
                self.state["master_params"], cc)
        if self._compression is not None and self._host_adam is not None:
            raise NotImplementedError(
                "host_update does not compose with compression_training "
                "(the QAT transform runs on the device compute path)")

        # ---- dataloader
        self.training_dataloader = None
        self._data_iterator = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data, collate_fn=collate_fn)
            from .dataloader import RepeatingLoader

            self._data_iterator = iter(RepeatingLoader(self.training_dataloader))

        # ---- bookkeeping
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        self._last_metrics = {}
        self._grad_acc_buffer = None
        self._cached_loss = None
        self._in_gas_boundary = True

        self.timers = SynchronizedWallClockTimer(synchronize=config.wall_clock_breakdown)
        self.tput_timer = ThroughputTimer(
            batch_size=config.train_batch_size, steps_per_output=config.steps_per_print
        )
        # ---- telemetry: structured registry, optional stall watchdog
        from ..telemetry import StallWatchdog, registry_from_config

        self.telemetry = registry_from_config(config.telemetry)
        self.monitor = MonitorMaster(
            config.monitor_config,
            registry=self.telemetry if config.telemetry.enabled else None)
        self.watchdog = None
        wd = config.telemetry.watchdog
        if wd.enabled:
            self.watchdog = StallWatchdog(
                registry=self.telemetry,
                timers=self.timers,
                deadline_s=wd.deadline_s,
                poll_s=wd.poll_s,
                snapshot_dir=wd.snapshot_dir or self.telemetry.run_dir,
                capture_profile=wd.capture_profile,
                profile_duration_s=wd.profile_duration_s,
            ).start()
            # every timer start/stop (fwd/bwd/step/train_batch and the pipe
            # engines' stage timers) doubles as a liveness heartbeat
            self.timers.set_event_hook(self.watchdog.timer_event)
        self._step_cost = None       # HLO cost_analysis of the compiled step
        self._comm_footprint = None  # trace-time collective wire footprint
        self._tele_captured = False
        self._trace_watch = TraceSessionWatch()
        #: ``telemetry.trace.time_to_first_step`` at the end of the first
        #: ``train_batch`` that compiled nothing (logged there, once)
        self.time_to_first_step = None

        # ---- resilience: preemption handlers + loss sentinel (PR 3)
        from .resilience import build_resilience

        self._ckpt_dir_hint = None  # last save/load dir (emergency target)
        self.resilience, self._sentinel = build_resilience(
            self, config.resilience)
        if self._sentinel is not None and self._host_adam is not None:
            # host-update mode mutates the fp32 masters in place during the
            # step; there is no intact pre-step state to keep on a skip
            logger.warning("[sentinel] loss sentinel is not supported with "
                           "host-update optimizers (in-place master update); "
                           "disabled")
            self._sentinel = None
        if self.resilience is not None and config.resilience.checkpoint_on_stall:
            self.resilience.attach_watchdog(self.watchdog)
        dist.configure(config)

        # ---- comm.overlap: latency-hiding distributed step.  Three levers
        # (config.py CommOverlapConfig): deferred+bucketed grad reduction,
        # device-prefetching input pipeline, XLA latency-hiding flags (the
        # last applied in initialize(), before the engine exists).
        ov = config.comm.overlap
        self._prefetcher = None
        self._prefetch_depth = 0
        if ov.enabled and ov.prefetch_depth > 0:
            depth = int(ov.prefetch_depth)
            donation = (not self._offload_optimizer
                        and self._sentinel is None)
            if donation and depth > 2:
                # bounded pool while donation is active: the prefetcher may
                # only ever hold batches for the current and next step, so a
                # buffer can never alias a donated step input
                logger.warning(
                    "comm.overlap: prefetch_depth clamped to 2 while buffer "
                    "donation is active (bounded buffer pool)")
                depth = 2
            self._prefetch_depth = depth
        from ..comm import schedule as comm_schedule

        comm_schedule.set_active_mode(ov.schedule.mode if ov.enabled
                                      else "off")
        # memory-movement planning (comm/memplan.py): the same cost model,
        # applied to parameter/optimizer state motion
        from ..comm import memplan as comm_memplan

        self._memory_mode = ov.schedule.memory if ov.enabled else "off"
        self._hbm_budget_bytes = (ov.schedule.hbm_budget_bytes
                                  if ov.enabled else None)
        comm_memplan.set_active_memory_mode(self._memory_mode)
        self.memory_plan = None

        # ---- how the batch's gradient is accumulated and reduced: chosen
        # once, by the module that owns the choice; what the reduction
        # carries across steps (1-bit Adam's error feedback) joins the state
        self._reduction = grad_reduce.select(self)
        for key, value in self._reduction.init_carried(
                self.state["master_params"]).items():
            self.state[key] = value
            self._state_shardings[key] = jax.tree_util.tree_map(
                lambda x: x.sharding, value)

        if self._memory_mode != "off" and self.zero_optimization_stage() >= 3:
            # stage-3 compute params: every leaf gathered at its use site.
            # ``static`` with a budget: fail EAGERLY when full residency
            # cannot fit (the OOM the planner's streaming fallback avoids).
            # ``auto``: the gather/release movement plan is derived from
            # the traced step the first time it compiles (see
            # ``_schedule_jit`` / ``memory_movement_plan``); here only the
            # one-streamed-leaf floor is guarded.
            from .zero.sharding import stage3_static_peak_bytes

            compute_abstract = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, self.precision.param_dtype),
                self.state["master_params"])
            static_peak = stage3_static_peak_bytes(compute_abstract)
            if self._hbm_budget_bytes:
                if self._memory_mode == "static":
                    comm_memplan.assert_hbm_fit(
                        "zero-3 static param placement", static_peak,
                        self._hbm_budget_bytes)
                else:
                    biggest = max(
                        (int(np.prod(x.shape))
                         * jnp.dtype(self.precision.param_dtype).itemsize
                         for x in jax.tree_util.tree_leaves(
                             self.state["master_params"])), default=0)
                    comm_memplan.assert_hbm_fit(
                        "zero-3 planned streaming (largest single leaf)",
                        biggest, self._hbm_budget_bytes)
                    log_dist(
                        "comm.memplan[auto]: zero-3 static residency "
                        f"{static_peak / 2**20:.1f} MiB vs budget "
                        f"{self._hbm_budget_bytes / 2**20:.1f} MiB -- "
                        "gather/release points planned from the traced "
                        "step", ranks=[0])

        self._compiled_eval_step = None
        self._compiled_micro_step = None
        self._compiled_apply = None

        n_params = tree_size(self.state["master_params"])
        log_dist(
            f"DeeperSpeedEngine: {n_params / 1e6:.1f}M params | zero stage "
            f"{self.zero_optimization_stage()} | dtype {jnp.dtype(self.precision.param_dtype).name} "
            f"| mesh pp={mesh.pp} dp={mesh.dp} ep={mesh.ep} sp={mesh.sp} tp={mesh.tp} "
            f"| mb={self.train_micro_batch_size_per_gpu()} gas={self.gradient_accumulation_steps()}",
            ranks=[0],
        )
        from ..utils.memory import see_memory_usage

        # opt-in via DST_MEMORY_REPORT=1 (reference ``see_memory_usage``
        # behind its memory_breakdown config)
        see_memory_usage("engine initialized")

    def _init_host_update(self, config):
        """Validate + construct the native host-side optimizer."""
        from ..ops.adam.cpu_adam import DeeperSpeedCPUAdam, cpu_adam_available
        from .constants import (ADAM_OPTIMIZER, ADAMW_OPTIMIZER,
                                CPU_ADAM_OPTIMIZER)

        if config.zero_config.stage != 0:
            raise NotImplementedError(
                "offload_optimizer.host_update requires zero stage 0 (the "
                "host update consumes full-replica grads; sharded state "
                "belongs on the device path)")
        if config.fp16.enabled:
            raise NotImplementedError(
                "host_update does not compose with fp16 dynamic scaling; "
                "use bf16 (masters are fp32 on host either way)")
        if jax.process_count() > 1:
            raise NotImplementedError(
                "host_update is single-process (grads fetch to one host)")
        opt = config.optimizer
        opt_type = (opt.type.lower() if opt else ADAM_OPTIMIZER)
        if opt_type not in (ADAM_OPTIMIZER, ADAMW_OPTIMIZER,
                            CPU_ADAM_OPTIMIZER):
            raise NotImplementedError(
                f"host_update supports Adam/AdamW/CPUAdam, got {opt.type}")
        if not cpu_adam_available():
            raise RuntimeError(
                "offload_optimizer.host_update: native cpu_adam library "
                "not available (op build failed?)")
        p = opt.params if opt else None
        self._host_adam = DeeperSpeedCPUAdam(
            lr=p.lr if p else 1e-3,
            betas=tuple(p.betas) if p else (0.9, 0.999),
            eps=p.eps if p else 1e-8,
            weight_decay=p.weight_decay if p else 0.0,
            adamw_mode=opt_type == ADAMW_OPTIMIZER)
        self._host_grads_steps = {}

    def _host_flat_names(self, tree):
        from .zero.sharding import _flat_with_names

        return _flat_with_names(tree)

    def _host_init_master(self, master_dev):
        """Pull the freshly-initialized fp32 masters to host and free the
        device copies; remember the tree structure for re-upload."""
        self._host_master = {}
        self._host_master_names = []
        for name, leaf in self._host_flat_names(master_dev):
            # np.array: OWN contiguous buffer (the native step is in-place)
            self._host_master[name] = np.array(leaf, np.float32)
            self._host_master_names.append(name)
        self._host_treedef = jax.tree_util.tree_structure(master_dev)
        self._host_no_cast = (
            dict(self._host_flat_names(self._no_cast))
            if self._no_cast is not None else {})

    def _upload_compute(self):
        """Host fp32 masters -> device compute-dtype params (the only
        device-resident weights in host-update mode).  The bf16 cast
        happens ON HOST (ml_dtypes) so H2D moves half the bytes."""
        import ml_dtypes

        dtype = self.precision.param_dtype
        np_dtype = (ml_dtypes.bfloat16 if dtype == jnp.bfloat16
                    else np.dtype(dtype))
        leaves = []
        for name in self._host_master_names:
            arr = self._host_master[name]
            if self._host_no_cast.get(name, False) or np_dtype == np.float32:
                leaves.append(arr)
            else:
                leaves.append(arr.astype(np_dtype))
        tree = jax.tree_util.tree_unflatten(self._host_treedef, leaves)
        return jax.device_put(tree, self.param_shardings)

    def _host_restore(self, masters_by_name, moments=None, t=None,
                      meta=None):
        """Shared restore path for host-update state (native checkpoint
        loader AND universal loader): masters copied in place, compute
        cast re-uploaded, moments/step into the native optimizer.

        Missing master names raise (the device path fails loudly on
        structure mismatch via from_state_dict; silence here would train a
        half-random model); missing moment names warn and stay fresh."""
        missing = [n for n in self._host_master_names
                   if n not in masters_by_name]
        if missing:
            raise ValueError(
                f"host_update restore: {len(missing)} master params absent "
                f"from the checkpoint (first: {missing[:3]}); the export "
                "does not match this model")
        for name in self._host_master_names:
            np.copyto(self._host_master[name],
                      np.asarray(masters_by_name[name], np.float32))
        self.state["master_params"] = self._upload_compute()
        if moments is not None:
            mu, nu = moments
            lost = [n for n in self._host_master_names
                    if n not in mu or n not in nu]
            if lost:
                logger.warning(
                    f"host_update restore: moments missing for {len(lost)} "
                    f"params (first: {lost[:3]}); they start fresh")
            for name in self._host_master_names:
                if name in mu and name in nu:
                    self._host_adam._moments[name] = (
                        np.array(mu[name], np.float32).reshape(-1),
                        np.array(nu[name], np.float32).reshape(-1))
            if t is not None:
                self._host_adam.t = int(t)
        if meta is not None:
            self._restore_counters(meta)

    def _restore_counters(self, meta):
        """Bookkeeping tail shared by every load path: rng + step counters
        + the device step scalar (one definition, no loader drift)."""
        if meta.get("rng_key") is not None:
            self._rng = jnp.asarray(np.asarray(meta["rng_key"],
                                               dtype=np.uint32))
        self.global_steps = meta.get("global_steps", self.global_steps)
        self.global_samples = meta.get("global_samples", self.global_samples)
        self.micro_steps = meta.get("micro_steps", self.micro_steps)
        self.skipped_steps = meta.get("skipped_steps", self.skipped_steps)
        # the device step scalar drives the LR schedule: prefer the APPLIED
        # step count (engine_step; fp16 skips don't advance it) over the
        # batch counter when the export carries it
        self.state["step"] = jax.device_put(
            jnp.asarray(meta.get("engine_step", self.global_steps),
                        jnp.int32), self._repl)

    def _make_grads_step_host(self, ltd_tokens=None):
        """(clipped grads, loss, norm) over the device compute params; the
        optimizer state never appears on device.  ``offload_optimizer.
        wire_dtype: "bf16"`` halves the grads' D2H bytes (the dominant
        per-step cost on bandwidth-limited host links; clip + norm still
        run in fp32 on device, the host upcasts before Adam)."""
        clip = self.config.gradient_clipping
        off = self.config.zero_config.offload_optimizer
        wire = jnp.bfloat16 if (
            off is not None and off.wire_dtype == "bf16") else jnp.float32

        def gs(params, batch, rng, step):
            grads, loss, _ = self._grads_for_batch(
                params, batch, rng, jnp.float32(1.0),
                ltd_tokens=ltd_tokens, step=step)
            with jax.named_scope("grad_norm_clip"):
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), grads)
                norm = tree_global_norm(grads)
                grads = _clip_by_global_norm(grads, norm, clip)
                grads = jax.tree_util.tree_map(lambda g: g.astype(wire),
                                               grads)
            return grads, loss, norm

        return jax.jit(gs)

    def _get_grads_step_host(self, ltd_tokens=None):
        if ltd_tokens not in self._host_grads_steps:
            self._host_grads_steps[ltd_tokens] = self._make_grads_step_host(
                ltd_tokens)
        return self._host_grads_steps[ltd_tokens]

    def _builds_own_loss(self):
        """Subclass hook: engines that construct their own loss (pipeline)
        return True so no model/user loss_fn is required."""
        return False

    # ------------------------------------------------- data-efficiency stack
    def _init_data_efficiency(self):
        """Instantiate the config-gated data-efficiency schedulers.

        Reference wiring points: curriculum difficulty injection
        (``engine.py:1814-1818``), random-LTD scheduler (``engine.py:551-570``),
        PLD theta (``engine.py:485-495,1809``), eigenvalue/MoQ
        (``engine.py:497-518``).  Here each scheduler runs on the host between
        steps and its value enters the compiled step as data (PLD theta), as a
        shape (curriculum seqlen -> jit shape-cache retrace), or as a static
        closure constant (LTD token budget -> one compiled step per quantized
        budget value, cached in ``self._train_steps``).
        """
        cfg = self.config
        self.curriculum_scheduler = None
        if cfg.curriculum.enabled:
            from .data_pipeline.curriculum_scheduler import CurriculumScheduler

            self.curriculum_scheduler = CurriculumScheduler(cfg.curriculum.params)
        self.progressive_layer_drop = None
        if cfg.progressive_layer_drop.enabled:
            from .progressive_layer_drop import ProgressiveLayerDrop

            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=cfg.progressive_layer_drop.theta,
                gamma=cfg.progressive_layer_drop.gamma,
            )
        self.random_ltd_scheduler = None
        de = cfg.data_efficiency
        routing = dict(de.data_routing.get("random_ltd", {})) if de.enabled else {}
        if routing.get("enabled"):
            from .data_pipeline.data_routing.scheduler import RandomLTDScheduler

            sched = dict(routing.get("random_ltd_schedule", {}))
            self.random_ltd_scheduler = RandomLTDScheduler(
                min_tokens=sched.get("min_value", 128),
                max_tokens=sched.get("max_value", 2048),
                total_steps=sched.get("schedule_config", {}).get(
                    "require_steps", sched.get("total_steps", 10000)),
                step_size=sched.get("schedule_config", {}).get(
                    "seq_per_step", sched.get("step_size", 16)),
            )
        self._train_steps = {}
        self._grads_steps = {}
        self._apply_batch_fn = None

    def _apply_data_efficiency(self, stacked):
        """Per-step injection: truncate to the curriculum seqlen, add the PLD
        theta to the batch, and return the current LTD token budget."""
        step = self.global_steps + 1
        if (self.curriculum_scheduler is not None
                and self.curriculum_scheduler.config.curriculum_type == "seqlen"):
            seqlen = self.curriculum_scheduler.update_difficulty(step)

            def trunc(x):
                if hasattr(x, "ndim") and x.ndim >= 3 and x.shape[2] > seqlen:
                    return x[:, :, :seqlen]
                return x

            stacked = jax.tree_util.tree_map(trunc, stacked)
        if self.progressive_layer_drop is not None and isinstance(stacked, dict):
            theta = self.progressive_layer_drop.update_state(step)
            gas = self.gradient_accumulation_steps()
            stacked = {**stacked,
                       "pld_theta": jax.device_put(
                           jnp.full((gas,), theta, jnp.float32), self._repl)}
        ltd = None
        if self.random_ltd_scheduler is not None:
            ltd = int(self.random_ltd_scheduler.update(step))
        return stacked, ltd

    def _get_train_step(self, ltd_tokens=None):
        """Compiled train step for the current (quantized) LTD budget."""
        if ltd_tokens not in self._train_steps:
            self._train_steps[ltd_tokens] = self._make_train_step(ltd_tokens)
        return self._train_steps[ltd_tokens]

    def _maybe_profile_flops(self, stacked):
        """One-shot per-module FLOPs profile at ``flops_profiler.profile_step``
        (reference ``engine.py:1788-1806`` hooking the profiler around one
        forward)."""
        fp = self.config.flops_profiler
        if not fp.enabled or self.global_steps + 1 != fp.profile_step:
            return
        if not (isinstance(stacked, dict) and "input_ids" in stacked):
            logger.warning("flops_profiler: only token-batch models are "
                           "profiled (need batch['input_ids'])")
            return
        from ..profiling.flops_profiler import FlopsProfiler
        from ..utils.memory import see_memory_usage

        prof = FlopsProfiler(self.module, ds_engine=self)
        ids = stacked["input_ids"]
        prof.profile(jax.eval_shape(lambda: ids[0]),
                     params=jax.eval_shape(
                         lambda: self.state["master_params"]))
        prof.print_model_profile(
            profile_step=fp.profile_step, module_depth=fp.module_depth,
            top_modules=fp.top_modules, detailed=fp.detailed,
            output_file=fp.output_file)
        see_memory_usage("flops_profiler step", force=True)
        self.flops_profiler = prof

    def redundancy_clean(self):
        """Bake pruning masks into the masters (reference
        ``redundancy_clean`` ``compress.py:148``); call before export."""
        assert self._compression is not None, "compression not configured"
        from ..compression.compress import redundancy_clean

        self.state["master_params"] = jax.device_put(
            redundancy_clean(self.state["master_params"], self._compression),
            self.master_shardings)

    def update_moq_schedule(self, batch=None, rng=None):
        """MoQ: re-rank quantized leaves by curvature sensitivity and assign
        lower bits to the least-sensitive half (consumes
        :meth:`compute_eigenvalue`'s Hessian eigenvector -- per-leaf mass of
        the top eigenvector is the sensitivity signal; reference eigenvalue-
        driven quantization schedule, ``engine.py:497-518``)."""
        assert self._compression is not None, "compression not configured"
        from ..compression.compress import eigenvalue_bit_schedule
        from .zero.sharding import _flat_with_names

        _, vec = self.compute_eigenvalue(batch=batch, rng=rng)
        mass = {name: float(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
                for name, leaf in _flat_with_names(vec)}
        self._compression = eigenvalue_bit_schedule(self._compression, mass)
        self._train_steps = {}  # bit plan changed: recompile
        self._grads_steps = {}
        return self._compression.eigenvalue_bits

    def compute_eigenvalue(self, batch=None, rng=None):
        """Max Hessian eigenvalue of the loss at the current params
        (reference ``engine.py:497-518`` -- MoQ's curvature signal; consumed
        by the compression scheduler's sensitivity ordering)."""
        assert self.config.eigenvalue.enabled, "eigenvalue not enabled in config"
        from .eigenvalue import Eigenvalue

        ec = self.config.eigenvalue
        ev = Eigenvalue(verbose=ec.verbose, max_iter=ec.max_iter, tol=ec.tol,
                        stability=ec.stability,
                        gas_boundary_resolution=ec.gas_boundary_resolution,
                        layer_name=ec.layer_name, layer_num=ec.layer_num)
        if batch is None:
            assert self._data_iterator is not None, "pass batch= or training_data"
            batch = next(self._data_iterator)
        mb = jax.tree_util.tree_map(jnp.asarray, batch)
        params = self.state["master_params"]
        if self._offload_optimizer:
            params = jax.device_put(params, self._master_dev_shardings)

        def loss_closure(p):
            return grad_reduce.split_loss(self._loss_fn(p, mb, None))[0]

        return ev.compute_eigenvalue(loss_closure, params, rng=rng)

    # ------------------------------------------------------------------ init
    def _make_init(self, model, model_parameters):
        if model_parameters is not None:
            abstract = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32), model_parameters
            )

            # the given params go in as an ARGUMENT of the jitted init: closed
            # over, they would be baked into the program as constants (a
            # 410M-param model makes gigabytes of HLO)
            def init_fn(params):
                return tree_cast(params, jnp.float32)

            return abstract, init_fn, (model_parameters,)

        example = model.example_batch(batch_size=1)
        first = example["input_ids"] if "input_ids" in example else example["x"]

        def raw_init(rng):
            variables = model.init(rng, first)
            return tree_cast(variables["params"], jnp.float32)

        abstract = jax.eval_shape(raw_init, self._rng)

        def init_fn():
            return raw_init(self._rng)

        return abstract, init_fn, ()

    def _build_state(self):
        # init on device, then stream offloaded components to pinned host
        # (the SPMD partitioner rejects host-kind out_shardings on the init
        # computation itself)
        master = jax.jit(self._init_fn,
                         out_shardings=self._master_dev_shardings)(
                             *self._init_args)
        self._init_args = ()  # do not keep the caller's params alive
        if self._host_adam is not None:
            # host-update mode: fp32 masters move to host, moments live in
            # the native optimizer, and the device keeps ONLY the compute-
            # dtype cast -- nothing optimizer-sized ever resides on device
            self._host_init_master(master)
            compute = self._upload_compute()
            del master  # free the device fp32 copy
            self._opt_dev_shardings = self._opt_shardings = None
            return {
                "master_params": compute,
                "opt_state": None,
                "step": jax.device_put(jnp.zeros((), jnp.int32), self._repl),
                "loss_scale": jax.device_put(
                    init_loss_scale(self.config.fp16), self._repl),
            }
        opt_abstract = jax.eval_shape(self.tx.init, master)
        opt_specs = self.plan.opt_state_specs(opt_abstract, master)
        self._opt_dev_shardings = _named(self.mesh.mesh, opt_specs)
        self._opt_shardings = self._opt_dev_shardings
        opt_state = jax.jit(self.tx.init,
                            out_shardings=self._opt_dev_shardings)(master)
        if self._offload_optimizer:
            self._opt_shardings = jax.tree_util.tree_map(
                lambda s: s.with_memory_kind("pinned_host"),
                self._opt_dev_shardings)
            master = jax.device_put(master, self.master_shardings)
            opt_state = jax.device_put(opt_state, self._opt_shardings)
        scale_state = init_loss_scale(self.config.fp16)
        state = {
            "master_params": master,
            "opt_state": opt_state,
            # placed like the step's own output: an unplaced scalar has
            # another type than the mesh-replicated one that comes back, and
            # the second train_batch would trace and compile all over again
            "step": jax.device_put(jnp.zeros((), jnp.int32), self._repl),
            "loss_scale": jax.device_put(scale_state, self._repl),
        }
        return state

    def _shardings_like_state(self):
        shardings = {
            "master_params": (self.param_shardings
                              if self._host_adam is not None
                              else self.master_shardings),
            "opt_state": self._opt_shardings,
            "step": self._repl,
            "loss_scale": jax.tree_util.tree_map(lambda _: self._repl, self.state["loss_scale"]),
        }
        return shardings

    def _no_cast_mask(self, abstract):
        """True leaves stay fp32 under mixed precision (fork's selective
        ``_deepspeed_no_cast``, reference ``engine.py:1074-1095``).  Models
        may expose ``no_cast_paths() -> [regex]``; embedding tables default
        to no-cast (their scatter-add grads accumulate in fp32)."""
        import re

        patterns = (self.module.no_cast_paths()
                    if hasattr(self.module, "no_cast_paths")
                    else [r"embed_in/embedding"])
        if not patterns:
            return None

        def mark(path, _):
            name = "/".join(
                str(getattr(k, "key", getattr(k, "name", getattr(k, "idx", k))))
                for k in path)
            return any(re.search(p, name) for p in patterns)

        return jax.tree_util.tree_map_with_path(mark, abstract)

    # -------------------------------------------------------------- step fns
    def _apply_update(self, master, updates, lr):
        if self._updates_include_lr:  # optax convention: params + updates
            return jax.tree_util.tree_map(
                lambda p, u: p + u.astype(jnp.float32), master, updates
            )
        return jax.tree_util.tree_map(
            lambda p, u: p - lr * u.astype(jnp.float32), master, updates
        )

    def _materialize_state(self, state):
        """Bring host-offloaded components into device memory (traced)."""
        if not self._offload_optimizer:
            return state
        out = {
            **state,
            "master_params": jax.device_put(state["master_params"],
                                            self._master_dev_shardings),
        }
        # NVMe tier: opt_state is None while spilled to disk -- paths that
        # do not consume it (eval, legacy forward) pass it through untouched
        if state["opt_state"] is not None:
            out["opt_state"] = jax.device_put(state["opt_state"],
                                              self._opt_dev_shardings)
        return out

    def _dehydrate_state(self, state):
        """Stream updated master/opt state back to pinned host (eager,
        called on the step's outputs).

        Host-kind *inputs* compile fine (XLA streams them in), but host-kind
        ``out_shardings`` trip the SPMD partitioner's
        ``annotate_device_placement`` handling in this XLA build -- so the
        compiled step returns device-resident state and the engine stages it
        out here; the dispatch is async, overlapping the D2H with the host
        side of the next step.
        """
        if not self._offload_optimizer:
            return state
        out = {
            **state,
            "master_params": jax.device_put(state["master_params"],
                                            self.master_shardings),
        }
        # NVMe tier: skip the pinned-host staging put -- _spill_opt reads
        # the device output directly, avoiding a second full host copy
        if self._opt_swapper is None:
            out["opt_state"] = jax.device_put(state["opt_state"],
                                              self._opt_shardings)
        return out

    def _spill_opt(self):
        """NVMe tier: flush the optimizer state to disk (async writes) and
        drop the in-memory copy until the next step needs it."""
        if self._opt_swapper is None or self.state["opt_state"] is None:
            return
        host = jax.tree_util.tree_map(np.asarray, self.state["opt_state"])
        self._opt_swapper.swap_out(host)
        self.state["opt_state"] = None

    def _ensure_opt_resident(self):
        """NVMe tier: bring the optimizer state back from disk into its
        (pinned-host when available) staging placement."""
        if self._opt_swapper is None or self.state["opt_state"] is not None:
            return
        host = self._opt_swapper.swap_in()
        self.state["opt_state"] = jax.device_put(host, self._opt_shardings)

    def _schedule_jit(self, fn, jit_kwargs, label="step"):
        """jit ``fn``, routing through the compiler-driven scheduling pass
        (``comm/schedule.py`` ``ScheduledStepFn``) when
        ``comm.overlap.schedule.mode == "auto"``: the step is traced once,
        every collective hoisted to its earliest dataflow-legal issue
        point, and the rewritten (bit-exact) program jitted.  Host-offload
        steps keep the plain jit -- their device_put memory-space moves
        must not be replayed through eval_jaxpr."""
        plan = self._reduction.plan
        if (plan is not None and plan.hoist and not self._offload_optimizer
                and self._host_adam is None):
            from ..comm.schedule import ScheduledStepFn

            return ScheduledStepFn(
                fn, jit_kwargs=jit_kwargs, label=label,
                plan_memory=(self._memory_mode == "auto"
                             and self.zero_optimization_stage() >= 3))
        return jax.jit(fn, **jit_kwargs)

    def _state_jit_kwargs(self, rest_in, donate=True, state_out=True):
        """jit sharding kwargs for state-consuming steps.

        With host offload the jit gets NO in/out shardings: explicit
        ``device_put``s inside the step move data between memory spaces
        (out_shardings-driven memory-kind annotations on scalars break the
        SPMD partitioner), and inputs carry their placement already.
        """
        # donation cannot alias buffers across memory kinds -- skip it when
        # state round-trips through pinned host.  The loss sentinel also
        # forbids donation: skipping a poisoned step means keeping the
        # pre-step state alive after the step ran.
        donate = donate and not self._offload_optimizer \
            and getattr(self, "_sentinel", None) is None
        kwargs = {"donate_argnums": (0,)} if donate else {}
        if not self._offload_optimizer:
            kwargs["in_shardings"] = (self._state_shardings,) + tuple(rest_in)
            if state_out:
                kwargs["out_shardings"] = (self._state_shardings, None)
        return kwargs

    def _compute_params(self, master, step=None):
        """Derive compute-dtype params at their ZeRO placement."""
        with jax.named_scope("optimizer"):   # the masters' cast is its tail
            params = self.precision.cast_for_compute(master, self._no_cast)
        if self._compression is not None and step is not None:
            from ..compression.compress import compress_params

            params = compress_params(params, self._compression, step)
        if self._qwz:
            # ZeRO++ qwZ: the dp-axis weight gather moves int8 + scales
            # instead of bf16 (reference quantized all_gather_coalesced,
            # ``partition_parameters.py:1101``).  jax.checkpoint makes the
            # backward re-run the cheap gather+dequant instead of keeping the
            # dp-replicated fp weights live from forward to backward --
            # preserving stage-3's memory profile.
            from .zero.quantized import quantized_resharding

            def gather(x, target, quantize):
                if not quantize:  # replicated/persistent leaf: plain constraint
                    return jax.lax.with_sharding_constraint(x, target)
                return jax.checkpoint(
                    lambda a: quantized_resharding(a, target))(x)

            with jax.named_scope("zero3_gather"):
                return jax.tree_util.tree_map(
                    gather, params, self._qwz_targets, self._qwz_mask)
        with jax.named_scope("zero3_gather"):
            return jax.lax.with_sharding_constraint(params,
                                                    self.param_shardings)

    @property
    def _grads_for_batch(self):
        """``(master, batch, rng, scale, ltd_tokens=, step=, carried=)`` ->
        (mean-loss grads still multiplied by ``scale``, reduced over the
        data-parallel replicas; mean loss; the model's own numbers of the
        step averaged over the microbatches, {} from a model that reports
        none): the reduction in effect (``runtime/grad_reduce.py``).
        ``carried`` holds what the reduction carries across steps, by state
        key; the reduction replaces its entries with the step's.  A property,
        not a method that forwards: a Python frame between the step and the
        microbatch scan is not free (PERF.md §6, PR 31).

        Subclasses re-express this as a method: the pipeline engine replaces
        the microbatch scan with the compiled pipeline over the pp axis."""
        return self._reduction.grads

    @jax.named_scope("grad_norm_clip")
    def _unscale_and_clip(self, grads, inv, clip, fp16):
        """Traced: fp32 grads times ``inv``, the overflow flag, the global
        norm and the clip -> (grads, overflow, norm)."""
        grads = jax.tree_util.tree_map(
            lambda g: (g * inv).astype(jnp.float32), grads)
        overflow = (has_inf_or_nan(grads) if fp16 is not None
                    else jnp.zeros((), bool))
        grad_norm = tree_global_norm(grads)
        return _clip_by_global_norm(grads, grad_norm, clip), overflow, grad_norm

    @jax.named_scope("optimizer")
    def _optimizer_pass(self, state, dev, master, grads, overflow, fp16):
        """Traced: the optimizer's update of masters and moments (kept as
        they were on an fp16 overflow) -> (new state, lr)."""
        lr = jnp.asarray(self._lr_fn(state["step"]), jnp.float32)
        updates, new_opt = self.tx.update(grads, dev["opt_state"], master)
        new_master = self._apply_update(master, updates, lr)
        if fp16 is not None:
            keep = lambda new, old: jax.tree_util.tree_map(
                lambda n, o: jnp.where(overflow, o, n), new, old
            )
            new_master = keep(new_master, master)
            new_opt = keep(new_opt, dev["opt_state"])
        return {
            "master_params": new_master,
            "opt_state": new_opt,
            "step": state["step"] + jnp.where(overflow, 0, 1).astype(jnp.int32),
            "loss_scale": update_loss_scale(state["loss_scale"], overflow, fp16),
        }, lr

    def _make_train_step(self, ltd_tokens=None):
        clip = self.config.gradient_clipping
        fp16 = self.config.fp16 if self.precision.is_fp16 else None

        def train_step(state, batch, rng):
            dev = self._materialize_state(state)
            master = dev["master_params"]
            scale = state["loss_scale"].scale if fp16 is not None else jnp.float32(1.0)

            carried = {k: state[k] for k in self._reduction.carries}
            grads, loss_mean, model_stats = self._grads_for_batch(
                master, batch, rng, scale, ltd_tokens=ltd_tokens,
                step=state["step"], carried=carried)
            grads, overflow, grad_norm = self._unscale_and_clip(
                grads, 1.0 / scale, clip, fp16)
            new_state, lr = self._optimizer_pass(state, dev, master, grads,
                                                 overflow, fp16)
            new_scale = new_state["loss_scale"]
            new_state.update(carried)
            metrics = {
                "loss": loss_mean,
                "grad_norm": grad_norm,
                "lr": lr,
                "overflow": overflow,
                "loss_scale": new_scale.scale,
            }
            if model_stats:
                metrics["model"] = model_stats
            return new_state, metrics

        return self._schedule_jit(
            train_step, self._state_jit_kwargs((None, self._repl)),
            label="train_step")

    def _make_eval_step(self):
        def eval_step(state, batch, rng):
            params = self._compute_params(
                self._materialize_state(state)["master_params"],
                step=state["step"])

            def micro(_, mb):
                # eval: deterministic
                return 0, grad_reduce.split_loss(
                    self._loss_fn(params, mb, None))[0]

            _, losses = jax.lax.scan(micro, 0, batch)
            return jnp.mean(losses)

        return self._schedule_jit(
            eval_step, self._state_jit_kwargs(
                (None, self._repl), donate=False, state_out=False),
            label="eval_step")

    def _make_micro_step(self):
        """(loss, grads) for the forward/backward legacy API."""

        def micro_step(state, microbatch, rng):
            scale = state["loss_scale"].scale if self.precision.is_fp16 else jnp.float32(1.0)
            params = self._compute_params(
                self._materialize_state(state)["master_params"],
                step=state["step"])
            loss, grads, _ = grad_reduce.micro_loss_and_grads(
                self, params, microbatch, rng, scale,
                wire=grad_reduce.wire_dtype(self))
            grads = jax.lax.with_sharding_constraint(grads, self.grad_shardings)
            # reduction ran in the wire dtype; the engine-side accumulation
            # buffer (backward()) must sum in accum_dtype
            grads = tree_cast(grads, self.precision.accum_dtype)
            return loss, grads

        return jax.jit(micro_step, **self._state_jit_kwargs(
            (None, self._repl), donate=False, state_out=False))

    def _make_grads_step(self, ltd_tokens=None):
        """(grads, mean loss) over the gas microbatches WITHOUT touching the
        optimizer state -- the first half of the NVMe tier's split step: its
        dispatch returns immediately, so the moments' disk swap-in on the
        host overlaps the device fwd/bwd (reference pipelined swapper,
        ``swap_tensor/optimizer_utils.py`` overlapped reads)."""
        fp16 = self.config.fp16 if self.precision.is_fp16 else None

        def grads_step(state, batch, rng):
            master = self._materialize_state(
                {**state, "opt_state": None})["master_params"]
            scale = (state["loss_scale"].scale if fp16 is not None
                     else jnp.float32(1.0))
            grads, loss_mean, _ = self._grads_for_batch(
                master, batch, rng, scale, ltd_tokens=ltd_tokens,
                step=state["step"])
            # hand the device-resident master to the apply half too: the
            # split step must not pay the pinned-host->device master
            # transfer twice
            return grads, loss_mean, master

        return jax.jit(grads_step)

    def _get_grads_step(self, ltd_tokens=None):
        if ltd_tokens not in self._grads_steps:
            self._grads_steps[ltd_tokens] = self._make_grads_step(ltd_tokens)
        return self._grads_steps[ltd_tokens]

    def _make_apply(self, divisor=None, device_master=False):
        """Optimizer epilogue over accumulated grads.  ``divisor`` is what
        the raw grads must be divided by to become microbatch means: the
        legacy forward/backward API accumulates gas raw micro-grads
        (divisor=gas); the NVMe split step's grads are already means
        (divisor=1).  ``device_master`` accepts the already-materialized
        device master from the grads half instead of re-staging it from
        pinned host."""
        gas = divisor if divisor is not None else self.gradient_accumulation_steps()
        clip = self.config.gradient_clipping
        fp16 = self.config.fp16 if self.precision.is_fp16 else None

        def apply_step(state, grads, master_dev=None):
            if device_master:
                master = master_dev
                dev = {**state, "master_params": master}
                if self._offload_optimizer and state["opt_state"] is not None:
                    dev["opt_state"] = jax.device_put(
                        state["opt_state"], self._opt_dev_shardings)
            else:
                dev = self._materialize_state(state)
                master = dev["master_params"]
            scale = state["loss_scale"].scale if fp16 is not None else jnp.float32(1.0)
            grads, overflow, grad_norm = self._unscale_and_clip(
                grads, 1.0 / (gas * scale), clip, fp16)
            new_state, lr = self._optimizer_pass(state, dev, master, grads,
                                                 overflow, fp16)
            return new_state, {"grad_norm": grad_norm, "lr": lr, "overflow": overflow,
                               "loss_scale": new_state["loss_scale"].scale}

        return jax.jit(apply_step, **self._state_jit_kwargs((self.grad_shardings,)))

    # ---------------------------------------------------------- batch intake
    def _batch_sharding(self, batch):
        """Global microbatch sharding: batch dim over dp x ep, seq over sp."""

        def spec(x):
            if x.ndim >= 3:  # [gas, B, S, ...]
                return NamedSharding(self.mesh.mesh, P(None, BATCH_AXES, topo.SP_AXIS))
            if x.ndim == 2:
                return NamedSharding(self.mesh.mesh, P(None, BATCH_AXES))
            return self._repl

        return jax.tree_util.tree_map(spec, batch)

    def _stack_microbatches(self, data):
        """Accept: full global batch (split into gas), a list/tuple of gas
        microbatches, or an iterator yielding gas microbatches.

        At ``process_count == 1`` the batch is host-global and one
        ``device_put`` distributes it.  At ``process_count > 1`` (multi-host
        pods) each process feeds its OWN slice of the global batch --
        ``train_batch_size / process_count`` samples, the contract of the
        reference's DistributedSampler (``runtime/dataloader.py:121``) --
        and ``jax.make_array_from_process_local_data`` assembles the global
        array without any cross-host data movement."""
        gas = self.gradient_accumulation_steps()
        if isinstance(data, (list, tuple)):
            micro = list(data)
            assert len(micro) == gas, f"need {gas} microbatches, got {len(micro)}"
            batch = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *micro)
        elif hasattr(data, "__next__"):
            micro = [next(data) for _ in range(gas)]
            batch = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *micro)
        else:  # a dict/pytree of full-batch arrays
            def split(x):
                x = jnp.asarray(x)
                assert x.shape[0] % gas == 0, (
                    f"batch dim {x.shape[0]} not divisible by gas={gas}"
                )
                return x.reshape(gas, x.shape[0] // gas, *x.shape[1:])

            batch = jax.tree_util.tree_map(split, data)
        shardings = self._batch_sharding(batch)
        if jax.process_count() == 1:
            return jax.device_put(batch, shardings)
        return jax.tree_util.tree_map(
            lambda x, sh: jax.make_array_from_process_local_data(
                sh, np.asarray(x)),
            batch, shardings)

    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return jax.device_put(sub, self._repl)

    # ------------------------------------------------------------ public API
    def train_batch(self, data_iter=None, batch=None):
        """One full training step over gas microbatches (reference
        ``pipe/engine.py:312`` semantics, available on every engine)."""
        if data_iter is None and batch is None:
            if self._data_iterator is None:
                raise ValueError("no data: pass data_iter/batch or training_data")
            data_iter = self._data_iterator  # persistent: keeps advancing epochs
        data = batch if batch is not None else data_iter
        # comm.overlap prefetch: wrap the PERSISTENT iterator once (an
        # explicit data_iter/batch bypasses -- its lifetime is unknown), so
        # batch N+1's device_put overlaps step N
        if (self._prefetch_depth > 0 and batch is None
                and data_iter is self._data_iterator):
            if self._prefetcher is None:
                from .dataloader import DevicePrefetchingLoader

                dl = self.training_dataloader
                pos_fn = (dl.state_dict
                          if hasattr(dl, "state_dict") else None)
                self._prefetcher = DevicePrefetchingLoader(
                    data_iter, self._stack_microbatches,
                    depth=self._prefetch_depth, position_fn=pos_fn,
                    pulls_per_batch=self.gradient_accumulation_steps())
            data = self._prefetcher

        # first batch: capture the trace-time collective footprint (every
        # compile this batch triggers -- train step, pipeline loss, MoE --
        # records its analytic wire bytes) and the HLO cost analysis
        capture = self.telemetry.enabled and not self._tele_captured
        if capture:
            dist.comms_logger.begin_trace_capture()
        if self.watchdog is not None:
            self.watchdog.heartbeat("train_batch", self.micro_steps)
        # after a profiler session that covered a step: publish the scopes
        # of the step program about to run, for whoever reads that trace
        publish = self._trace_watch.ended()
        with step_span("train/step", self.global_steps, "train_step",
                       profiled=self._trace_watch.profiled) as step:
            loss = self._train_step_phases(step, data, capture, publish)
        if self.time_to_first_step is None and not step.record["compiled"]:
            # once: the first step that compiled nothing has closed
            self.time_to_first_step = time_to_first_step(step.record["t1"])
            log_dist(describe_time_to_first_step(self.time_to_first_step),
                     ranks=[0])
        return loss

    def _run_step(self, step, dispatch, publish, fn, *args):
        """Call a step program inside its ``train/dispatch`` span, which
        gets ``compiled=1`` if the call compiled anything, as the step's
        record does, with ``compile``: what the compile was made of, from
        the span's start to here (``compile_stats().between``)."""
        if publish:
            self._publish_scopes(fn, *args)
        compiled = compile_stats().programs
        out = fn(*args)
        if compile_stats().programs != compiled:
            dispatch.set(compiled=1)
            step.record["compiled"] = True
            found = compile_stats().between(dispatch.entered,
                                            time.perf_counter())
            kept = step.record.get("compile")
            step.record["compile"] = found if kept is None else {
                key: kept[key] + value for key, value in found.items()}
        return out

    def _publish_scopes(self, fn, *args):
        """``telemetry.step_scopes()`` gets the scope of every instruction
        of ``fn``'s compiled program, ``telemetry.kernel_passes()`` its
        kernel calls by pass.  With the live arguments of the call about to
        be made the executable comes from jit's in-memory cache: nothing
        compiles and nothing is loaded."""
        t0 = time.perf_counter()
        try:
            text = fn.lower(*args).compile().as_text()
            name = publish_step_scopes(text)
            publish_kernel_passes(text)
        except Exception as e:
            logger.warning(f"telemetry: step scopes not published ({e})")
            return
        logger.info(f"telemetry: scopes of {name} published in "
                    f"{time.perf_counter() - t0:.3f}s "
                    f"({len(step_scopes()[name])} instructions)")

    def _train_step_phases(self, step, data, capture, publish):
        """The step itself, phase by phase (``dst:train/<phase>``)."""
        lowered = None

        self.tput_timer.start()
        self.timers(TRAIN_BATCH_TIMER).start()
        with span("train/input"):
            if data is self._prefetcher and self._prefetcher is not None:
                stacked = next(self._prefetcher)  # already stacked + device_put
            else:
                stacked = self._stack_microbatches(data)
            stacked, ltd_tokens = self._apply_data_efficiency(stacked)
        self._maybe_profile_flops(stacked)
        if self._host_adam is not None:
            # host-update mode: device computes clipped fp32 grads over the
            # compute params; the native SIMD Adam updates host-resident
            # fp32 masters + moments; the refreshed compute cast uploads.
            # Reference ZeRO-Offload flow (CPU Adam + fp16 param upload).
            with span("train/dispatch") as dispatch:
                grads_fn = self._get_grads_step_host(ltd_tokens)
                rng = self._next_rng()
                step_arr = jnp.asarray(self.global_steps, jnp.int32)
                if capture:
                    lowered = self._lower_for_cost(
                        grads_fn, self.state["master_params"], stacked, rng,
                        step_arr)
                grads, loss_dev, norm = self._run_step(
                    step, dispatch, publish, grads_fn,
                    self.state["master_params"], stacked, rng, step_arr)
            with span("train/readback"):
                # one batched fetch: device_get overlaps the per-leaf D2H
                # copies instead of serializing blocking np.asarray calls
                grads = jax.device_get(grads)
                ghost = dict(self._host_flat_names(grads))
                del grads
                lr = float(np.asarray(self._lr_fn(self.global_steps)))
            with span("train/host_adam"):
                self._host_adam.step(self._host_master, ghost, lr=lr)
            with span("train/dispatch"):
                self.state["master_params"] = self._upload_compute()
                self.state["step"] = jax.device_put(
                    jnp.asarray(self.global_steps + 1, jnp.int32), self._repl)
            new_state = self.state
            metrics = {"loss": loss_dev, "grad_norm": norm, "lr": lr,
                       "overflow": False, "loss_scale": 1.0}
        elif self._opt_swapper is not None and not self._reduction.carries:
            # NVMe split step (VERDICT r3 Weak #4: the whole-state blocking
            # disk roundtrip serialized with the step): dispatch the
            # grads-only half first -- it needs no optimizer state, so the
            # moments' swap-in (host disk IO) runs WHILE the device computes
            # fwd/bwd; the update half then consumes both.  Symmetrically,
            # swap_out's flush (pipeline_write default) overlaps the NEXT
            # batch's grads and is waited at its swap_in.
            with span("train/dispatch") as dispatch:
                grads_fn = self._get_grads_step(ltd_tokens)
                sub_state = {"master_params": self.state["master_params"],
                             "loss_scale": self.state["loss_scale"],
                             "step": self.state["step"]}
                rng = self._next_rng()
                if capture:
                    lowered = self._lower_for_cost(grads_fn, sub_state,
                                                   stacked, rng)
                grads, loss_mean, master_dev = self._run_step(
                    step, dispatch, publish, grads_fn, sub_state, stacked,
                    rng)
            with span("train/swap_in"):
                self._ensure_opt_resident()
            with span("train/dispatch") as dispatch:
                if self._apply_batch_fn is None:
                    self._apply_batch_fn = self._make_apply(
                        divisor=1, device_master=True)
                new_state, metrics = self._run_step(
                    step, dispatch, publish, self._apply_batch_fn, self.state,
                    grads, master_dev)
            metrics = {**metrics, "loss": loss_mean}
        else:
            with span("train/dispatch") as dispatch:
                self._ensure_opt_resident()
                step_fn = self._get_train_step(ltd_tokens)
                rng = self._next_rng()
                if capture:
                    # lowering first also primes the jit trace cache, so the
                    # collective records land exactly once inside the capture
                    lowered = self._lower_for_cost(step_fn, self.state,
                                                   stacked, rng)
                new_state, metrics = self._run_step(
                    step, dispatch, publish, step_fn, self.state, stacked,
                    rng)
        poisoned = False
        if self._sentinel is not None:
            with span("train/readback"):
                loss_now = float(np.asarray(metrics["loss"]))
            poisoned = self._sentinel.observe(loss_now)
        rolled_back = False
        if poisoned:
            # keep the pre-step state: donation is disabled while the
            # sentinel is active, so self.state is still intact
            self.skipped_steps += 1
            if self.telemetry.enabled:
                self.telemetry.counter("sentinel/skipped_steps").inc(
                    1, step=self.global_steps)
            if self._sentinel.should_rollback():
                rolled_back = self._rollback_last_valid()
        else:
            with span("train/report"):
                self.state = self._dehydrate_state(new_state)
                self._spill_opt()
        self.timers(TRAIN_BATCH_TIMER).stop()
        self.tput_timer.stop(global_step=True)
        step_time = step.elapsed()

        if capture:
            self._comm_footprint = dist.comms_logger.end_trace_capture()
            if lowered is not None:
                from ..telemetry import compiled_cost

                # the executable is already in the jit cache, so this is
                # a cache hit, not a second compile
                self._step_cost = compiled_cost(lowered.compile())
            self._tele_captured = True

        if not rolled_back:
            # a rollback restored all counters from the checkpoint; the
            # poisoned batch that triggered it never happened
            self.global_steps += 1
            self.micro_steps += self.gradient_accumulation_steps()
            self.global_samples += self.train_batch_size()
        self._last_metrics = metrics
        if "model" in metrics:
            # into this step's record, as the device arrays they are: whoever
            # asks ``step_counters()`` or ``step_timeline(read=True)`` waits
            publish_step_counters("train_step", metrics["model"])
        if self.precision.is_fp16 and not rolled_back:
            with span("train/readback"):
                overflow = bool(metrics["overflow"])
            if overflow:
                self.skipped_steps += 1
        with span("train/report"):
            self._report_step(metrics)
            self._emit_step_telemetry(step_time)
            if self.resilience is not None:
                # preemption signal (or watchdog escalation) lands here, at
                # the step boundary: emergency save + TrainingPreempted
                self.resilience.check_step_boundary(self)
        return metrics["loss"]

    def eval_batch(self, data_iter=None, batch=None, compute_loss=True, bcast_loss=True):
        data = batch if batch is not None else data_iter
        if self._compiled_eval_step is None:
            self._compiled_eval_step = self._make_eval_step()
        stacked = self._stack_microbatches(data)
        return self._compiled_eval_step(self.state, stacked, self._next_rng())

    # -- legacy fwd/bwd/step API (reference ``engine.py:1775,1916,2114``)
    def forward(self, batch):
        """Compute loss for one microbatch; grads are cached for backward()."""
        if self._host_adam is not None:
            raise NotImplementedError(
                "the legacy forward/backward/step API is not supported with "
                "offload_optimizer.host_update (the update lives on host, "
                "outside the compiled apply); use train_batch()")
        if self._compiled_micro_step is None:
            self._compiled_micro_step = self._make_micro_step()
        self.timers(FORWARD_GLOBAL_TIMER).start()
        mb = jax.tree_util.tree_map(jnp.asarray, batch)
        sharding = jax.tree_util.tree_map(
            lambda x: NamedSharding(self.mesh.mesh, P(BATCH_AXES) if x.ndim == 1
                                    else P(BATCH_AXES, *([None] * (x.ndim - 1)))), mb)
        mb = jax.device_put(mb, sharding)
        loss, grads = self._compiled_micro_step(self.state, mb, self._next_rng())
        self._cached_loss, self._cached_grads = loss, grads
        self.timers(FORWARD_GLOBAL_TIMER).stop()
        return loss

    def backward(self, loss=None, allreduce_gradients=True, release_loss=False):
        """Accumulate the grads computed by the last forward()."""
        assert getattr(self, "_cached_grads", None) is not None, "call forward() first"
        self.timers(BACKWARD_GLOBAL_TIMER).start()
        if self._grad_acc_buffer is None:
            self._grad_acc_buffer = self._cached_grads
        else:
            self._grad_acc_buffer = jax.tree_util.tree_map(
                jnp.add, self._grad_acc_buffer, self._cached_grads
            )
        self._cached_grads = None
        self.micro_steps += 1
        self.timers(BACKWARD_GLOBAL_TIMER).stop()
        return loss

    def is_gradient_accumulation_boundary(self):
        return (self.micro_steps % self.gradient_accumulation_steps()) == 0

    def step(self):
        """Apply the accumulated gradient at a gas boundary."""
        assert self._grad_acc_buffer is not None, "no accumulated gradients"
        if self._compiled_apply is None:
            self._compiled_apply = self._make_apply()
        self.timers(STEP_GLOBAL_TIMER).start()
        self._ensure_opt_resident()
        new_state, metrics = self._compiled_apply(self.state, self._grad_acc_buffer)
        self.state = self._dehydrate_state(new_state)
        self._spill_opt()
        self._grad_acc_buffer = None
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        self._last_metrics = {**self._last_metrics, **metrics}
        self.timers(STEP_GLOBAL_TIMER).stop()
        self._report_step(metrics)
        if self.resilience is not None:
            self.resilience.check_step_boundary(self)

    def zero_grad(self):
        self._grad_acc_buffer = None

    def allreduce_gradients(self, bucket_size=None):
        """No-op: grad reduction happens inside the compiled step (XLA psum)."""

    # ------------------------------------------------------------- reporting
    def _lower_for_cost(self, fn, *args):
        """Lower the step's main compiled fn for HLO cost analysis.  The
        lowering primes the jit trace cache, so the subsequent call reuses
        it; ``.compile()`` afterwards hits the executable cache."""
        if not self.config.telemetry.hlo_cost_analysis:
            return None
        try:
            return fn.lower(*args)
        except Exception as e:
            logger.warning(f"telemetry: HLO lowering for cost analysis "
                           f"failed ({e}); MFU/MBU channels disabled")
            return None

    def _publish_memory_plan(self):
        """Expose the jaxpr-derived gather/release movement plan once the
        first traced step exists (``memory: auto``, zero-3).  Engine state,
        not telemetry: published whether or not channels are enabled."""
        if self.memory_plan is not None:
            return
        all_moves = []
        for fn in getattr(self, "_train_steps", {}).values():
            all_moves.extend(getattr(fn, "move_sites", ()))
        if not all_moves:
            return
        from ..comm.memplan import movement_summary

        self.memory_plan = tuple(all_moves)
        summ = movement_summary(self.memory_plan)
        log_dist(
            "comm.memplan[auto]: zero-3 movement plan -- "
            f"{summ['n_sites']} gather/release sites, "
            f"{summ['gathered_bytes'] / 2**20:.1f} MiB gathered, "
            f"peak live {summ['peak_live_bytes'] / 2**20:.1f} MiB, "
            f"mean span {summ['mean_live_span']:.1f} eqns",
            ranks=[0])

    def _emit_step_telemetry(self, step_time):
        """Per-step structured channels: wall time, HLO-derived MFU/MBU, and
        the per-execution collective bytes-on-wire footprint."""
        self._publish_memory_plan()
        tele = self.telemetry
        if not tele.enabled:
            return
        from ..telemetry import utilization

        step = self.global_steps
        tele.scalar("train/step_time_s").record(step_time, step=step)
        tele.scalar("train/samples_per_sec").record(
            self.train_batch_size() / max(step_time, 1e-9), step=step)
        util = (utilization(self._step_cost, step_time)
                if self._step_cost else None)
        if util:
            tele.scalar("train/flops_per_step").record(util["flops"], step=step)
            tele.scalar("train/hbm_bytes_per_step").record(
                util["bytes_accessed"], step=step)
            tele.scalar("train/tflops_per_sec").record(
                util["flops_per_s"] / 1e12, step=step)
            tele.scalar("train/mfu").record(
                util["mfu"], step=step, device_kind=util["device_kind"],
                n_devices=util["n_devices"])
            tele.scalar("train/mbu").record(util["mbu"], step=step)
        for name, value in self._last_metrics.get("model", {}).items():
            # a vector (a looped model's share of each exit) by index
            for i, x in enumerate(np.atleast_1d(np.asarray(value))):
                tele.scalar(f"train/model/{name}").record(
                    float(x), step=step, index=i)
        if self._comm_footprint:
            from ..telemetry.wire import variant_dtype
            total = 0.0
            for rec in self._comm_footprint:
                total += rec["bytes"]
                attrs = {"variant": rec["variant"],
                         "dtype": variant_dtype(rec["variant"]),
                         "n_ranks": rec["n_ranks"], "calls": rec["count"]}
                if rec.get("schedule"):
                    attrs["schedule"] = rec["schedule"]
                tele.scalar(f"comm/{rec['op']}/bytes_on_wire").record(
                    rec["bytes"], step=step, **attrs)
            tele.scalar("comm/bytes_on_wire_per_step").record(total, step=step)
            tele.counter("comm/bytes_on_wire_total").inc(total, step=step)
            # analytic exposed-vs-overlapped split: comm time at ICI peak vs
            # the slack the step left around its compute estimate
            from ..telemetry.hlo_cost import device_peaks
            from ..telemetry.wire import ici_bandwidth, overlap_estimate

            peak_flops, _, kind = device_peaks()
            compute_s = (self._step_cost["flops"]
                         / (peak_flops * max(len(jax.devices()), 1))
                         if self._step_cost else None)
            est = overlap_estimate(total, step_time, compute_s,
                                   ici_bandwidth(kind))
            tele.scalar("comm/est_comm_s").record(est["est_comm_s"], step=step)
            tele.scalar("comm/exposed_s").record(est["exposed_s"], step=step)
            tele.scalar("comm/overlapped_s").record(
                est["overlapped_s"], step=step)
            tele.scalar("comm/exposed_vs_overlapped").record(
                est["overlap_frac"], step=step, device_kind=kind)
        plan = self._reduction.plan
        if plan is not None:
            # compiler-driven scheduling pass stats (comm/schedule.py):
            # what the planner chose + what the hoist pass moved
            hoisted = ncoll = 0
            all_sites = []
            for fn in getattr(self, "_train_steps", {}).values():
                if hasattr(fn, "n_hoisted"):
                    hoisted += fn.n_hoisted
                    ncoll += fn.n_collectives
                all_sites.extend(getattr(fn, "sites", ()))
            tele.scalar("comm/schedule/hoisted_collectives").record(
                hoisted, step=step, collectives=ncoll,
                schedule=plan.tag, mode=plan.mode)
            if all_sites:
                # GSPMD-materialized (sharding_constraint) collectives: the
                # sites find_collectives classified from layout transitions;
                # surfaced in the wire telemetry AND written back onto the
                # plan so describe() shows them (the T3 satellite)
                from ..comm.schedule import implicit_wire_summary

                n_impl, impl_bytes = implicit_wire_summary(
                    all_sites, axis_sizes=dict(self.mesh.mesh.shape))
                plan.implicit_sites = n_impl
                plan.implicit_wire_bytes = impl_bytes
                if n_impl:
                    tele.scalar("comm/gspmd_implicit/bytes_on_wire").record(
                        impl_bytes, step=step, sites=n_impl,
                        schedule=plan.tag)
            if self.memory_plan:
                from ..comm.memplan import movement_summary

                summ = movement_summary(self.memory_plan)
                tele.scalar("memplan/peak_live_bytes").record(
                    summ["peak_live_bytes"], step=step,
                    sites=summ["n_sites"], mode=self._memory_mode)
        if step % self.config.steps_per_print == 0:
            tele.flush()

    def _report_step(self, metrics):
        if self.monitor.enabled and self.global_steps % self.config.steps_per_print == 0:
            events = [
                ("Train/Samples/train_loss", float(metrics.get("loss", 0.0)), self.global_samples),
                ("Train/Samples/lr", float(metrics.get("lr", 0.0)), self.global_samples),
            ]
            if self.precision.is_fp16:
                events.append(("Train/Samples/loss_scale",
                               float(metrics.get("loss_scale", 1.0)), self.global_samples))
            if self.curriculum_scheduler is not None:
                events.append(("Train/Samples/curriculum_difficulty",
                               float(self.curriculum_scheduler.get_current_difficulty()),
                               self.global_samples))
            if self.random_ltd_scheduler is not None:
                events.append(("Train/Samples/random_ltd_tokens",
                               float(self.random_ltd_scheduler.current_tokens),
                               self.global_samples))
            if self.progressive_layer_drop is not None:
                events.append(("Train/Samples/pld_theta",
                               float(self.progressive_layer_drop.current_theta),
                               self.global_samples))
            self.monitor.write_events(events)
        if self.config.wall_clock_breakdown and self.global_steps % self.config.steps_per_print == 0:
            self.timers.log([FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER,
                             STEP_GLOBAL_TIMER, TRAIN_BATCH_TIMER])

    # ------------------------------------------------------------ properties
    def train_batch_size(self):
        return self.config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self.config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self.config.gradient_accumulation_steps

    def zero_optimization_stage(self):
        return self.config.zero_config.stage

    def zero_optimization(self):
        return self.config.zero_enabled

    def fp16_enabled(self):
        return self.precision.is_fp16

    def bfloat16_enabled(self):
        return self.precision.is_bf16

    def get_lr(self):
        return [float(self._lr_fn(int(self.state["step"])))]

    def get_loss_scale(self):
        return float(self.state["loss_scale"].scale)

    @property
    def loss_scale(self):
        return self.get_loss_scale()

    def get_global_grad_norm(self):
        gn = self._last_metrics.get("grad_norm")
        return float(gn) if gn is not None else None

    def get_params(self):
        """Compute-dtype params (derived view of the master weights)."""

        def derive(m):
            if self._offload_optimizer:
                m = jax.device_put(m, self._master_dev_shardings)
            return self._compute_params(m)

        return jax.jit(derive)(self.state["master_params"])

    # ------------------------------------------------------------ dataloader
    def deepspeed_io(self, dataset, batch_size=None, route=None, pin_memory=True,
                     data_sampler=None, collate_fn=None, num_local_io_workers=None):
        from .dataloader import DeeperSpeedDataLoader

        bs = (batch_size or
              self.train_micro_batch_size_per_gpu() * self.mesh.data_parallel_size)
        # data-efficiency curriculum sampling (reference ``deepspeed_io``
        # building ``DeepSpeedDataSampler``, ``engine.py:1683``): draw batches
        # from the easiest prefix of a metric-sorted order, ramped by the
        # curriculum scheduler.  ``sorted_index_path`` is a DataAnalyzer
        # export (npy permutation); without one the natural order is used.
        ds_cfg = dict(self.config.data_efficiency.data_sampling)
        if data_sampler is None and self.config.data_efficiency.enabled \
                and ds_cfg.get("enabled"):
            from .data_pipeline.data_sampling.data_sampler import (
                DeeperSpeedDataSampler)

            sorted_index = None
            path = ds_cfg.get("sorted_index_path")
            if path:
                sorted_index = np.load(path)
            data_sampler = DeeperSpeedDataSampler(
                n_samples=len(dataset) if not isinstance(dataset, dict)
                else len(next(iter(dataset.values()))),
                batch_size=bs,
                curriculum_scheduler=self.curriculum_scheduler,
                sorted_index=sorted_index,
                seed=ds_cfg.get("seed", self.config.data_efficiency.seed),
                # the loader is drawn gas times per optimizer step
                draws_per_step=self.gradient_accumulation_steps(),
            )
        return DeeperSpeedDataLoader(
            dataset,
            batch_size=bs,
            collate_fn=collate_fn,
            drop_last=True,
            seed=self.config.seed,
            sampler=data_sampler,
        )

    # ------------------------------------------------------------ checkpoint
    def save_checkpoint(self, save_dir, tag=None, client_state=None, save_latest=True,
                        exclude_frozen_parameters=False):
        from .checkpointing import save_checkpoint

        self._ckpt_dir_hint = save_dir  # emergency-save / rollback target
        self._ensure_opt_resident()
        try:
            return save_checkpoint(self, save_dir, tag=tag,
                                   client_state=client_state or {},
                                   save_latest=save_latest)
        finally:
            self._spill_opt()

    def load_checkpoint(self, load_dir, tag=None, load_module_strict=True,
                        load_optimizer_states=True, load_lr_scheduler_states=True,
                        load_module_only=False, custom_load_fn=None):
        """Restore from ``load_dir`` -> (path, client state), the whole of
        it one ``setup/load_checkpoint`` span: a restart's time to first
        step has it beside ``setup/initialize``."""
        with span("setup/load_checkpoint"):
            return self._load_checkpoint(
                load_dir, tag=tag,
                load_optimizer_states=load_optimizer_states,
                load_module_only=load_module_only)

    def _load_checkpoint(self, load_dir, tag, load_optimizer_states,
                         load_module_only):
        # universal (per-parameter slice) checkpoints load through their own
        # path into any topology (reference ``engine.py:800``
        # ``load_universal_checkpoint``)
        if self.config.checkpoint_config.load_universal:
            from ..checkpoint.universal import load_universal_into_engine

            if tag is not None:
                logger.warning("load_universal: universal exports are untagged; "
                               f"ignoring tag={tag}")
            need_opt = load_optimizer_states and not load_module_only
            if need_opt:
                self._ensure_opt_resident()  # NVMe tier: template for restore
            try:
                meta = load_universal_into_engine(
                    self, load_dir,
                    load_optimizer_states=need_opt)
            finally:
                if need_opt:
                    self._spill_opt()
            return load_dir, meta.get("client_state", {})
        from .checkpointing import load_checkpoint

        self._ckpt_dir_hint = load_dir  # emergency-save / rollback target
        need_opt = load_optimizer_states and not load_module_only
        if need_opt:
            self._ensure_opt_resident()  # NVMe tier: template for restore
        try:
            return load_checkpoint(self, load_dir, tag=tag,
                                   load_optimizer_states=load_optimizer_states,
                                   load_module_only=load_module_only)
        finally:
            if need_opt:
                self._spill_opt()

    def _rollback_last_valid(self):
        """Sentinel escalation: after max_consecutive_bad poisoned steps,
        restore the newest checksum-valid tag in place and resume from it
        (reference analog: manual restart from the last good checkpoint;
        here the corrupt-tag walk-back does the tag selection)."""
        hint = self._ckpt_dir_hint
        n = self._sentinel._consecutive_bad
        if hint is None:
            logger.error("[sentinel] auto_rollback requested but no "
                         "checkpoint directory is known (save or load a "
                         "checkpoint first); continuing without rollback")
            self._sentinel.reset_bad()
            return False
        logger.warning(f"[sentinel] {n} consecutive poisoned steps; "
                       f"restoring last valid checkpoint under {hint}")
        ckpt_dir, _ = self.load_checkpoint(hint)
        if ckpt_dir is None:
            logger.error(f"[sentinel] rollback FAILED: no loadable "
                         f"checkpoint under {hint}")
            self._sentinel.reset_bad()
            return False
        self.telemetry.counter("ckpt/rollback_count").inc(
            1, step=self.global_steps, reason="sentinel")
        self._sentinel.rollback_done()
        return True

    # --------------------------------------------------------------- helpers
    def __call__(self, batch):
        return self.forward(batch)

    def destroy(self):
        """Release engine-owned resources (reference ``engine.destroy()``):
        the NVMe swap directory + its aio thread pool, the stall watchdog
        thread, and the telemetry sinks."""
        if self._opt_swapper is not None:
            self._opt_swapper.close()
            self._opt_swapper = None
        if self.watchdog is not None:
            self.timers.set_event_hook(None)
            self.watchdog.stop()
            self.watchdog = None
        if self.resilience is not None:
            self.resilience.uninstall()
            self.resilience = None
        self.telemetry.close()

    def train(self, mode=True):
        self._train_mode = mode
        return self

    def eval(self):
        return self.train(False)

"""`initialize()` -- the main entry point (reference ``deepspeed/__init__.py:64``).

Returns the reference's 4-tuple ``(engine, optimizer, dataloader,
lr_scheduler)``.  Engine selection mirrors ``deepspeed/__init__.py:156-196``:
a ``PipelineModule`` model gets the ``PipelineEngine``; anything else the base
``DeeperSpeedEngine``.
"""

import argparse

from .config import DeeperSpeedConfig
from .engine import DeeperSpeedEngine
from ..telemetry.trace import span
from ..utils.logging import log_dist


def initialize(
    args=None,
    model=None,
    optimizer=None,
    model_parameters=None,
    training_data=None,
    lr_scheduler=None,
    mpu=None,
    dist_init_required=None,
    collate_fn=None,
    config=None,
    mesh=None,
    loss_fn=None,
    config_params=None,
):
    with span("setup/initialize"):
        assert model is not None, "deeperspeed_tpu.initialize requires a model"
        if config is None:
            config = config_params
        if config is None and args is not None and hasattr(args, "deepspeed_config"):
            config = args.deepspeed_config
        assert config is not None, "no config: pass config= or args.deepspeed_config"

        _apply_overlap_xla_flags(config)
        model = _apply_moe_quantized_alltoall(model, config)

        from .pipe.module import PipelineModule

        if isinstance(model, PipelineModule) or hasattr(model, "stage_forward"):
            engine = _build_pipeline_engine(
                model, config, optimizer=optimizer,
                model_parameters=model_parameters, training_data=training_data,
                lr_scheduler=lr_scheduler, mesh=mesh, loss_fn=loss_fn,
                collate_fn=collate_fn,
            )
        elif _hybrid_enabled(config):
            # reference engine selection: hybrid config -> DeepSpeedHybridEngine
            # (``deepspeed/__init__.py:156-196``)
            from .hybrid_engine import DeeperSpeedHybridEngine

            engine = DeeperSpeedHybridEngine(
                model=model, config=config, optimizer=optimizer,
                model_parameters=model_parameters, training_data=training_data,
                lr_scheduler=lr_scheduler, mesh=mesh, loss_fn=loss_fn,
                collate_fn=collate_fn,
            )
        else:
            engine = DeeperSpeedEngine(
                model=model, config=config, optimizer=optimizer,
                model_parameters=model_parameters, training_data=training_data,
                lr_scheduler=lr_scheduler, mesh=mesh, mpu=mpu, loss_fn=loss_fn,
                collate_fn=collate_fn,
            )
    log_dist("initialize() complete", ranks=[0])
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def _apply_overlap_xla_flags(config):
    """``comm.overlap.xla_latency_hiding`` -> append the TPU
    latency-hiding-scheduler / async-collective-fusion flags to XLA_FLAGS.

    Peeked from the raw config (same runtime-gating idiom as the MoE
    all-to-all toggle below) so it runs BEFORE the engine forces backend
    init: XLA reads the flags exactly once, at backend creation.
    ``comm/overlap.py`` holds the flag table and refuses (with a warning)
    when the backend already initialized or the process is not targeting
    TPU -- unknown ``xla_tpu_*`` flags abort non-TPU clients."""
    if isinstance(config, str):
        import json

        try:
            with open(config) as f:
                config = json.load(f)
        except (OSError, ValueError):
            return
    if isinstance(config, DeeperSpeedConfig):
        ov = config.comm.overlap
        enabled = bool(ov.enabled and ov.xla_latency_hiding)
    elif isinstance(config, dict):
        o = config.get("comm", {}).get("overlap", {})
        enabled = bool(o.get("enabled")) and bool(o.get("xla_latency_hiding"))
    else:
        return
    if enabled:
        from ..comm.overlap import apply_xla_latency_hiding

        apply_xla_latency_hiding()


def _apply_moe_quantized_alltoall(model, config):
    """``comm.quantized.moe_alltoall`` -> flip the model's MoE dispatch to the
    int8 wire format (``moe/sharded_moe.py``).

    Config-gated at runtime so a serving/training JSON toggles it without
    editing model code; only applies to models whose config dataclass
    carries ``moe_quantized_alltoall`` (GPTNeoX family) -- others pass
    through untouched.
    """
    import dataclasses

    if isinstance(config, str):
        import json

        try:
            with open(config) as f:
                config = json.load(f)
        except (OSError, ValueError):
            return model
    if isinstance(config, DeeperSpeedConfig):
        cq = config.comm.quantized
    elif isinstance(config, dict):
        q = config.get("comm", {}).get("quantized", {})
        cq = argparse.Namespace(
            moe_alltoall=bool(q.get("moe_alltoall")),
            moe_alltoall_dtype=str(q.get("moe_alltoall_dtype", "int8")),
            group_size=int(q.get("group_size", 128)))
    else:
        return model
    mcfg = getattr(model, "config", None)
    if not (cq.moe_alltoall and dataclasses.is_dataclass(mcfg)
            and hasattr(mcfg, "moe_quantized_alltoall")):
        return model
    if not getattr(mcfg, "has_moe", False):
        return model
    new_cfg = dataclasses.replace(
        mcfg, moe_quantized_alltoall=True,
        moe_quantized_group_size=cq.group_size,
        moe_quantized_alltoall_dtype=getattr(cq, "moe_alltoall_dtype",
                                             "int8"))
    return model.clone(config=new_cfg) if hasattr(model, "clone") \
        else model.replace(config=new_cfg)


def _hybrid_enabled(config):
    """Peek the hybrid flag without paying a throwaway full config parse
    (the engine builds the real DeeperSpeedConfig itself)."""
    if isinstance(config, DeeperSpeedConfig):
        return bool(config.hybrid_engine.get("enabled"))
    if isinstance(config, dict):
        return bool(config.get("hybrid_engine", {}).get("enabled"))
    if isinstance(config, str):
        import json

        try:
            with open(config) as f:
                return bool(json.load(f).get("hybrid_engine", {}).get("enabled"))
        except (OSError, ValueError):
            return False
    return False


def _build_pipeline_engine(model, config, **kwargs):
    """Pick the pipeline execution strategy (config ``pipeline.executor``):

    * ``compiled`` -- the scan+ppermute single-kernel pipeline (GPT-NeoX
      family block graphs; fastest, GPipe-shaped memory).
    * ``interpreted`` -- the 1F1B instruction-stream executor
      (``pipe/interpreted.py``): arbitrary heterogeneous ``LayerSpec``
      graphs, ``TiedLayerSpec`` tying, 1F1B memory profile.
    * ``auto`` -- compiled when the module converts, else interpreted
      (mirrors reference engine selection, ``deepspeed/__init__.py:156-196``).
    """
    from .pipe.engine import PipelineEngine, PipelineError
    from .pipe.interpreted import InterpretedPipelineEngine
    from .pipe.module import PipelineModule

    cfg = config if isinstance(config, DeeperSpeedConfig) else DeeperSpeedConfig(
        config, mesh=kwargs.get("mesh"))
    executor = cfg.pipeline.executor
    if executor not in ("auto", "compiled", "interpreted"):
        raise ValueError(
            f"pipeline.executor={executor!r}: expected "
            "'auto', 'compiled' or 'interpreted'")

    def interpreted():
        # the interpreted engine computes loss on the last stage from the
        # PipelineModule's own loss_fn; an explicitly-passed loss_fn would be
        # silently ignored, so reject the ambiguity instead
        if kwargs.get("loss_fn") is not None:
            raise ValueError(
                "the interpreted pipeline takes its loss from "
                "PipelineModule(..., loss_fn=...); remove the loss_fn= "
                "argument to initialize()")
        if kwargs.get("model_parameters") is not None:
            raise ValueError(
                "model_parameters= is not supported on the interpreted "
                "pipeline path (params build per stage from the LayerSpecs)")
        kw = {k: v for k, v in kwargs.items()
              if k not in ("loss_fn", "model_parameters")}
        return InterpretedPipelineEngine(model, cfg, **kw)

    if executor == "interpreted":
        if hasattr(model, "stage_forward") and not isinstance(model, PipelineModule):
            raise ValueError(
                "pipeline.executor='interpreted' needs a PipelineModule; "
                f"got a stage model ({type(model).__name__})")
        return interpreted()
    if hasattr(model, "stage_forward") or executor == "compiled":
        return PipelineEngine(model=model, config=cfg, **kwargs)
    assert isinstance(model, PipelineModule)
    # auto: fall back to interpreted only when the module cannot CONVERT to
    # the compiled stage form -- errors raised later in engine construction
    # (e.g. mesh pp mismatch, with its actionable message) must surface,
    # not be masked by a fallback that fails differently
    from .pipe.engine import _pipe_module_to_stage_model

    try:
        _pipe_module_to_stage_model(model)
    except PipelineError:
        return interpreted()
    return PipelineEngine(model=model, config=cfg, **kwargs)


def add_config_arguments(parser):
    """Reference ``deepspeed/__init__.py:246``: bootstrap CLI flags."""
    group = parser.add_argument_group("DeeperSpeed-TPU", "configuration")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeeperSpeed-TPU (kept for CLI parity)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the json config")
    group.add_argument("--deeperspeed", default=False, action="store_true")
    group.add_argument("--deeperspeed_config", default=None, type=str)
    group.add_argument("--local_rank", type=int, default=-1)
    return parser

"""Pipeline-parallel engine.

Equivalent of reference ``runtime/pipe/engine.py:55`` (``PipelineEngine``),
re-designed for XLA: instead of interpreting 1F1B instruction streams with
eager p2p (``_exec_schedule`` ``pipe/engine.py:1331``), the whole
M-microbatch pipeline compiles into the train step (see ``compiled.py``).
The gas microbatches ARE the pipeline microbatches, matching the reference's
``train_batch`` contract (``pipe/engine.py:312``): one call consumes
``gradient_accumulation_steps`` microbatches and applies one optimizer step.

As in the reference (``pipe/engine.py`` forbids ``forward``/``backward``
outside schedules), the micro-level legacy API is unavailable on this engine.
"""

import jax
import jax.numpy as jnp

from ... import comm as dist
from ...utils.logging import log_dist
from .. import grad_reduce
from ..engine import DeeperSpeedEngine
from .compiled import make_pipeline_loss_fn
from .module import PipelineModule


class PipelineError(RuntimeError):
    pass


class PipelineEngine(DeeperSpeedEngine):
    def __init__(self, model, config, loss_fn=None, **kwargs):
        if isinstance(model, PipelineModule):
            model = _pipe_module_to_stage_model(model)
        if not hasattr(model, "stage_forward"):
            raise PipelineError(
                "PipelineEngine needs a stage model (e.g. models.GPTNeoXPipe) "
                "or a PipelineModule of homogeneous transformer blocks"
            )
        self._pipeline_loss = None
        self._pipeline_grads = None
        super().__init__(model=model, config=config, loss_fn=loss_fn, **kwargs)
        if getattr(self, "_compression", None) is not None:
            raise NotImplementedError(
                "compression_training is not supported on the compiled "
                "pipeline path (the pipeline loss bypasses _compute_params)")
        if self.progressive_layer_drop is not None:
            # the compiled pipeline loss reads only input_ids/labels/loss_mask
            # -- silently ignoring the injected theta would fake PLD while the
            # monitor logs it as active (same guard class as random-LTD below)
            raise NotImplementedError(
                "progressive_layer_drop is not supported on the compiled "
                "pipeline path")
        if self.mesh.pp != model.num_stages:
            raise PipelineError(
                f"mesh pp={self.mesh.pp} != model stages={model.num_stages}; set "
                f"config mesh.pipe_parallel_size to match"
            )
        if self.config.pipeline.schedule not in ("1f1b", "gpipe"):
            # a typo must not silently select the wrong memory profile
            raise PipelineError(
                f"pipeline.schedule={self.config.pipeline.schedule!r} is not "
                f"one of ('1f1b', 'gpipe')")
        self.num_stages = model.num_stages
        self.micro_batches = self.gradient_accumulation_steps()
        log_dist(
            f"PipelineEngine: {self.num_stages} stages x "
            f"{model.layers_per_stage} layers, {self.micro_batches} microbatches",
            ranks=[0],
        )

    def _builds_own_loss(self):
        return True

    def _get_pipeline_loss(self):
        if self._pipeline_loss is None:
            dtype = self.precision.param_dtype if self.precision.is_mixed else None
            self._pipeline_loss = make_pipeline_loss_fn(
                self.module, self.mesh, self.gradient_accumulation_steps(),
                compute_dtype=dtype,
            )
        return self._pipeline_loss

    # -------------------------------------------------- pipelined grads/loss
    def _get_pipeline_grads(self):
        if self._pipeline_grads is None:
            from .compiled_1f1b import make_pipeline_grad_fn

            dtype = self.precision.param_dtype if self.precision.is_mixed else None
            self._pipeline_grads = make_pipeline_grad_fn(
                self.module, self.mesh, self.gradient_accumulation_steps(),
                compute_dtype=dtype,
            )
        return self._pipeline_grads

    def _grads_for_batch(self, master, batch, rng, scale, ltd_tokens=None,
                         step=None, carried=None):
        # grads are taken w.r.t. the fp32 master directly; the compute-dtype
        # cast lives inside the pipeline's manual region (see compiled.py /
        # compiled_1f1b.py)
        if ltd_tokens is not None:
            raise NotImplementedError(
                "random-LTD is not supported on the compiled pipeline path")
        self._record_pipe_wire(batch)
        # the pipeline reduces grads once over the whole batch (the sharding
        # constraint below), not per microbatch
        grad_reduce.record_plain_wire(self, master, 1, self._reduction.tag)
        from ...utils.tree import tree_cast

        if self.config.pipeline.schedule == "1f1b":
            # manual-backward 1F1B: grads come straight out of the compiled
            # schedule (no jax.grad over the pipeline program)
            grad_fn = self._get_pipeline_grads()
            p = jax.lax.with_sharding_constraint(master, self.param_shardings)
            grads, loss = grad_fn(p, batch, rng, cot_scale=scale)
        else:
            loss_fn = self._get_pipeline_loss()

            def scaled(p):
                p = jax.lax.with_sharding_constraint(p, self.param_shardings)
                loss = loss_fn(p, batch, rng)
                return (loss * scale).astype(jnp.float32), loss

            (_, loss), grads = jax.value_and_grad(scaled, has_aux=True)(master)
        grads = tree_cast(grads, self.precision.accum_dtype)
        grads = jax.lax.with_sharding_constraint(grads, self.grad_shardings)
        return grads, loss, {}

    def _record_pipe_wire(self, batch):
        """Trace-time analytic bytes for the stage-to-stage ppermute traffic.

        The tick body traces several times under remat + autodiff, so the
        record lives here (one execution per compile) instead of inside the
        scan: (M + S - 1) ticks each moving a [B, S, H] activation buffer
        forward, and its transposed cotangent backward."""
        if not dist.comms_logger._capturing:
            return
        S = self.num_stages
        if S <= 1 or "input_ids" not in batch:
            return
        m, b, s = batch["input_ids"].shape
        dtype = jnp.dtype(self.module.config.dtype)
        ticks = m + S - 1
        dist.comms_logger.record_traced(
            "pipe_ppermute",
            2.0 * ticks * b * s * self.module.config.hidden_size * dtype.itemsize,
            S, variant=dtype.name, count=2 * ticks)

    def _make_eval_step(self):
        loss_fn = self._get_pipeline_loss()

        def eval_step(state, batch, rng):
            master = state["master_params"]
            params = jax.lax.with_sharding_constraint(master, self.param_shardings)
            return loss_fn(params, batch, None)  # eval: deterministic

        return jax.jit(eval_step, in_shardings=(self._state_shardings, None, self._repl))

    # ------------------------------------------- reference API restrictions
    def forward(self, *args, **kwargs):
        raise PipelineError("Only train_batch() and eval_batch() are accessible "
                            "on a pipeline engine (reference pipe/engine.py contract)")

    def backward(self, *args, **kwargs):
        raise PipelineError("Only train_batch() and eval_batch() are accessible "
                            "on a pipeline engine (reference pipe/engine.py contract)")

    def step(self, *args, **kwargs):
        raise PipelineError("Only train_batch() and eval_batch() are accessible "
                            "on a pipeline engine (reference pipe/engine.py contract)")

    def is_first_stage(self):
        return True  # single-controller: every process sees the whole pipeline

    def is_last_stage(self):
        return True

    def set_dataiterator(self, iterator):
        self._data_iterator = iterator


def _pipe_module_to_stage_model(pipe_module):
    """Convert a PipelineModule of homogeneous transformer-block specs into
    a stage model for the compiled path: GPT-NeoX-family blocks become
    GPTNeoXPipe, Llama-family blocks (Llama-2 / Mistral / untied OPT)
    become LlamaPipe (reference partitions arbitrary LayerSpec lists,
    ``pipe/module.py:370``; heterogeneous graphs go to the interpreted
    executor)."""
    from ...models.gpt_neox_pipe import GPTNeoXPipe
    from ...models.llama_pipe import LlamaPipe

    specs = pipe_module.specs
    block_cfgs = []
    for spec in specs:
        cfg = getattr(spec, "module_kwargs", {}).get("config") or (
            spec.module_args[0] if getattr(spec, "module_args", None) else None
        )
        if cfg is not None and type(cfg).__name__ in ("GPTNeoXConfig",
                                                      "LlamaConfig"):
            block_cfgs.append(cfg)
    if not block_cfgs or len(block_cfgs) != len(specs):
        raise PipelineError(
            "compiled pipeline requires a PipelineModule made solely of "
            "GPT-NeoX-family or Llama-family block LayerSpecs; construct "
            "models.GPTNeoXPipe/LlamaPipe(config, num_stages) directly, or "
            "use pipeline.executor='interpreted' for heterogeneous graphs"
        )
    blk_cfg = block_cfgs[0]
    if any(c is not blk_cfg and c != blk_cfg for c in block_cfgs):
        raise PipelineError("PipelineModule block specs carry differing configs")
    if len(block_cfgs) != blk_cfg.num_layers:
        raise PipelineError(
            f"PipelineModule has {len(block_cfgs)} block specs but the config "
            f"says num_layers={blk_cfg.num_layers}; the compiled pipeline "
            f"builds from the config -- make them agree (e.g. "
            f"dataclasses.replace(cfg, num_layers={len(block_cfgs)}))"
        )
    family = (LlamaPipe if type(blk_cfg).__name__ == "LlamaConfig"
              else GPTNeoXPipe)
    return family(blk_cfg, pipe_module.num_stages)

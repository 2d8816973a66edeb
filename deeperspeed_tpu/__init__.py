"""DeeperSpeed-TPU: a TPU-native large-scale training framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of DeeperSpeed
(EleutherAI's DeepSpeed fork, see /root/reference): ZeRO-style partitioned
data parallelism, pipeline parallelism, tensor parallelism, MoE expert
parallelism, Ulysses sequence parallelism + ring attention, mixed precision
with dynamic loss scaling, fused Pallas kernels, checkpoint save/reshape/
resume, monitors, profilers, and an elastic launcher -- all expressed as
sharded computations on a named `jax.sharding.Mesh` rather than as an
eager hook-based wrapper.

Public API shape follows the reference (`deepspeed/__init__.py:64,246,269`):

    import deeperspeed_tpu as dst
    engine = dst.initialize(model=..., config=...)[0]
    loss = engine.train_batch(batch)
"""

import time as _time

_IMPORT_T0 = _time.perf_counter()   # ``setup/import`` runs from here

__version__ = "0.1.0"
__git_branch__ = "main"

from . import comm  # noqa: F401
from .accelerator import get_accelerator  # noqa: F401
from .runtime.config import DeeperSpeedConfig  # noqa: F401
from .runtime.engine import DeeperSpeedEngine  # noqa: F401
from .runtime.initialize import initialize, add_config_arguments  # noqa: F401
from .runtime.pipe.module import PipelineModule, LayerSpec, TiedLayerSpec  # noqa: F401
from .parallel.topology import ProcessTopology, PipeModelDataParallelTopology  # noqa: F401
from .utils import logging as _logging  # noqa: F401
from .telemetry.trace import keep_setup_span as _keep_setup_span

# the whole of this import, for ``telemetry.setup_timeline()``
_keep_setup_span("setup/import", _IMPORT_T0, _time.perf_counter())


def init_distributed(dist_backend=None, **kwargs):
    """Initialize the distributed runtime (multi-host JAX or single-host).

    Mirrors ``deepspeed.init_distributed`` (reference ``comm/comm.py:604``):
    idempotent, safe to call before :func:`initialize`.
    """
    return comm.init_distributed(dist_backend=dist_backend, **kwargs)


def init_inference(model=None, config=None, **kwargs):
    """Build an inference engine (reference ``deepspeed/__init__.py:269``)."""
    from .inference.engine import InferenceEngine
    from .inference.config import DeeperSpeedInferenceConfig

    if config is None:
        config = DeeperSpeedInferenceConfig(**kwargs)
    elif isinstance(config, dict):
        config = DeeperSpeedInferenceConfig(**{**config, **kwargs})
    return InferenceEngine(model=model, config=config)

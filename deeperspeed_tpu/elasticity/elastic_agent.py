"""Elastic restart agent.

Equivalent of reference ``elasticity/elastic_agent.py:60`` (``DSElasticAgent``
extending torch-elastic's ``LocalElasticAgent``): supervise the training
function, and on failure re-resolve the world (devices may have come or
gone), recompute the elastic batch configuration, and restart from the
latest checkpoint.  The reference delegates rendezvous to torch elastic; a
single-controller JAX job has no in-job rendezvous -- membership changes
arrive as a new device/host set on restart (GKE JobSet / PJRT re-init), so
the agent's job is the *restart policy* + *batch re-resolution*, with
recovery = checkpoint resume (exactly the reference's recovery model,
SURVEY §5 "failure detection").
"""

import time
from typing import Callable, Optional

from ..utils.logging import logger
from .elasticity import compute_elastic_config


class WorkerFailure(RuntimeError):
    pass


class DSElasticAgent:
    """Run ``train_fn(config, resume_dir)`` under an elastic restart policy.

    ``train_fn`` contract: build the engine from ``config`` (whose batch
    keys the agent re-resolves per restart), load the checkpoint when
    ``resume_dir`` is set, train, and either return normally or raise.

    ``world_size_fn`` returns the currently-available chip count (defaults
    to ``len(jax.devices())``); it is re-queried before every (re)start so a
    shrunk/grown slice gets a compatible batch per the elastic algebra
    (reference ``compute_elastic_config`` driving the v0.1/v0.2 schedules).

    One process for each chip: the default ``world_size_fn`` asks JAX in
    THIS process, which from then on holds the chip.  That is right for a
    ``train_fn`` that trains in this process.  A ``train_fn`` that starts
    worker processes must be given a ``world_size_fn`` that does not touch
    JAX -- a worker whose parent holds the chip fails or hangs.
    """

    def __init__(self, train_fn: Callable, config: dict,
                 checkpoint_dir: Optional[str] = None,
                 max_restarts: int = 3, restart_delay_s: float = 0.0,
                 world_size_fn: Optional[Callable[[], int]] = None):
        self.train_fn = train_fn
        self.base_config = dict(config)
        self.checkpoint_dir = checkpoint_dir
        self.max_restarts = max_restarts
        self.restart_delay_s = restart_delay_s
        if world_size_fn is None:
            def world_size_fn():
                import jax

                return len(jax.devices())
        self.world_size_fn = world_size_fn
        self.restart_count = 0
        self.history = []

    def _resolve_config(self, world_size):
        cfg = dict(self.base_config)
        el = cfg.get("elasticity", {})
        if el.get("enabled"):
            final_batch, _, micro = compute_elastic_config(
                cfg, world_size=world_size, return_microbatch=True)
            cfg["train_batch_size"] = final_batch
            cfg["train_micro_batch_size_per_gpu"] = micro
            cfg.pop("gradient_accumulation_steps", None)
            logger.info(
                f"elastic agent: world={world_size} -> batch={final_batch} "
                f"micro={micro}")
        return cfg

    def run(self):
        """Supervise until success or restarts are exhausted.  Returns the
        train_fn result; raises ``WorkerFailure`` after the final attempt."""
        import os

        attempt = 0
        while True:
            world = int(self.world_size_fn())
            cfg = self._resolve_config(world)
            # resume whenever a committed checkpoint exists -- a whole-process
            # restart (JobSet reschedules the pod) arrives here as attempt 0
            # and must NOT retrain from scratch over its own checkpoints
            resume = None
            if self.checkpoint_dir and os.path.isfile(
                    os.path.join(self.checkpoint_dir, "latest")):
                resume = self.checkpoint_dir
            t0 = time.time()
            try:
                result = self.train_fn(cfg, resume)
                self.history.append({"attempt": attempt, "world": world,
                                     "ok": True,
                                     "duration_s": time.time() - t0})
                return result
            except Exception as e:  # noqa: BLE001 - any worker failure
                self.history.append({"attempt": attempt, "world": world,
                                     "ok": False, "error": repr(e),
                                     "duration_s": time.time() - t0})
                attempt += 1
                self.restart_count = attempt
                if attempt > self.max_restarts:
                    raise WorkerFailure(
                        f"training failed after {self.max_restarts} restarts"
                    ) from e
                logger.warning(
                    f"elastic agent: attempt {attempt - 1} failed ({e!r}); "
                    f"restarting ({attempt}/{self.max_restarts})")
                if self.restart_delay_s:
                    time.sleep(self.restart_delay_s)

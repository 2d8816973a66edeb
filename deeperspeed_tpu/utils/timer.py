"""Wall-clock + throughput timers.

TPU-native rethink of reference ``deepspeed/utils/timer.py``: instead of CUDA
events we use host wall clock around `jax.block_until_ready` fences.  Under
XLA the device queue is asynchronous exactly like CUDA streams, so a timer
`stop()` optionally synchronizes before reading the clock.
"""

import time

from .logging import log_dist

FORWARD_MICRO_TIMER = "fwd_microstep"
FORWARD_GLOBAL_TIMER = "fwd"
BACKWARD_MICRO_TIMER = "bwd_microstep"
BACKWARD_GLOBAL_TIMER = "bwd"
STEP_MICRO_TIMER = "step_microstep"
STEP_GLOBAL_TIMER = "step"
TRAIN_BATCH_TIMER = "train_batch"


def _sync_device(who):
    """Fence the device queues; ``who`` names the caller on the fence's
    span (``dst:train/fence``), so a trace says who made the host wait."""
    try:
        import jax
        import jax.numpy as jnp

        from ..telemetry.trace import span

        # Fence: block on a trivial device computation.  Per-device queues
        # execute in order, so this lands only after all pending work;
        # jax.effects_barrier() only waits on *effectful* ops and does not
        # drain ordinary pending computations.
        with span("train/fence", who=who):
            for d in jax.local_devices():
                jax.device_put(jnp.zeros(()), d).block_until_ready()
    except Exception:
        pass


class _Timer:
    # bound on record=True intervals kept per timer: enough for a long run's
    # distribution without growing without limit
    MAX_RECORDS = 4096

    def __init__(self, name, on_event=None):
        self.name_ = name
        self.started_ = False
        self.elapsed_ = 0.0
        self.start_time = 0.0
        self.count = 0
        self.records = []  # intervals (seconds) captured via stop(record=True)
        self.on_event = on_event  # callable(name, "start"|"stop", elapsed|None)

    def start(self):
        assert not self.started_, f"{self.name_} timer has already been started"
        self.start_time = time.time()
        self.started_ = True
        if self.on_event is not None:
            self.on_event(self.name_, "start", None)

    def stop(self, reset=False, record=False):
        assert self.started_, f"{self.name_} timer is not started"
        elapsed = time.time() - self.start_time
        if reset:
            self.elapsed_ = elapsed
        else:
            self.elapsed_ += elapsed
        self.started_ = False
        self.count += 1
        if record:
            if len(self.records) >= self.MAX_RECORDS:
                del self.records[: self.MAX_RECORDS // 2]
            self.records.append(elapsed)
        if self.on_event is not None:
            self.on_event(self.name_, "stop", elapsed)
        return elapsed

    def reset(self):
        self.elapsed_ = 0.0
        self.started_ = False
        self.count = 0
        self.records = []

    def elapsed(self, reset=True):
        started = self.started_
        if started:
            self.stop()
        elapsed = self.elapsed_
        if reset:
            self.reset()
        if started:
            self.start()
        return elapsed

    def mean(self):
        return (self.elapsed_ / self.count) if self.count else 0.0


class SynchronizedWallClockTimer:
    """Named-timer group with optional device synchronization on stop.

    ``on_event(name, "start"|"stop", elapsed)`` fires on every timer
    transition -- the stall watchdog subscribes here to track the last
    completed phase (fwd/bwd/step/pipe-stage).
    """

    def __init__(self, synchronize=True, on_event=None):
        self.timers = {}
        self.synchronize = synchronize
        self.on_event = on_event

    def set_event_hook(self, on_event):
        self.on_event = on_event
        for t in self.timers.values():
            t.on_event = on_event

    def __call__(self, name):
        if name not in self.timers:
            self.timers[name] = _Timer(name, on_event=self.on_event)
        return self.timers[name]

    def has_timer(self, name):
        return name in self.timers

    def get_timers(self):
        return self.timers

    @staticmethod
    def memory_usage():
        try:
            import jax

            stats = jax.local_devices()[0].memory_stats() or {}
            in_use = stats.get("bytes_in_use", 0)
            peak = stats.get("peak_bytes_in_use", 0)
            return f"MemAllocated={in_use / 2**30:.2f} GB, MaxMemAllocated={peak / 2**30:.2f} GB"
        except Exception:
            return "MemAllocated=? GB"

    def log(self, names, normalizer=1.0, reset=True, memory_breakdown=False, ranks=None):
        assert normalizer > 0.0
        if self.synchronize:
            _sync_device("wall_clock")
        string = "time (ms)"
        for name in names:
            if name in self.timers:
                elapsed_time = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                string += f" | {name}: {elapsed_time:.2f}"
        log_dist(string, ranks=ranks or [0])

    def get_mean(self, names, normalizer=1.0, reset=True):
        assert normalizer > 0.0
        means = {}
        for name in names:
            if name in self.timers:
                elapsed_time = self.timers[name].mean() * 1000.0 / normalizer
                means[name] = elapsed_time
                if reset:
                    self.timers[name].reset()
        return means


class ThroughputTimer:
    """Samples/sec + TFLOPS reporting (reference ``utils/timer.py:198``)."""

    def __init__(self, batch_size, start_step=2, steps_per_output=50, monitor_memory=False, logging_fn=None):
        self.start_time = 0
        self.end_time = 0
        self.started = False
        self.batch_size = max(1, batch_size)
        self.start_step = start_step
        self.epoch_count = 0
        self.micro_step_count = 0
        self.global_step_count = 0
        self.total_elapsed_time = 0
        self.step_elapsed_time = 0
        self.steps_since_output = 0
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.logging = logging_fn or log_dist
        self.initialized = False

    def update_epoch_count(self):
        self.epoch_count += 1
        self.micro_step_count = 0

    def _init_timer(self):
        self.initialized = True

    def start(self):
        self._init_timer()
        self.started = True
        if self.global_step_count >= self.start_step:
            _sync_device("throughput_timer.start")
            self.start_time = time.time()

    def stop(self, global_step=False, report_speed=True):
        if not self.started:
            return
        self.started = False
        self.micro_step_count += 1
        if global_step:
            self.global_step_count += 1
        if self.start_time > 0:
            _sync_device("throughput_timer.stop")
            self.end_time = time.time()
            duration = self.end_time - self.start_time
            self.total_elapsed_time += duration
            self.step_elapsed_time += duration
            if global_step:
                self.steps_since_output += 1
            if global_step and report_speed and self.global_step_count % self.steps_per_output == 0:
                curr = self.batch_size * self.steps_since_output / self.step_elapsed_time
                self.logging(
                    f"epoch={self.epoch_count}/micro_step={self.micro_step_count}/"
                    f"global_step={self.global_step_count}, RunningAvgSamplesPerSec="
                    f"{self.avg_samples_per_sec():.2f}, CurrSamplesPerSec={curr:.2f}"
                )
                self.step_elapsed_time = 0
                self.steps_since_output = 0

    def avg_samples_per_sec(self):
        if self.global_step_count > self.start_step and self.total_elapsed_time > 0:
            samples = self.batch_size * (self.global_step_count - self.start_step)
            return samples / self.total_elapsed_time
        return float("-inf")

#!/usr/bin/env python
"""Fault-injection harness: storage faults (PR 3) + serving faults (PR 6).

**Storage** -- deterministically injects faults into the checkpoint
engine's IO seam (``runtime/checkpoint_engine/checkpoint_engine.py``:
``_io_open`` / ``_io_fsync`` / ``_io_replace``) and asserts the
durability contract:

* ``latest`` only ever points at a tag whose ``manifest.json`` verifies,
* a save killed at ANY io operation (mid-shard-write, pre-commit,
  post-commit/pre-latest) leaves the previous valid tag loadable with
  bit-exact payloads,
* a corrupted newest tag is skipped in favor of the previous valid tag,
* interrupted tags are garbage-collected by the next save.

**Serving** -- injects round-level faults into the v2 inference engine's
scheduling-round seam (``inference/v2/engine_v2.py``: ``_round_seam``)
under a live :class:`ServingFrontend` and asserts the resilience
contract: every scenario ends with the front end serving again, zero
leaked KV blocks, and the typed serving telemetry populated.

* ``nan_logits``  -- non-finite logits: failed round requeued with
  backoff, a persistent offender quarantined by the circuit breaker,
* ``oom_round``   -- MemoryError mid-round: blocks freed, work requeued,
* ``slow_step``   -- a crawling round: watchdog fires, degradation
  ladder escalates, then auto-recovers on calm rounds,
* ``flood``       -- admission burst: overload shedding with retry-after,
  goodput-under-deadline strictly above the no-shedding baseline,
* ``spec_reject_storm`` -- zero draft acceptance forced on every
  speculative round: COW rollback frees every forked tail block, the
  accept-rate governor degrades to k=0, then re-probes after cooldown.

Scenarios::

    python tools/chaos.py --scenario kill --workdir /tmp/chaos
    python tools/chaos.py --scenario storage     # torn_write eio bitflip kill
    python tools/chaos.py --scenario serving     # nan_logits oom_round slow_step flood
    python tools/chaos.py --scenario all

Storage scenarios run a stub engine writing real bytes through the real
``write_checkpoint`` path into a tmpdir; serving scenarios run a real
tiny-model engine forced onto CPU.  The pytest wrappers
(``tests/unit/checkpoint/test_integrity.py``,
``tests/unit/inference/test_chaos_serving.py``) run the same scenarios as
tier-1 tests.
"""

import argparse
import builtins
import json
import os
import shutil
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from deeperspeed_tpu.runtime.checkpoint_engine import checkpoint_engine as ce  # noqa: E402
from deeperspeed_tpu.runtime import checkpointing as ck  # noqa: E402


class KilledMidSave(BaseException):
    """Simulated kill -9: deliberately NOT an Exception so ordinary
    ``except Exception`` cleanup in the code under test cannot swallow it,
    mirroring how a real SIGKILL skips all handlers."""


class FaultInjector:
    """Patches the checkpoint engine's IO seam to fire one fault at the
    Nth matching operation.  Ops are counted per (kind) so a scenario is
    reproducible: op_index=k means 'the k-th write-open / fsync / replace
    since arming'."""

    def __init__(self):
        self.mode = None       # 'eio' | 'kill' | 'torn_write' | 'bitflip'
        self.op_kind = None    # 'open_w' | 'fsync' | 'replace'
        self.op_index = None
        self.counts = {"open_w": 0, "fsync": 0, "replace": 0}
        self.fired = False
        self._installed = False
        self._orig = {}

    # -- arming ------------------------------------------------------------

    def arm(self, mode, op_kind, op_index):
        self.mode = mode
        self.op_kind = op_kind
        self.op_index = op_index
        self.counts = {k: 0 for k in self.counts}
        self.fired = False

    def disarm(self):
        self.mode = None
        self.fired = False

    def install(self):
        if self._installed:
            return self
        self._orig = {"open": ce._io_open, "fsync": ce._io_fsync,
                      "replace": ce._io_replace}
        ce._io_open = self._open
        ce._io_fsync = self._fsync
        ce._io_replace = self._replace
        self._installed = True
        return self

    def uninstall(self):
        if not self._installed:
            return
        ce._io_open = self._orig["open"]
        ce._io_fsync = self._orig["fsync"]
        ce._io_replace = self._orig["replace"]
        self._installed = False

    def __enter__(self):
        return self.install()

    def __exit__(self, *exc):
        self.uninstall()

    def _should_fire(self, kind):
        if self.mode is None or self.fired or kind != self.op_kind:
            return False
        self.counts[kind] += 1
        if self.counts[kind] - 1 != self.op_index:
            return False
        self.fired = True
        return True

    # -- seam implementations ---------------------------------------------

    def _open(self, path, mode="r", *a, **kw):
        if "w" in mode or "a" in mode or "+" in mode:
            if self._should_fire("open_w"):
                if self.mode == "kill":
                    raise KilledMidSave(f"kill at open({path!r})")
                if self.mode == "eio":
                    raise OSError(5, "Input/output error (injected)", path)
                if self.mode == "torn_write":
                    return _TornFile(builtins.open(path, mode, *a, **kw))
        return builtins.open(path, mode, *a, **kw)

    def _fsync(self, fd):
        if self._should_fire("fsync"):
            if self.mode == "kill":
                raise KilledMidSave("kill at fsync")
            if self.mode == "eio":
                raise OSError(5, "Input/output error (injected)")
        return os.fsync(fd)

    def _replace(self, src, dst):
        if self._should_fire("replace"):
            if self.mode == "kill":
                raise KilledMidSave(f"kill at replace(-> {dst!r})")
            if self.mode == "eio":
                raise OSError(5, "Input/output error (injected)", dst)
            if self.mode == "torn_write":
                # a torn write that tmp+rename would otherwise hide: the
                # rename happens, but the payload lost its tail (as if the
                # device lied about the flush)
                with builtins.open(src, "rb") as f:
                    data = f.read()
                with builtins.open(src, "wb") as f:
                    f.write(data[:max(0, len(data) // 2)])
        return os.replace(src, dst)


class _TornFile:
    """File proxy that drops the second half of every write."""

    def __init__(self, f):
        self._f = f

    def write(self, data):
        return self._f.write(data[:max(0, len(data) // 2)])

    def __getattr__(self, name):
        return getattr(self._f, name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._f.close()


def flip_one_bit(path, byte_index=0):
    """Post-hoc bit-flip corruption of an on-disk artifact."""
    with builtins.open(path, "r+b") as f:
        f.seek(byte_index)
        b = f.read(1)
        f.seek(byte_index)
        f.write(bytes([b[0] ^ 0x40]))


# ---------------------------------------------------------------------------
# stub engine: real write_checkpoint/open_checkpoint path, no accelerator
# ---------------------------------------------------------------------------

class _StubConfig:
    def __init__(self, writer=None):
        from deeperspeed_tpu.runtime.config import CheckpointConfig

        kw = {"writer": writer} if writer else {}
        self.checkpoint_config = CheckpointConfig(
            io_retries=0, **kw)  # no retry: injected EIO must surface


class _StubEngine:
    """Just enough engine surface for write_checkpoint/open_checkpoint."""

    def __init__(self, writer=None):
        self.config = _StubConfig(writer)
        self.checkpoint_engine = None
        self.telemetry = None
        self.watchdog = None
        self.micro_steps = 0


def _payload(step):
    """Deterministic, step-distinct artifact payloads."""
    model = (b"model-step-%06d-" % step) * 257
    optim = (b"optim-step-%06d-" % step) * 131
    return model, optim


def save_step(engine, workdir, step):
    model, optim = _payload(step)
    return ck.write_checkpoint(
        engine, workdir, f"global_step{step}",
        model_bytes=lambda: model, optim_bytes=lambda: optim,
        meta={"tag": f"global_step{step}", "global_steps": step},
        save_latest=True)


def assert_recoverable(workdir, expect_step, context="", check_latest=True):
    """The durability contract: whatever just happened, the directory must
    resolve to a checksum-valid tag holding step ``expect_step``'s exact
    bytes.

    ``check_latest`` additionally asserts the ``latest`` pointer itself
    names a verifying tag -- true for any SAVE-time fault (commit gates the
    pointer), but deliberately not for at-rest corruption of an already
    committed tag, where the pointer is stale by design and the load-path
    walk-back is the defense."""
    tag, ckpt_dir, _ = ck.resolve_valid_checkpoint(workdir)
    assert tag == f"global_step{expect_step}", \
        f"{context}: resolved {tag!r}, expected step {expect_step}"
    ok, errors = ce.verify_manifest(ckpt_dir)
    assert ok, f"{context}: manifest verify failed: {errors}"
    model, optim = _payload(expect_step)
    with builtins.open(os.path.join(ckpt_dir, ck.MODEL_FILE), "rb") as f:
        assert f.read() == model, f"{context}: model bytes differ"
    with builtins.open(os.path.join(ckpt_dir, ck.OPTIM_FILE), "rb") as f:
        assert f.read() == optim, f"{context}: optim bytes differ"
    if check_latest:
        # `latest` itself must point at a valid tag (never a torn save)
        latest = ck.read_latest_tag(workdir)
        ok, errors = ce.verify_manifest(os.path.join(workdir, latest))
        assert ok, f"{context}: latest -> {latest} fails verification: {errors}"


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------

def scenario_kill(workdir, writer=None):
    """Kill the process at EVERY injectable io op of a save, one run per op
    index, and prove resume always lands on a valid checkpoint."""
    results = []
    for op_kind in ("open_w", "fsync", "replace"):
        op_index = 0
        while True:
            shutil.rmtree(workdir, ignore_errors=True)
            os.makedirs(workdir)
            engine = _StubEngine(writer)
            inj = FaultInjector()
            with inj:
                save_step(engine, workdir, 1)  # baseline valid checkpoint
                inj.arm("kill", op_kind, op_index)
                died = False
                try:
                    save_step(engine, workdir, 2)
                except KilledMidSave:
                    died = True
                except (RuntimeError, OSError):
                    # async writer: the kill lands in a pool thread and
                    # surfaces as a failed commit -- same durability claim
                    died = True
                inj.disarm()
            if not died:
                # op_index ran past the save's op count: kill landed
                # nowhere, the save completed -- step 2 must be valid
                assert_recoverable(workdir, 2,
                                   f"kill {op_kind}[{op_index}] (no-op)")
                break
            expect = 2 if ck.read_latest_tag(workdir) == "global_step2" else 1
            assert_recoverable(workdir, expect,
                               f"kill at {op_kind}[{op_index}]")
            # next save must GC the interrupted tag and succeed
            engine2 = _StubEngine(writer)
            save_step(engine2, workdir, 3)
            assert_recoverable(workdir, 3,
                               f"save after kill at {op_kind}[{op_index}]")
            leftover = [d for d in os.listdir(workdir)
                        if os.path.isdir(os.path.join(workdir, d))
                        and os.path.isfile(os.path.join(
                            workdir, d, ck.INCOMPLETE_MARKER))]
            assert not leftover, \
                f"kill at {op_kind}[{op_index}]: interrupted tags not " \
                f"GC'd: {leftover}"
            results.append(f"{op_kind}[{op_index}]: recovered at step {expect}")
            op_index += 1
    return results


def scenario_eio(workdir, writer=None):
    """EIO during a save must fail the commit loudly and leave the previous
    checkpoint as the loadable latest."""
    results = []
    for op_kind in ("open_w", "fsync", "replace"):
        shutil.rmtree(workdir, ignore_errors=True)
        os.makedirs(workdir)
        engine = _StubEngine(writer)
        inj = FaultInjector()
        with inj:
            save_step(engine, workdir, 1)
            inj.arm("eio", op_kind, 0)
            failed = False
            try:
                save_step(engine, workdir, 2)
            except (OSError, RuntimeError):
                failed = True
            inj.disarm()
        assert failed, f"eio at {op_kind}[0] was silently swallowed"
        assert_recoverable(workdir, 1, f"eio at {op_kind}[0]")
        results.append(f"{op_kind}[0]: commit failed loudly, step 1 intact")
    return results


def scenario_torn_write(workdir, writer=None):
    """A torn artifact (half the payload lost at rename time) must fail
    commit verification; a torn file planted post-commit must be caught by
    the load-path walk-back."""
    results = []
    # torn during save: commit must refuse
    engine = _StubEngine(writer)
    inj = FaultInjector()
    with inj:
        save_step(engine, workdir, 1)
        inj.arm("torn_write", "replace", 0)
        failed = False
        try:
            save_step(engine, workdir, 2)
        except RuntimeError:
            failed = True
        inj.disarm()
    assert failed, "torn write passed commit verification"
    assert_recoverable(workdir, 1, "torn write during save")
    results.append("torn-at-replace: commit refused, step 1 intact")
    # torn after commit (silent corruption at rest): walk-back catches it
    engine = _StubEngine(writer)
    save_step(engine, workdir, 2)
    tag_dir = os.path.join(workdir, "global_step2")
    path = os.path.join(tag_dir, ck.MODEL_FILE)
    with builtins.open(path, "rb") as f:
        data = f.read()
    with builtins.open(path, "wb") as f:
        f.write(data[:len(data) // 2])
    assert_recoverable(workdir, 1, "torn at rest in newest tag",
                       check_latest=False)
    results.append("torn-at-rest: newest tag skipped, step 1 served")
    return results


def scenario_bitflip(workdir, writer=None):
    """A single flipped bit in any artifact of the newest tag must be
    detected and the previous tag served instead."""
    results = []
    for name in (ck.MODEL_FILE, ck.OPTIM_FILE, ck.ENGINE_FILE):
        shutil.rmtree(workdir, ignore_errors=True)
        os.makedirs(workdir)
        engine = _StubEngine(writer)
        save_step(engine, workdir, 1)
        save_step(engine, workdir, 2)
        flip_one_bit(os.path.join(workdir, "global_step2", name),
                     byte_index=7)
        assert_recoverable(workdir, 1, f"bitflip in {name}",
                           check_latest=False)
        results.append(f"{name}: flip detected, step 1 served")
    return results


# ---------------------------------------------------------------------------
# serving chaos: round-level faults under a live ServingFrontend (PR 6)
# ---------------------------------------------------------------------------

def _force_cpu():
    """Serving scenarios must be hermetic: a tiny model on CPU, never the
    session's accelerator."""
    os.environ["DST_ACCELERATOR"] = "cpu"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


class ServingFaultInjector:
    """Patches ``engine_v2._round_seam`` to fire a fault in a window of
    scheduling rounds.  Round counting starts at ``install()``; the window
    is ``[fire_at, fire_at + n_rounds)`` over rounds that actually
    dispatched (the seam runs after the compiled step returns, before
    ``commit_tokens`` -- the failure surface of a real device fault)."""

    def __init__(self):
        # 'nan_logits' | 'oom_round' | 'slow_step' | 'spec_reject_storm'
        self.mode = None
        self.fire_at = 0
        self.n_rounds = 0
        self.delay_s = 0.0
        self.round = 0          # rounds seen since install
        self.fired_rounds = 0
        self._installed = False
        self._orig = None

    def arm(self, mode, fire_at=None, n_rounds=1, delay_s=0.0):
        self.mode = mode
        self.fire_at = self.round if fire_at is None else fire_at
        self.n_rounds = n_rounds
        self.delay_s = delay_s

    def disarm(self):
        self.mode = None

    def install(self):
        if self._installed:
            return self
        from deeperspeed_tpu.inference.v2 import engine_v2 as ev2

        self._ev2 = ev2
        self._orig = ev2._round_seam
        ev2._round_seam = self._seam
        self._installed = True
        return self

    def uninstall(self):
        if not self._installed:
            return
        self._ev2._round_seam = self._orig
        self._installed = False

    def __enter__(self):
        return self.install()

    def __exit__(self, *exc):
        self.uninstall()

    def _seam(self, batch_uids, outputs):
        import numpy as np
        import time as _time

        i = self.round
        self.round += 1
        if self.mode and self.fire_at <= i < self.fire_at + self.n_rounds:
            self.fired_rounds += 1
            if self.mode == "slow_step":
                _time.sleep(self.delay_s)
            elif self.mode == "oom_round":
                raise MemoryError(
                    f"injected device OOM in scheduling round {i}")
            elif self.mode == "nan_logits":
                # a numerically-poisoned dispatch: the in-graph finite flags
                # go false and the logits lane is NaN (jax->numpy arrays are
                # read-only, so replace rather than mutate)
                outputs.finite = np.zeros(len(outputs.finite), bool)
                outputs.logits = np.full(
                    np.asarray(outputs.logits).shape, np.nan, np.float32)
            elif self.mode == "spec_reject_storm":
                # the model "changes its mind" about every draft: force the
                # longest accepted prefix to zero on all rows.  Rollback +
                # the accept-rate governor are what's under test.
                outputs.accepted = np.zeros_like(
                    np.asarray(outputs.accepted))
        return outputs


def _serving_frontend(num_blocks=64, block_size=8, max_ctx=64, seq_budget=4,
                      decode_batch=4, resilience=None, watchdog=None,
                      warm=True, speculative=None):
    _force_cpu()
    from deeperspeed_tpu.inference.v2 import (InferenceEngineV2,
                                              ServingFrontend)
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    model = GPTNeoX(GPTNeoXConfig.tiny(max_seq_len=max_ctx))
    cfg = {"dtype": "float32",
           "kv_cache": {"num_blocks": num_blocks, "block_size": block_size},
           "state_manager": {"max_context": max_ctx,
                             "max_ragged_batch_size": max_ctx,
                             "max_ragged_sequence_count": seq_budget},
           "max_decode_batch": decode_batch}
    if resilience is not None:
        cfg["resilience"] = resilience
    if speculative is not None:
        cfg["speculative"] = speculative
    engine = InferenceEngineV2(model, config=cfg)
    if warm:
        engine.warmup()   # compiles must not read as chaos-induced stalls
    return ServingFrontend(engine, watchdog=watchdog)


def _serving_registry():
    """Fresh enabled registry so scenarios can assert on the typed
    serving counters.  Returns (registry, restore_fn)."""
    from deeperspeed_tpu.telemetry import (TelemetryRegistry, get_registry,
                                           set_registry)

    old = get_registry()
    reg = set_registry(TelemetryRegistry(enabled=True, jsonl=False))
    return reg, lambda: set_registry(old)


def assert_serving_recovered(fe, context):
    """The serving resilience contract: after ANY chaos scenario the front
    end must (a) hold zero leaked KV blocks once idle and (b) serve a
    fresh request to completion."""
    from deeperspeed_tpu.inference.v2 import RequestState

    sm = fe.engine.state_manager
    free = sm.free_blocks_with_evictable()
    total = sm.allocator.total_blocks
    assert free == total, \
        f"{context}: leaked KV blocks ({total - free} unaccounted)"
    probe = fe.submit([3, 1, 4, 1, 5], slo="interactive", max_new_tokens=3)
    fe.run_until_idle()
    assert probe.state is RequestState.DONE, \
        f"{context}: post-chaos probe request ended {probe.state}"
    free = sm.free_blocks_with_evictable()
    assert free == total, \
        f"{context}: probe leaked KV blocks ({total - free})"


def scenario_nan_logits(workdir, writer=None):
    """A round of non-finite logits must be contained (requeue + recompute,
    poisoned prefix blocks dropped); a PERSISTENT NaN source must trip the
    circuit breaker into quarantining the request, not livelock."""
    from deeperspeed_tpu.inference.v2 import RequestState

    results = []
    reg, restore = _serving_registry()
    try:
        fe = _serving_frontend()
        inj = ServingFaultInjector()
        with inj:
            # phase 1: one poisoned round -> both requests recover
            t1 = fe.submit([1, 2, 3, 4, 5], max_new_tokens=4)
            t2 = fe.submit([9, 8, 7], max_new_tokens=4)
            inj.arm("nan_logits", n_rounds=1)
            fe.run_until_idle()
            assert inj.fired_rounds == 1, "nan round never fired"
            assert t1.state is RequestState.DONE, f"t1 ended {t1.state}"
            assert t2.state is RequestState.DONE, f"t2 ended {t2.state}"
            assert reg.counter("infer/step_failures").total >= 1
            assert reg.counter("infer/requeue_count").total >= 1
            results.append("one nan round: requeued + recovered to DONE")
            # phase 2: every round poisoned -> breaker quarantines
            inj.arm("nan_logits", n_rounds=10_000)
            t3 = fe.submit([5, 5, 5, 5], max_new_tokens=4)
            fe.run_until_idle()
            assert t3.state is RequestState.QUARANTINED, \
                f"persistent nan: t3 ended {t3.state} (expected QUARANTINED)"
            assert reg.counter("infer/quarantine_count").total >= 1
            inj.disarm()
        assert_serving_recovered(fe, "nan_logits")
        results.append(
            f"persistent nan: quarantined after "
            f"{fe.scheduler.max_step_failures} retries, serving again")
    finally:
        restore()
    return results


def scenario_oom_round(workdir, writer=None):
    """A MemoryError mid-round must free the round's blocks, requeue its
    requests with backoff, and complete them once the fault clears."""
    from deeperspeed_tpu.inference.v2 import RequestState

    results = []
    reg, restore = _serving_registry()
    try:
        fe = _serving_frontend()
        inj = ServingFaultInjector()
        with inj:
            t1 = fe.submit([1, 2, 3, 4, 5, 6], max_new_tokens=4)
            t2 = fe.submit([11, 12, 13], max_new_tokens=4)
            inj.arm("oom_round", n_rounds=1)
            fe.run_until_idle()
            assert inj.fired_rounds == 1, "oom round never fired"
            assert t1.state is RequestState.DONE, f"t1 ended {t1.state}"
            assert t2.state is RequestState.DONE, f"t2 ended {t2.state}"
            assert reg.counter("infer/step_failures").total >= 1
        assert_serving_recovered(fe, "oom_round")
        results.append("injected OOM round: requeued, completed, no leaks")
    finally:
        restore()
    return results


def scenario_slow_step(workdir, writer=None):
    """A crawling round must fire the stall watchdog and escalate the
    degradation ladder (shrunk prefill chunk); calm rounds must walk it
    back down to normal serving."""
    from deeperspeed_tpu.inference.v2 import RequestState
    from deeperspeed_tpu.telemetry import StallWatchdog

    results = []
    reg, restore = _serving_registry()
    wd = StallWatchdog(registry=reg, deadline_s=0.15,
                       snapshot_dir=os.path.join(workdir, "snapshots"))
    try:
        fe = _serving_frontend(
            watchdog=wd,
            resilience={"degrade_stall_s": 0.2, "degrade_recover_rounds": 2,
                        "degrade_chunk_divisor": 4})
        wd.start()   # after warmup: compiles must not read as stalls
        base_chunk = fe.scheduler.prefill_chunk
        inj = ServingFaultInjector()
        with inj:
            t1 = fe.submit(list(range(1, 25)), max_new_tokens=8)
            inj.arm("slow_step", n_rounds=1, delay_s=0.5)
            fe.step()                      # the crawling round
            assert inj.fired_rounds == 1, "slow round never fired"
            fe.step()                      # ladder evaluates the crawl
            assert fe.ladder.stage >= 1, \
                f"ladder did not escalate (stage {fe.ladder.stage})"
            assert fe.scheduler.prefill_chunk < base_chunk, \
                "stage >= 1 must shrink the prefill chunk"
            results.append(
                f"slow round: ladder escalated to stage {fe.ladder.stage}")
            fe.run_until_idle()
            for _ in range(50):            # calm rounds -> full recovery
                if fe.ladder.stage == 0:
                    break
                fe.step()
            assert fe.ladder.stage == 0, \
                f"ladder stuck at stage {fe.ladder.stage}"
            assert fe.scheduler.prefill_chunk == base_chunk, \
                "recovery must restore the prefill chunk"
            assert t1.state is RequestState.DONE, f"t1 ended {t1.state}"
        assert wd.stall_count >= 1, "watchdog never fired on the slow round"
        assert fe.ladder.transitions >= 2  # at least one up + one down
        assert_serving_recovered(fe, "slow_step")
        results.append(
            f"watchdog fired {wd.stall_count}x; ladder recovered to stage 0")
    finally:
        wd.stop()
        restore()
    return results


class VirtualClock:
    """The serving modules' clock, in the harness's hands: while installed
    it stands in for the ``time`` module of the front end, its admission
    and ladder, the scheduler and the replica pool, so every deadline,
    retry-after, heartbeat and probe cool-down is reckoned on ONE clock
    that moves only when the scenario says so (``advance``) or the code
    under test sleeps.  A loaded host then changes how long a scenario
    takes, never what it asserts.  The policies themselves run untouched."""

    def __init__(self, start=1000.0):
        self.now = float(start)
        self._saved = {}

    def monotonic(self):
        return self.now

    def sleep(self, seconds):
        self.now += float(seconds)

    advance = sleep

    def __enter__(self):
        from deeperspeed_tpu.inference.v2 import (frontend, replica,
                                                  resilience, scheduler)

        for mod in (frontend, replica, resilience, scheduler):
            self._saved[mod] = mod.time
            mod.time = self
        return self

    def __exit__(self, *exc):
        for mod, real in self._saved.items():
            mod.time = real
        self._saved.clear()


def scenario_flood(workdir, writer=None, n_requests=48, prompt_len=24,
                   decode_tokens=32, round_s=0.01):
    """An admission burst far beyond capacity, on the front end's own clock
    (``VirtualClock``, every serving round ``round_s`` long): shedding must
    engage, the retry-after hints must grow capped-exponentially along a
    streak of sheds, every request the shedding front end admits must
    finish inside its deadline (an admission is a promise) while the same
    burst on a front end that admits everything lets admitted work expire,
    and the flood must end with the front end serving again and zero
    leaks."""
    import numpy as np

    _force_cpu()
    from deeperspeed_tpu.inference.v2 import RequestState
    from tools.bench_inference import _flood_frontend

    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, 256, size=prompt_len))
               for _ in range(n_requests + 1)]

    def turn(front, clock):
        front.step()
        clock.advance(round_s)

    def flood(shed, clock):
        """-> (front end, tickets, deadline): the deadline is 1.5x what one
        uncontended request takes on this clock; 3 arrivals a round."""
        front = _flood_frontend(shed=shed,
                                max_ctx=prompt_len + decode_tokens + 8)
        t0 = clock.now
        alone = front.submit(prompts[-1], max_new_tokens=decode_tokens)
        while front.has_work:
            turn(front, clock)
        assert alone.state is RequestState.DONE
        deadline_s = 1.5 * (clock.now - t0)
        tickets = []
        for i in range(0, n_requests, 3):
            tickets += [front.submit(p, deadline_s=deadline_s,
                                     max_new_tokens=decode_tokens)
                        for p in prompts[i:i + 3]]
            turn(front, clock)
        while front.has_work:
            turn(front, clock)
        return front, tickets, deadline_s

    results = []
    reg, restore = _serving_registry()
    try:
        with VirtualClock() as clock:
            fe, tickets, deadline_s = flood(True, clock)
            shed = [t for t in tickets if t.state is RequestState.SHED]
            admitted = [t for t in tickets if t.state is not RequestState.SHED]
            assert shed, "flood never shed a request"
            assert admitted, "flood shed everything"
            late = [t.uid for t in admitted if not t.met_deadline]
            assert not late, \
                f"admitted under shedding, yet past {deadline_s:.2f}s: {late}"
            # retry-after: base * 2^(n-1) clamped to the cap, n the length
            # of the shed streak so far, within the configured jitter
            cfg = fe.config
            base, cap, jit = (cfg.retry_after_base_s, cfg.retry_after_cap_s,
                              cfg.retry_after_jitter_frac)
            streak = longest = 0
            for t in tickets:
                if t.state is not RequestState.SHED:
                    streak = 0
                    continue
                streak += 1
                longest = max(longest, streak)
                nominal = min(cap, base * 2.0 ** (streak - 1))
                assert ((1 - jit) * nominal - 1e-9 <= t.retry_after_s
                        <= min(cap, (1 + jit) * nominal) + 1e-9), \
                    (f"shed {streak} of a streak hinted {t.retry_after_s}s, "
                     f"nominal {nominal}s")
            assert longest >= 3 and (
                max(t.retry_after_s for t in shed)
                >= 2 * min(t.retry_after_s for t in shed)), \
                "retry-after never grew along a shed streak"
            assert fe.admission.shed_count == len(shed)
            assert reg.counter("infer/shed_count").total >= len(shed)
            assert fe.expired_count == 0
            assert_serving_recovered(fe, "flood")

            base_fe, base_tickets, _ = flood(False, clock)
            assert not any(t.state is RequestState.SHED
                           for t in base_tickets)
            in_time = [t for t in base_tickets if t.met_deadline]
            assert base_fe.expired_count > 0 and \
                len(in_time) + base_fe.expired_count == n_requests, \
                (f"admitting everything: {len(in_time)} in time, "
                 f"{base_fe.expired_count} expired of {n_requests}")
            assert_serving_recovered(base_fe, "flood (no shedding)")
        results.append(
            f"flood: shed {len(shed)} of {n_requests} (retry-after up to "
            f"{max(t.retry_after_s for t in shed):.2f}s over a streak of "
            f"{longest}), all {len(admitted)} admitted finished inside "
            f"{deadline_s:.2f}s on the front end's clock; with everything "
            f"admitted {len(in_time)} did and {base_fe.expired_count} expired")
    finally:
        restore()
    return results


def scenario_tenant_storm(workdir, writer=None, flood_x=10, n_waves=8):
    """One best-effort tenant floods the pool at ``flood_x`` times its
    normal rate.  Its token bucket must throttle the excess (narrated by a
    ``tenant_throttle`` flight dump), the other tenants' goodput must
    degrade by less than 10%, the autoscaler must ride the storm through a
    full warm scale-out / drain / readmit cycle with zero flaps and zero
    jit misses on the warmed replica, and the priority-preemption pass
    must leave the allocator audit-clean with zero leaked blocks."""
    _force_cpu()
    from tools.bench_inference import run_tenant_bench

    results = []
    reg, restore = _serving_registry()
    try:
        bench = run_tenant_bench(flood_x=flood_x, n_waves=n_waves)
        assert bench["throttled"] > 0, "storm never hit the token bucket"
        assert bench["value"] >= 0.9, \
            (f"tenant isolation broke: paying tenants kept only "
             f"{bench['value']:.2f} of their no-storm goodput")
        scale = bench["autoscale_flood"]
        assert scale["flaps"] == 0, f"autoscaler flapped: {scale}"
        assert scale["n_actions"] >= 1, "storm never triggered a scale-out"
        modes = set(bench["scale_cycle_modes"])
        for mode in ("warm_standby", "scale_in", "readmit"):
            assert mode in modes, \
                f"scale cycle never exercised {mode!r}: {sorted(modes)}"
        assert bench["warm_jit_miss_delta"] == 0, \
            (f"warm-scaled replica recompiled while serving: "
             f"{bench['warm_jit_miss_delta']} jit misses past warmup")
        pre = bench["preempt"]
        assert pre["preemptions"] >= 1, "latency tenant never preempted"
        assert pre["audit_clean"] and pre["leaked_blocks"] == 0, \
            f"preemption rollback leaked blocks: {pre}"
        assert bench["leaked_blocks"] == 0
        assert reg.counter("infer/tenant_throttled").total > 0
        assert reg.counter("infer/autoscale_actions").total >= 1
        results.append(
            f"tenant storm x{flood_x}: throttled {bench['throttled']}, "
            f"isolation {bench['value']:.2f}, scale cycle "
            f"{bench['scale_cycle_modes']} with 0 flaps, "
            f"{pre['preemptions']} preemption(s) audit-clean")
    finally:
        restore()
    return results


def scenario_spec_reject_storm(workdir, writer=None):
    """Force zero draft acceptance on every speculative round (the model
    'changes its mind' about every draft).  The rollback path must free
    every forked draft-tail block, the accept-rate governor must degrade
    the front end to k=0 plain decoding with a floor-breach event, and
    once the storm clears speculation must re-probe after its cooldown."""
    from deeperspeed_tpu.inference.v2 import RequestState
    from deeperspeed_tpu.inference.v2.speculative import CallableDrafter

    results = []
    reg, restore = _serving_registry()
    try:
        fe = _serving_frontend(
            speculative={"method": "ngram", "k": 3, "floor_patience": 2,
                         "floor_cooldown": 4})
        # deterministic draft pressure: the storm needs drafted > 0 every
        # round, which a history-dependent n-gram lookup can't guarantee on
        # a tiny random model
        fe.scheduler.drafter = CallableDrafter(lambda hist, k: [7] * k)
        gov = fe.scheduler.governor
        inj = ServingFaultInjector()
        with inj:
            inj.arm("spec_reject_storm", n_rounds=10_000)
            fe.submit([1, 2, 3, 4, 5], max_new_tokens=8)
            for _ in range(200):
                if gov.breaches:
                    break
                if not fe.has_work:
                    fe.submit([1, 2, 3, 4, 5], max_new_tokens=8)
                fe.step()
            assert gov.breaches >= 1, "governor never tripped on 0% accepts"
            assert gov.effective_k == 0, \
                "breached governor must degrade to k=0"
            assert reg.counter("infer/spec_floor_breach").total >= 1
            results.append(
                "reject storm: governor degraded to k=0 after "
                f"{gov.cfg.floor_patience} floored rounds")
            inj.disarm()
            # cooldown rounds tick by on plain decoding; then re-probe
            for _ in range(200):
                if gov.active:
                    break
                if not fe.has_work:
                    fe.submit([1, 2, 3, 4, 5], max_new_tokens=4)
                fe.step()
            assert gov.active and gov.effective_k == gov.cfg.k, \
                "speculation did not re-probe after cooldown"
        fe.run_until_idle()
        for t in fe.tickets.values():
            assert t.state is RequestState.DONE, f"ticket ended {t.state}"
        fe.engine.state_manager.allocator.audit()
        assert_serving_recovered(fe, "spec_reject_storm")
        results.append("storm cleared: re-probed speculation, zero leaks")
    finally:
        restore()
    return results


# --runtime-locks: wrap every discipline lock of each pool the scenarios
# build in the analyzer's rank-checking proxies, so a chaos sweep doubles
# as a dynamic validation of the declared lock order (DST-C001's model)
RUNTIME_LOCKS = False


def _maybe_instrument(fe):
    if RUNTIME_LOCKS:
        from deeperspeed_tpu.analysis import runtime_locks

        runtime_locks.instrument_pool(fe)
    return fe


def _replica_pool(n=4, num_blocks=64, block_size=8, max_ctx=64,
                  seq_budget=4, decode_batch=4, pool=None, resilience=None):
    """Tiny CPU replica pool: N engines with bit-identical weights (same
    model, same init seed) behind one RoutingFrontend.  Returns
    ``(pool_frontend, make_reference_scheduler)`` -- the factory builds a
    fresh same-weights scheduler for expected-output (greedy) baselines."""
    _force_cpu()
    from deeperspeed_tpu.inference.v2 import (DSScheduler, InferenceEngineV2,
                                              RoutingFrontend)
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    model = GPTNeoX(GPTNeoXConfig.tiny(max_seq_len=max_ctx))
    cfg = {"dtype": "float32",
           "kv_cache": {"num_blocks": num_blocks, "block_size": block_size},
           "state_manager": {"max_context": max_ctx,
                             "max_ragged_batch_size": max_ctx,
                             "max_ragged_sequence_count": seq_budget},
           "max_decode_batch": decode_batch}
    if resilience is not None:
        cfg["resilience"] = resilience
    if pool is not None:
        cfg["replica_pool"] = pool
    engines = [InferenceEngineV2(model, config=cfg) for _ in range(n)]

    def make_ref():
        return DSScheduler(InferenceEngineV2(model, config=cfg))

    return _maybe_instrument(RoutingFrontend(engines)), make_ref


def _pool_clean(fe, context, include_ejected=True):
    """Pool-wide leak check: every allocator whole, no live entries."""
    from deeperspeed_tpu.inference.v2 import ReplicaState

    summary = fe.audit(include_ejected=include_ejected)
    assert not summary["live_tickets"], \
        f"{context}: leaked tickets {summary['live_tickets']}"
    assert summary["pending_failovers"] == 0, \
        f"{context}: stuck failovers ({summary['pending_failovers']})"
    for rep in fe.replicas:
        if not include_ejected and rep.state is ReplicaState.EJECTED:
            continue
        sm = rep.engine.state_manager
        free = sm.free_blocks_with_evictable()
        total = sm.allocator.total_blocks
        assert free == total, \
            (f"{context}: replica {rep.rid} leaked KV blocks "
             f"({total - free} unaccounted)")


def scenario_replica_kill(workdir, writer=None):
    """Kill one of four replicas mid-flood.  Its in-flight requests must
    fail over and complete BIT-EXACTLY (greedy) vs an unkilled run, the
    pool must leak nothing, and the dead replica must be re-admitted by
    probing once the fault clears."""
    import numpy as np
    from deeperspeed_tpu.inference.v2 import ReplicaState, RequestState

    results = []
    reg, restore = _serving_registry()
    try:
        fe, make_ref = _replica_pool(
            n=4, pool={"probe_cooldown_s": 0.01,
                       "probe_cooldown_cap_s": 0.05})
        rng = np.random.default_rng(17)
        prompts = [list(rng.integers(1, 250, size=m))
                   for m in (9, 12, 7, 14, 10, 8, 13, 11)]
        max_new = 6
        expected = [np.asarray(o)[len(p):] for p, o in
                    zip(prompts, make_ref().generate(prompts, max_new))]

        tickets = [fe.submit(p, max_new_tokens=max_new, deadline_s=120.0)
                   for p in prompts]
        assert all(t.state is not RequestState.SHED for t in tickets)
        for _ in range(2):   # let every replica pick up work
            fe.step()
        victim = next(r for r in fe.replicas
                      if any(e.replica is r and not e.ticket.done
                             for e in fe._entries.values()))
        victim.fault = "kill"
        fe.run_until_idle()
        # PROBING is a legitimate transient here: with the fault still
        # armed every probe dies and re-ejects, so assert the breaker
        # tripped rather than a snapshot of the probe cycle
        assert victim.eject_count >= 1, "victim was never ejected"
        assert victim.state in (ReplicaState.EJECTED,
                                ReplicaState.PROBING), \
            f"victim ended {victim.state}"
        assert fe.failover_count >= 1, "kill produced no failover"
        for t, exp in zip(tickets, expected):
            assert t.state is RequestState.DONE, \
                f"{t.uid} ended {t.state} ({t.error})"
            np.testing.assert_array_equal(
                np.asarray(t.tokens, np.int32), exp,
                err_msg=f"{t.uid}: failover replay not bit-exact")
        _pool_clean(fe, "replica_kill (victim down)")
        assert reg.counter("infer/pool_ejected").total >= 1
        assert reg.counter("infer/pool_failovers").total >= 1
        results.append(
            f"killed replica {victim.rid}: {fe.failover_count} failovers, "
            f"{fe.replayed_tokens} replayed tokens, all outputs bit-exact")

        # fault clears -> probing re-admission -> serving on all four
        victim.fault = None
        fe.run_until_settled()
        assert victim.state is ReplicaState.HEALTHY, \
            f"victim not re-admitted (state {victim.state})"
        assert fe.readmitted_count >= 1
        assert reg.counter("infer/pool_readmitted").total >= 1
        probe = fe.submit([3, 1, 4, 1, 5], max_new_tokens=3)
        fe.run_until_idle()
        assert probe.state is RequestState.DONE, \
            f"post-chaos probe ended {probe.state}"
        _pool_clean(fe, "replica_kill (recovered)")
        results.append(
            f"probe re-admitted replica {victim.rid} after "
            f"{victim.probe_attempts} probe(s); pool serving again")
    finally:
        restore()
    return results


def scenario_replica_slow(workdir, writer=None):
    """A straggler replica must degrade (routed around while healthy
    replicas can take the work) WITHOUT losing its in-flight requests,
    then recover to healthy once its rounds come back fast."""
    import time as _time

    from deeperspeed_tpu.inference.v2 import ReplicaState, RequestState

    results = []
    reg, restore = _serving_registry()
    try:
        fe, _ = _replica_pool(
            n=2, pool={"slow_round_s": 0.05, "recover_idle_s": 0.2,
                       "recover_rounds": 2})
        victim = fe.replicas[0]
        t1 = fe.submit([1, 2, 3, 4, 5], max_new_tokens=3, deadline_s=60.0)
        assert fe._entries[t1.uid].replica is victim  # tie-break: rid order
        victim.fault = ("slow", 0.12)
        fe.step()
        assert victim.state is ReplicaState.DEGRADED, \
            f"straggler not degraded (state {victim.state})"
        results.append("slow rounds degraded the straggler")
        # new work routes AROUND the degraded replica...
        t2 = fe.submit([9, 8, 7, 6], max_new_tokens=3, deadline_s=60.0)
        assert fe._entries[t2.uid].replica is fe.replicas[1], \
            "router sent new work to a degraded replica"
        # ...but its in-flight request is NOT failed over: it finishes
        # in place, just slower
        fe.run_until_idle()
        assert t1.state is RequestState.DONE, f"t1 ended {t1.state}"
        assert t2.state is RequestState.DONE, f"t2 ended {t2.state}"
        assert fe.failover_count == 0, "degradation must not migrate work"
        victim.fault = None
        deadline = _time.monotonic() + 10.0
        while (victim.state is not ReplicaState.HEALTHY
               and _time.monotonic() < deadline):
            _time.sleep(0.05)
            fe.step()
        assert victim.state is ReplicaState.HEALTHY, \
            f"straggler never recovered (state {victim.state})"
        t3 = fe.submit([2, 7, 1, 8], max_new_tokens=3)
        fe.run_until_idle()
        assert t3.state is RequestState.DONE
        _pool_clean(fe, "replica_slow")
        results.append("fault cleared: straggler recovered to healthy")
    finally:
        restore()
    return results


def scenario_replica_flap(workdir, writer=None, cooldown_s=0.01,
                          cooldown_cap_s=1.0):
    """A replica that dies, recovers, and dies again, on the pool's own
    clock (``VirtualClock``): every flap must fail its work over cleanly,
    each ejection must sit out its probe cool-down before ONE probe
    re-admits the replica, and the cool-down must GROW across the quick
    re-ejection (flap damping) instead of resetting."""
    from deeperspeed_tpu.inference.v2 import ReplicaState, RequestState
    from deeperspeed_tpu.inference.v2.resilience import capped_exponential

    results = []
    reg, restore = _serving_registry()
    try:
        with VirtualClock() as clock:
            fe, _ = _replica_pool(
                n=2, pool={"probe_cooldown_s": cooldown_s,
                           "probe_cooldown_cap_s": cooldown_cap_s,
                           "flap_window_s": 60.0})
            victim = fe.replicas[0]
            done, cooldowns = [], []
            for episode in range(2):
                t = fe.submit([episode + 1, 2, 3, 4, 5], max_new_tokens=4,
                              deadline_s=60.0)
                done.append(t)
                assert fe._entries[t.uid].replica is victim, \
                    f"episode {episode}: the idle pool routed past replica 0"
                victim.fault = "kill"
                fe.run_until_idle()
                assert victim.state is ReplicaState.EJECTED
                assert victim.eject_count == episode + 1
                assert t.state is RequestState.DONE, \
                    f"{t.uid} ended {t.state} after its replica died"
                assert fe.failover_count == episode + 1
                victim.fault = None
                # flap damping: the attempts of the first episode carried
                # across the quick re-eject, so this cool-down is longer
                cooldown = capped_exponential(cooldown_s, cooldown_cap_s,
                                              episode + 1)
                cooldowns.append(cooldown)
                clock.advance(0.9 * cooldown)
                fe.step()
                assert (victim.state is ReplicaState.EJECTED
                        and victim.probe_attempts == episode), \
                    (f"episode {episode}: probed {0.9 * cooldown:.4f}s after "
                     f"the ejection, inside its {cooldown:.4f}s cool-down")
                clock.advance(0.2 * cooldown)
                fe.step()
                assert victim.state is ReplicaState.PROBING, \
                    f"episode {episode}: no probe after the cool-down"
                fe.run_until_settled()
                assert victim.state is ReplicaState.HEALTHY, \
                    f"episode {episode}: not re-admitted ({victim.state})"
                assert victim.probe_attempts == episode + 1, \
                    (f"probe backoff reset across flaps "
                     f"(attempts {victim.probe_attempts})")
                assert fe.readmitted_count == episode + 1
            assert cooldowns[1] == 2 * cooldowns[0]
            _pool_clean(fe, "replica_flap")
        results.append(
            f"2 flaps survived: eject_count={victim.eject_count}, one probe "
            f"each after cool-downs of {cooldowns[0]:.3f}s then "
            f"{cooldowns[1]:.3f}s on the pool's clock")
    finally:
        restore()
    return results


def scenario_drain_under_load(workdir, writer=None):
    """Graceful drain mid-flood, both postures: a generous grace period
    finishes in-flight work in place (zero migrations); a zero grace
    period migrates it through the failover path.  Either way the drained
    replica ends empty, reports drained, and readmit() restores it."""
    from deeperspeed_tpu.inference.v2 import ReplicaState, RequestState

    results = []
    reg, restore = _serving_registry()
    try:
        fe, _ = _replica_pool(n=2)
        tickets = [fe.submit([i + 1, 5, 9, 2, 6], max_new_tokens=4,
                             deadline_s=60.0) for i in range(4)]
        fe.step()
        rid = next(r.rid for r in fe.replicas
                   if any(e.replica is r and not e.ticket.done
                          for e in fe._entries.values()))
        # posture 1: generous grace -> finish in place
        fe.drain(rid, grace_s=30.0)
        t_new = fe.submit([7, 7, 7, 7], max_new_tokens=3, deadline_s=60.0)
        assert fe._entries[t_new.uid].replica.rid != rid, \
            "router sent new work to a draining replica"
        fe.run_until_idle()
        fe.run_until_settled()
        rep = fe.replicas[rid]
        assert rep.state is ReplicaState.DRAINED, f"state {rep.state}"
        assert fe.drains and fe.drains[-1]["migrated"] == 0, \
            f"graceful drain migrated work: {fe.drains}"
        for t in tickets + [t_new]:
            assert t.state is RequestState.DONE, f"{t.uid} ended {t.state}"
        results.append(
            f"drain(grace=30s) on replica {rid}: finished in place, "
            f"drained in {fe.drains[-1]['seconds']:.3f}s, 0 migrated")
        fe.readmit(rid)
        assert rep.state is ReplicaState.HEALTHY

        # posture 2: zero grace -> migrate through failover
        tickets2 = [fe.submit([i + 3, 1, 4, 1, 5, 9], max_new_tokens=4,
                              deadline_s=60.0) for i in range(4)]
        fe.step()
        rid2 = next(r.rid for r in fe.replicas
                    if any(e.replica is r and not e.ticket.done
                           for e in fe._entries.values()))
        before = fe.failover_count
        fe.drain(rid2, grace_s=0.0)
        fe.run_until_idle()
        fe.run_until_settled()
        rep2 = fe.replicas[rid2]
        assert rep2.state is ReplicaState.DRAINED, f"state {rep2.state}"
        assert fe.drains[-1]["migrated"] >= 1, \
            "zero-grace drain migrated nothing"
        assert fe.failover_count > before
        for t in tickets2:
            assert t.state is RequestState.DONE, f"{t.uid} ended {t.state}"
        _pool_clean(fe, "drain_under_load")
        assert reg.histogram("infer/pool_drain_seconds").count >= 2
        fe.readmit(rid2)
        probe = fe.submit([3, 1, 4], max_new_tokens=2)
        fe.run_until_idle()
        assert probe.state is RequestState.DONE
        results.append(
            f"drain(grace=0) on replica {rid2}: "
            f"{fe.drains[-1]['migrated']} migrated via failover, all DONE")
    finally:
        restore()
    return results


# --------------------------------------------------------------------------
# disaggregated serving + host KV tier chaos
# --------------------------------------------------------------------------

class SeamPatcher:
    """Generic module-seam fault: swap a module attribute for a wrapper
    while installed.  ``transform(args, result)`` produces the faulted
    return value when armed; ``None`` mode passes through."""

    def __init__(self, module, attr, transform):
        self._module = module
        self._attr = attr
        self._transform = transform
        self.armed = False
        self.fired = 0
        self._orig = None

    def __enter__(self):
        self._orig = getattr(self._module, self._attr)

        def _wrapped(*args, **kw):
            result = self._orig(*args, **kw)
            if self.armed:
                self.fired += 1
                return self._transform(args, result)
            return result

        setattr(self._module, self._attr, _wrapped)
        return self

    def __exit__(self, *exc):
        setattr(self._module, self._attr, self._orig)


def _disagg_frontend(num_blocks=64, block_size=8, max_ctx=64, seq_budget=4,
                     decode_batch=4, prefill_chunk=None, disagg=None,
                     kv_dtype=""):
    """A DisaggregatedFrontend over two same-weights engines (deterministic
    self-init from one model instance), plus a third engine for colocated
    bit-exact reference runs.  Returns (frontend, reference_engine)."""
    _force_cpu()
    from deeperspeed_tpu.inference.v2 import (DisaggregatedFrontend,
                                              InferenceEngineV2)
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    model = GPTNeoX(GPTNeoXConfig.tiny(max_seq_len=max_ctx))
    kv_cfg = {"num_blocks": num_blocks, "block_size": block_size}
    if kv_dtype:
        kv_cfg["dtype"] = kv_dtype
    cfg = {"dtype": "float32",
           "kv_cache": kv_cfg,
           "state_manager": {"max_context": max_ctx,
                             "max_ragged_batch_size": max_ctx,
                             "max_ragged_sequence_count": seq_budget},
           "max_decode_batch": decode_batch}
    if disagg is not None:
        cfg["disagg"] = disagg
    prefill = InferenceEngineV2(model, config=cfg)
    decode = InferenceEngineV2(model, config=cfg)
    ref = InferenceEngineV2(model, config=cfg)
    fe = DisaggregatedFrontend(prefill, decode, prefill_chunk=prefill_chunk)
    return fe, ref


def scenario_migration_drop(workdir, writer=None, kv_dtype=""):
    """KV blocks lost mid-hop between the prefill and decode engines: every
    affected request must fall back to decode-side recompute -- same greedy
    tokens, no hang, no leaked blocks on either allocator -- and migrations
    must succeed again once the fault clears.  ``kv_dtype`` selects the
    block-scaled KV payload on the wire ("" = fp32, "int8", "fp8")."""
    import numpy as np

    from deeperspeed_tpu.inference.v2 import RequestState, DSScheduler
    from deeperspeed_tpu.inference.v2 import disagg as disagg_mod

    results = []
    reg, restore = _serving_registry()
    try:
        fe, ref_engine = _disagg_frontend(
            disagg={"migrate_timeout_s": 5.0}, kv_dtype=kv_dtype)
        rng = np.random.default_rng(0)
        prompts = [list(int(t) for t in rng.integers(1, 250, size=n))
                   for n in (19, 11, 26)]
        expect = DSScheduler(ref_engine).generate(prompts, max_new_tokens=6)
        with SeamPatcher(disagg_mod, "_migration_seam",
                         lambda args, res: None) as patch:
            patch.armed = True
            tickets = [fe.submit(p, max_new_tokens=6) for p in prompts]
            fe.run_until_idle(max_rounds=2000)
            patch.armed = False
            assert patch.fired >= 1, "migration seam never fired"
            for t, p, e in zip(tickets, prompts, expect):
                assert t.state is RequestState.DONE, \
                    f"migration_drop: ticket {t.uid} ended {t.state}"
                got = list(p) + t.tokens
                assert np.array_equal(np.asarray(got, np.int32), e), \
                    f"migration_drop: fallback diverged for {t.uid}"
            assert fe.fallbacks >= len(prompts), \
                f"expected >= {len(prompts)} fallbacks, saw {fe.fallbacks}"
            assert fe.migrations == 0
            assert reg.counter("infer/migration_fallbacks").total >= 1
            fe.audit()
            results.append(
                f"dropped hops: {fe.fallbacks} recompute fallbacks, "
                f"outputs bit-exact, both allocators clean")
            # fault cleared: migrations land again
            t2 = fe.submit(prompts[0], max_new_tokens=6)
            fe.run_until_idle(max_rounds=2000)
            assert t2.state is RequestState.DONE
            assert np.array_equal(
                np.asarray(list(prompts[0]) + t2.tokens, np.int32),
                expect[0])
            assert fe.migrations >= 1, "post-fault migration never landed"
            fe.audit()
            results.append("fault cleared: migration path serving again")
    finally:
        restore()
    return results


def scenario_host_tier_corrupt(workdir, writer=None, kv_dtype=""):
    """A spilled block failing its blake2b identity check on restore must
    read as a plain cache miss -- the prompt recomputes, outputs stay
    bit-exact, the poisoned entry is dropped, zero leaked blocks.
    ``kv_dtype`` selects the block-scaled KV payload that spills to host
    ("" = fp32, "int8", "fp8")."""
    import numpy as np

    from deeperspeed_tpu.inference.v2 import (DSScheduler, InferenceEngineV2,
                                              kv_tier as kv_tier_mod)
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    _force_cpu()
    results = []
    reg, restore = _serving_registry()
    try:
        model = GPTNeoX(GPTNeoXConfig.tiny(max_seq_len=64))

        def build(num_blocks, tier):
            kv_cfg = {"num_blocks": num_blocks, "block_size": 8,
                      "prefix_cache": True}
            if kv_dtype:
                kv_cfg["dtype"] = kv_dtype
            cfg = {"dtype": "float32",
                   "kv_cache": kv_cfg,
                   "state_manager": {"max_context": 64,
                                     "max_ragged_batch_size": 64,
                                     "max_ragged_sequence_count": 4},
                   "max_decode_batch": 4,
                   "kv_tier": {"enabled": tier, "capacity_blocks": 64}}
            return InferenceEngineV2(model, config=cfg)

        rng = np.random.default_rng(1)
        prompts = [list(int(t) for t in rng.integers(1, 250, size=20))
                   for _ in range(10)]
        expect = DSScheduler(build(64, tier=False)).generate(
            prompts, max_new_tokens=5)
        # 12-block pool vs a ~20-full-block working set: serving all ten
        # prompts churns the cache and spills evicted prefixes to host
        engine = build(12, tier=True)
        out = DSScheduler(engine).generate(prompts, max_new_tokens=5)
        for e, o in zip(expect, out):
            assert np.array_equal(e, o)
        tier = engine.host_tier
        assert tier.spills >= 1, "working set never spilled"

        def _flip(args, res):
            bad = [np.array(p, copy=True) for p in res]
            bad[0].view(np.uint8).reshape(-1)[0] ^= 0xFF
            return bad

        with SeamPatcher(kv_tier_mod, "_restore_seam", _flip) as patch:
            patch.armed = True
            out2 = DSScheduler(engine).generate(prompts, max_new_tokens=5)
            patch.armed = False
            assert patch.fired >= 1, "restore seam never fired"
            for e, o in zip(expect, out2):
                assert np.array_equal(e, o), \
                    "host_tier_corrupt: recompute diverged"
            assert tier.corrupt >= 1, "digest check never tripped"
            engine.state_manager.allocator.audit()
        results.append(
            f"corrupted restores: {tier.corrupt} digest rejections, "
            f"outputs bit-exact via recompute, allocator clean")
        # clean restores still work after the fault window
        before = tier.hits
        out3 = DSScheduler(engine).generate(prompts, max_new_tokens=5)
        for e, o in zip(expect, out3):
            assert np.array_equal(e, o)
        assert tier.hits > before, "post-fault restore never hit"
        engine.state_manager.allocator.audit()
        assert reg.counter("infer/host_tier_spills").total >= 1
        results.append("fault cleared: host-tier restores hitting again")
    finally:
        restore()
    return results


# --------------------------------------------------------------------------
# cross-host fabric chaos: the transport seam (channel faults) and the host
# process seam (FabricReplicaHost.killed) are the ONLY knobs -- scenarios
# drive the real wire path, never a mock.  ``transport="loopback"`` variants
# are deterministic and tier-1; the same functions run over real sockets
# (``transport="socket"``) behind --runslow.
# --------------------------------------------------------------------------
def _fabric_pool(n=2, transport="loopback", num_blocks=64, block_size=8,
                 max_ctx=64, seq_budget=4, decode_batch=4, pool=None,
                 fabric=None, slo_burn=None):
    """N engines behind a FabricRoutingFrontend: loopback channel pairs
    (tier-1) or real socketpairs, hosts co-scheduled in the router's step
    loop either way.  Returns (frontend, make_reference_scheduler)."""
    _force_cpu()
    from deeperspeed_tpu.inference.v2 import DSScheduler, InferenceEngineV2
    from deeperspeed_tpu.inference.v2.fabric import (FabricReplicaHost,
                                                     FabricRoutingFrontend,
                                                     RemoteReplica,
                                                     socket_pair)
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    model = GPTNeoX(GPTNeoXConfig.tiny(max_seq_len=max_ctx))
    cfg = {"dtype": "float32",
           "kv_cache": {"num_blocks": num_blocks, "block_size": block_size},
           "state_manager": {"max_context": max_ctx,
                             "max_ragged_batch_size": max_ctx,
                             "max_ragged_sequence_count": seq_budget},
           "max_decode_batch": decode_batch,
           "replica_pool": {"probe_cooldown_s": 0.01,
                            "probe_cooldown_cap_s": 0.05,
                            "probe_deadline_s": 0.25, **(pool or {})},
           "fabric": {"enabled": True, "heartbeat_interval_s": 0.02,
                      "staleness_s": 0.3, "gossip_interval_s": 0.05,
                      **(fabric or {})}}
    if slo_burn:
        cfg["slo_burn"] = {"enabled": True, **slo_burn}
    engines = [InferenceEngineV2(model, config=cfg) for _ in range(n)]
    if transport == "loopback":
        fe = FabricRoutingFrontend.loopback(engines)
    else:
        pcfg = engines[0].config.replica_pool
        fcfg = engines[0].config.fabric
        hosts, remotes = [], []
        for i, e in enumerate(engines):
            client_ch, server_ch = socket_pair()
            host = FabricReplicaHost(e, server_ch, rid=i, config=pcfg,
                                     fabric=fcfg)
            remote = RemoteReplica(i, client_ch, pcfg, fcfg,
                                   host.replica.frontend.slo_classes,
                                   host=host)
            hosts.append(host)
            remotes.append(remote)
        fe = FabricRoutingFrontend(
            remotes, pcfg, fabric=fcfg, hosts=hosts,
            block_size=engines[0].config.kv_cache.block_size,
            slo_burn=engines[0].config.slo_burn)

    def make_ref():
        return DSScheduler(InferenceEngineV2(model, config=cfg))

    return _maybe_instrument(fe), make_ref


def _trace_ejections(fe):
    """Instrument the pool's ejection path: returns a list that accumulates
    (rid, cause) for every ejection (the gossip-vs-breaker cause is the
    thing fabric scenarios must distinguish)."""
    causes = []
    orig = fe._eject

    def _traced(rep, cause):
        causes.append((rep.rid, cause))
        return orig(rep, cause)

    fe._eject = _traced
    return causes


def _fabric_clean(fe, context, include_down=True):
    """Fabric-wide leak check: router audit (no live entries, no stuck
    failovers), per-host allocators whole, and zero stranded shadow
    tickets on any remote."""
    summary = fe.audit(include_ejected=include_down)
    assert not summary["live_tickets"], \
        f"{context}: leaked tickets {summary['live_tickets']}"
    assert summary["pending_failovers"] == 0, \
        f"{context}: stuck failovers ({summary['pending_failovers']})"
    for host in fe._local_hosts:
        if not include_down and host.killed:
            continue
        sm = host.replica.engine.state_manager
        free = sm.free_blocks_with_evictable()
        total = sm.allocator.total_blocks
        assert free == total, \
            (f"{context}: host {host.rid} leaked KV blocks "
             f"({total - free} unaccounted)")
    for rep in fe.replicas:
        # the breaker's current probe canary is legitimately in flight on
        # an unreachable peer; anything else unfinished is a strand
        probe_uid = rep.probe_ticket.uid if rep.probe_ticket else None
        live = [u for u, t in rep.frontend.tickets.items()
                if not t.done and u != probe_uid]
        assert not live, f"{context}: stranded shadow tickets {live}"


def _drive_fabric(fe, tickets, victim, timeout_s=60.0):
    """Step the fabric until every ticket resolves; captures the FIRST
    ejection timestamp of ``victim`` (later failed probes re-stamp
    ``ejected_at``, so a post-hoc read measures the wrong thing)."""
    import time as _time

    first_eject = None
    deadline = _time.monotonic() + timeout_s
    while (any(not t.done for t in tickets) or fe.has_work) \
            and _time.monotonic() < deadline:
        fe.step()
        if first_eject is None and victim is not None \
                and victim.eject_count >= 1:
            first_eject = victim.ejected_at
    return first_eject


def _fabric_workload(fe, make_ref, n_prompts=6, max_new=6, seed=29):
    import numpy as np

    rng = np.random.default_rng(seed)
    prompts = [list(int(t) for t in rng.integers(1, 250, size=m))
               for m in (9, 12, 7, 14, 10, 8, 13, 11)[:n_prompts]]
    expected = [np.asarray(o)[len(p):] for p, o in
                zip(prompts, make_ref().generate(prompts, max_new))]
    return prompts, expected


def _pick_fabric_victim(fe):
    return next(r for r in fe.replicas
                if any(e.replica is r and not e.ticket.done
                       for e in fe._entries.values()))


def _assert_streams_exact(tickets, streams, expected, context):
    import numpy as np

    from deeperspeed_tpu.inference.v2 import RequestState

    for t, got, exp in zip(tickets, streams, expected):
        assert t.state is RequestState.DONE, \
            f"{context}: {t.uid} ended {t.state} ({t.error})"
        assert got == list(t.tokens), \
            f"{context}: {t.uid} stream != ticket (dup or hole)"
        np.testing.assert_array_equal(
            np.asarray(t.tokens, np.int32), exp,
            err_msg=f"{context}: {t.uid} not bit-exact")


def scenario_net_partition(workdir, writer=None, transport="loopback"):
    """Both directions of one replica's link go dark mid-stream.  Gossip
    staleness must eject the unreachable peer, its in-flight requests must
    replay bit-exactly on the survivor, the orphaned host must finish its
    abandoned work and free every block, and healing the link must probe
    the peer back in (counted as a fabric reconnect)."""
    from deeperspeed_tpu.inference.v2 import ReplicaState, RequestState

    results = []
    reg, restore = _serving_registry()
    try:
        fe, make_ref = _fabric_pool(n=2, transport=transport)
        causes = _trace_ejections(fe)
        prompts, expected = _fabric_workload(fe, make_ref)
        streams = [[] for _ in prompts]
        tickets = [fe.submit(p, max_new_tokens=6, deadline_s=120.0,
                             on_token=streams[i].append)
                   for i, p in enumerate(prompts)]
        assert all(t.state is not RequestState.SHED for t in tickets)
        for _ in range(2):
            fe.step()
        victim = _pick_fabric_victim(fe)
        victim.channel.fault = "drop"         # client -> host direction
        victim.host.channel.fault = "drop"    # host -> client direction
        _drive_fabric(fe, tickets, victim)
        assert victim.eject_count >= 1, "partitioned peer never ejected"
        assert ("gossip_stale" in {c for _, c in causes}), \
            f"ejection causes {causes} (expected gossip_stale)"
        assert fe.failover_count >= 1
        _assert_streams_exact(tickets, streams, expected, "net_partition")
        # the orphaned host never saw our cancels: it must finish its
        # abandoned work on its own and leak nothing
        for _ in range(5000):
            if not victim.host.replica.frontend.has_work:
                break
            victim.host.pump()
        assert not victim.host.replica.frontend.has_work, \
            "orphaned host wedged on abandoned work"
        _fabric_clean(fe, "net_partition (link down)")
        results.append(
            f"partitioned replica {victim.rid}: gossip_stale ejection, "
            f"{fe.failover_count} failovers bit-exact, orphan drained clean")

        victim.channel.fault = None
        victim.host.channel.fault = None
        fe.run_until_settled()
        assert victim.state is ReplicaState.HEALTHY, \
            f"healed peer not re-admitted ({victim.state})"
        assert victim.reconnects >= 1
        assert reg.counter("infer/fabric_reconnects").total >= 1
        probe = fe.submit([3, 1, 4, 1, 5], max_new_tokens=3)
        fe.run_until_idle()
        assert probe.state is RequestState.DONE
        _fabric_clean(fe, "net_partition (healed)")
        results.append(
            f"link healed: probe re-admitted replica {victim.rid}, "
            f"{victim.reconnects} reconnect(s), pool serving again")
    finally:
        restore()
    return results


def scenario_slow_link(workdir, writer=None, transport="loopback"):
    """A laggy link (delayed frame delivery, nothing lost) must NOT trip
    failover: the staleness window absorbs the jitter, every stream
    completes bit-exactly on its original replica, and the heartbeat
    staleness histogram records the gaps."""
    results = []
    reg, restore = _serving_registry()
    try:
        # staleness sized well above the injected delay: jitter absorbed
        fe, make_ref = _fabric_pool(n=2, transport=transport,
                                    fabric={"staleness_s": 2.0})
        prompts, expected = _fabric_workload(fe, make_ref, seed=31)
        victim = fe.replicas[0]
        delay = ("delay", 2)
        victim.channel.fault = delay
        victim.host.channel.fault = delay
        streams = [[] for _ in prompts]
        tickets = [fe.submit(p, max_new_tokens=6, deadline_s=120.0,
                             on_token=streams[i].append)
                   for i, p in enumerate(prompts)]
        _drive_fabric(fe, tickets, None)
        _assert_streams_exact(tickets, streams, expected, "slow_link")
        assert fe.failover_count == 0, \
            "slow link must degrade latency, never migrate work"
        assert fe.ejected_count == 0, "slow link tripped the breaker"
        assert reg.histogram("infer/fabric_staleness_s").count >= 1, \
            "no heartbeat gaps observed"
        _fabric_clean(fe, "slow_link")
        results.append(
            f"delayed link absorbed: 0 failovers, 0 ejections, "
            f"{reg.histogram('infer/fabric_staleness_s').count} heartbeat "
            "gaps recorded, all streams bit-exact")
    finally:
        restore()
    return results


def scenario_half_open_socket(workdir, writer=None, transport="loopback"):
    """Half-open link: the host's outbound direction dies (tokens and
    heartbeats stop arriving) while its inbound keeps working -- the
    classic half-open TCP failure.  The router must treat silence as
    death: gossip-eject, replay elsewhere with no duplicate or missing
    tokens (some tokens already streamed pre-fault), and the host -- which
    still HEARS us -- must honor the migration cancels promptly."""
    from deeperspeed_tpu.inference.v2 import ReplicaState, RequestState

    results = []
    reg, restore = _serving_registry()
    try:
        fe, make_ref = _fabric_pool(n=2, transport=transport)
        causes = _trace_ejections(fe)
        prompts, expected = _fabric_workload(fe, make_ref, seed=37)
        streams = [[] for _ in prompts]
        tickets = [fe.submit(p, max_new_tokens=6, deadline_s=120.0,
                             on_token=streams[i].append)
                   for i, p in enumerate(prompts)]
        victim = _pick_fabric_victim(fe)
        # let at least one token stream before the direction dies, so the
        # replay provably starts mid-stream
        for _ in range(400):
            fe.step()
            if any(e.replica is victim and e.ticket.tokens
                   for e in fe._entries.values()):
                break
        assert any(e.replica is victim and e.ticket.tokens
                   for e in fe._entries.values()), \
            "victim never streamed a token pre-fault"
        victim.host.channel.fault = "drop"    # outbound dead, inbound alive
        _drive_fabric(fe, tickets, victim)
        assert victim.eject_count >= 1, "half-open peer never ejected"
        assert "gossip_stale" in {c for _, c in causes}, \
            f"ejection causes {causes}"
        _assert_streams_exact(tickets, streams, expected,
                              "half_open_socket")
        # inbound worked: the migration cancels landed, so the host went
        # idle by cancel, not by grinding out abandoned generations
        for _ in range(200):
            if not victim.host.replica.frontend.has_work:
                break
            victim.host.pump()
        assert not victim.host.replica.frontend.has_work, \
            "host ignored cancels it provably received"
        _fabric_clean(fe, "half_open_socket (fault armed)")
        results.append(
            f"half-open link: replica {victim.rid} gossip-ejected, "
            f"mid-stream replay bit-exact, cancels honored over the "
            "surviving direction")

        victim.host.channel.fault = None
        fe.run_until_settled()
        assert victim.state is ReplicaState.HEALTHY
        assert victim.reconnects >= 1
        probe = fe.submit([3, 1, 4, 1, 5], max_new_tokens=3)
        fe.run_until_idle()
        assert probe.state is RequestState.DONE
        _fabric_clean(fe, "half_open_socket (healed)")
        results.append("direction restored: peer probed back in")
    finally:
        restore()
    return results


def scenario_peer_kill(workdir, writer=None, transport="loopback"):
    """Process death mid-stream: the host stops pumping entirely (no
    frames, no heartbeats, unread inbox -- exactly what a SIGKILL'd peer
    looks like).  Every in-flight request must complete on a surviving
    replica with no duplicate or missing tokens, gossip must eject the
    dead peer within the configured staleness window, and reviving the
    process must probe it back in as a counted reconnect."""
    import time as _time

    from deeperspeed_tpu.inference.v2 import ReplicaState, RequestState

    results = []
    reg, restore = _serving_registry()
    try:
        fe, make_ref = _fabric_pool(n=2, transport=transport)
        causes = _trace_ejections(fe)
        prompts, expected = _fabric_workload(fe, make_ref, seed=41)
        # warm both replicas first: the staleness-window latency assertion
        # below must measure detection, not XLA compiles
        warm = [fe.submit(p, max_new_tokens=6, deadline_s=120.0)
                for p in prompts]
        fe.run_until_idle()
        assert all(t.state is RequestState.DONE for t in warm)

        streams = [[] for _ in prompts]
        tickets = [fe.submit(p, max_new_tokens=6, deadline_s=120.0,
                             on_token=streams[i].append)
                   for i, p in enumerate(prompts)]
        for _ in range(2):
            fe.step()
        victim = _pick_fabric_victim(fe)
        victim.host.killed = True
        killed_at = _time.monotonic()
        first_eject = _drive_fabric(fe, tickets, victim)
        assert first_eject is not None, "dead peer never ejected"
        detect_s = first_eject - killed_at
        staleness = fe.fabric.staleness_s
        assert detect_s >= staleness - 0.05, \
            f"ejected after {detect_s:.3f}s -- before silence could prove " \
            f"death (window {staleness}s)"
        assert detect_s <= staleness + 1.5, \
            f"gossip took {detect_s:.3f}s to eject (window {staleness}s)"
        assert "gossip_stale" in {c for _, c in causes}, \
            f"ejection causes {causes}"
        assert fe.failover_count >= 1
        _assert_streams_exact(tickets, streams, expected, "peer_kill")
        _fabric_clean(fe, "peer_kill (host dead)", include_down=False)
        results.append(
            f"killed host {victim.rid}: gossip ejection in "
            f"{detect_s:.3f}s (window {staleness}s), "
            f"{fe.failover_count} failovers, all streams bit-exact")

        victim.host.killed = False
        fe.run_until_settled()
        assert victim.state is ReplicaState.HEALTHY, \
            f"revived peer not re-admitted ({victim.state})"
        assert victim.reconnects == 1, victim.reconnects
        assert reg.counter("infer/fabric_reconnects").total >= 1
        assert reg.counter("infer/fabric_frames").total >= 1
        probe = fe.submit([3, 1, 4, 1, 5], max_new_tokens=3)
        fe.run_until_idle()
        assert probe.state is RequestState.DONE
        _fabric_clean(fe, "peer_kill (revived)")
        results.append(
            f"process revived: probed back in, {victim.reconnects} "
            "reconnect, pool serving on both replicas")
    finally:
        restore()
    return results


def scenario_slo_burn(workdir, writer=None, transport="loopback"):
    """A straggler replica drags the pool's TTFT over the SLO target:
    the FAST burn window must page first (typed alert + parseable
    ``flight_slo_burn_*.json`` dump, state ``fast_burn`` -- evidence
    captured BEFORE the slow window confirms), the slow window must
    then confirm the regression, the autoscaler-facing ``slo_pressure``
    signal must go hot, and clearing the fault must clear the alert
    exactly once (no flapping) with pressure back to zero."""
    import time as _time

    from deeperspeed_tpu.inference.v2 import RequestState
    from deeperspeed_tpu.telemetry.slo import (ALERT_CLEARED,
                                               ALERT_CONFIRMED, ALERT_FAST,
                                               STATE_CONFIRMED,
                                               STATE_FAST_BURN, STATE_OK)

    results = []
    reg, restore = _serving_registry()
    try:
        # windows compressed to chaos-scale wall clock; slow_round_s is
        # parked high and staleness wide so the straggler stays HEALTHY
        # and routable -- this scenario is about the LATENCY plane
        # noticing, not the health plane ejecting
        fe, _ = _fabric_pool(
            n=2, transport=transport,
            pool={"slow_round_s": 30.0},
            fabric={"staleness_s": 30.0, "heartbeat_interval_s": 0.02},
            slo_burn={"metric": "infer/ttft_s", "target_s": 0.08,
                      "objective": 0.9, "fast_window_s": 0.6,
                      "slow_window_s": 2.4, "fast_burn": 2.0,
                      "slow_burn": 1.5, "clear_rounds": 4})
        ev = fe.slo_burn
        assert ev is not None, "slo_burn config did not build an evaluator"
        alerts = reg.counter("infer/slo_burn_alerts")

        def kind_count(kind):
            return int(alerts.by_tag.get("kind", {}).get(kind, 0))

        # warm both replicas with the target parked out of reach:
        # violations are judged at observe time, so the compile-cost
        # TTFTs of warmup register as healthy instead of paging
        ev.target_s = 1e9
        warm = [fe.submit([7, 6, 5, 4, 3], max_new_tokens=2,
                          deadline_s=60.0) for _ in range(4)]
        fe.run_until_idle()
        assert all(t.state is RequestState.DONE for t in warm)
        assert ev.state == STATE_OK
        assert kind_count(ALERT_FAST) == 0, "alert fired during warmup"
        ev.target_s = 0.08                           # arm the objective

        victim = fe.replicas[0]
        victim.host.replica.fault = ("slow", 0.1)   # every round +100ms
        fast_seen_at_state = None
        confirmed_before_fast = False
        tickets = []
        deadline = _time.monotonic() + 12.0
        while kind_count(ALERT_CONFIRMED) < 1 \
                and _time.monotonic() < deadline:
            # keep offering work so violating TTFTs keep flowing
            if len([t for t in tickets if not t.done]) < 2:
                tickets.append(fe.submit([1, 2, 3, 4], max_new_tokens=2,
                                         deadline_s=60.0))
            fe.step()
            if fast_seen_at_state is None and kind_count(ALERT_FAST) >= 1:
                fast_seen_at_state = ev.state
                confirmed_before_fast = kind_count(ALERT_CONFIRMED) >= 1
        assert kind_count(ALERT_FAST) >= 1, \
            f"fast-window alert never fired (state {ev.state})"
        assert not confirmed_before_fast, \
            "slow window confirmed before the fast window paged"
        assert fast_seen_at_state in (STATE_FAST_BURN, STATE_CONFIRMED)
        assert kind_count(ALERT_CONFIRMED) >= 1, \
            f"slow window never confirmed (state {ev.state})"
        assert fe.slo_pressure >= 1.0, fe.slo_pressure
        results.append(
            f"straggler TTFT burn: fast alert paged in state "
            f"'{fast_seen_at_state}', slow window confirmed, "
            f"slo_pressure={fe.slo_pressure:.2f}")

        # the fast alert's evidence: a parseable flight_slo_burn_*.json
        # with the alert payload in `extra` (run_scenario re-checks the
        # generic dump contract afterwards)
        from deeperspeed_tpu.telemetry.trace import get_tracer

        dumps = [p for p in get_tracer().flight_dumps
                 if os.path.basename(p).startswith("flight_slo_burn_")]
        assert dumps, "fast alert left no flight_slo_burn_*.json dump"
        with open(dumps[0]) as f:
            snap = json.load(f)
        assert snap["extra"]["metric"] == "infer/ttft_s", snap["extra"]
        assert snap["extra"]["kind"] == ALERT_FAST
        results.append(f"evidence dump parsed: {os.path.basename(dumps[0])} "
                       f"(fast_burn={snap['extra']['fast_burn']:.2f})")

        # recovery: clear the fault, keep offering probes until the
        # windows drain calm -- exactly ONE cleared alert, no flap.
        # Early probes may legally SHED while the burn-escalated shed
        # ladder unwinds from admission-pause; recovery is complete only
        # once the burn state is ok AND a probe serves end-to-end again.
        victim.host.replica.fault = None
        fe.run_until_idle()
        probe_done = False
        deadline = _time.monotonic() + 20.0
        while (ev.state != STATE_OK or not probe_done) \
                and _time.monotonic() < deadline:
            t = fe.submit([9, 8, 7], max_new_tokens=2, deadline_s=60.0)
            fe.run_until_idle()
            probe_done = t.state is RequestState.DONE
            fe.step()
            _time.sleep(0.02)
        assert ev.state == STATE_OK, \
            f"burn never cleared (state {ev.state})"
        assert probe_done, "admission never resumed after the burn cleared"
        assert kind_count(ALERT_CLEARED) == 1, \
            f"cleared {kind_count(ALERT_CLEARED)}x (flapping)"
        assert fe.slo_pressure == 0.0, fe.slo_pressure
        # hold calm for a while: the alert must NOT re-fire
        for _ in range(30):
            fe.step()
            _time.sleep(0.01)
        assert kind_count(ALERT_FAST) == 1, "alert flapped after recovery"
        _fabric_clean(fe, "slo_burn (recovered)")
        results.append(
            "fault cleared: burn state ok, 1 cleared alert, "
            "pressure 0, no flapping over 30 calm rounds")
    finally:
        restore()
    return results


# --------------------------------------------------- rolling deployments
def _deploy_pool(n=3, num_blocks=64, block_size=8, max_ctx=64,
                 seq_budget=4, decode_batch=4, pool=None):
    """``_replica_pool`` plus the rolling-deployment fixtures: a source
    engine holding a NEW weight version (every >=1-d leaf flipped along
    axis 0 -- a drastic, deterministic perturbation so greedy outputs
    genuinely change) and a per-version reference factory.  Returns
    ``(pool_frontend, source_engine, make_ref)``; ``make_ref(new=True)``
    builds the new-version greedy baseline."""
    _force_cpu()
    import jax
    from deeperspeed_tpu.inference.v2 import (DSScheduler, InferenceEngineV2,
                                              RoutingFrontend)
    from deeperspeed_tpu.inference.v2.deploy import WeightVersion
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    model = GPTNeoX(GPTNeoXConfig.tiny(max_seq_len=max_ctx))
    cfg = {"dtype": "float32",
           "kv_cache": {"num_blocks": num_blocks, "block_size": block_size},
           "state_manager": {"max_context": max_ctx,
                             "max_ragged_batch_size": max_ctx,
                             "max_ragged_sequence_count": seq_budget},
           "max_decode_batch": decode_batch}
    if pool is not None:
        cfg["replica_pool"] = pool

    def _perturb(params):
        return jax.tree_util.tree_map(
            lambda x: x if x.ndim == 0 else jax.numpy.flip(x, axis=0),
            params)

    engines = [InferenceEngineV2(model, config=cfg) for _ in range(n)]
    fe = _maybe_instrument(RoutingFrontend(engines))
    src = InferenceEngineV2(model, config=cfg)
    src.params = _perturb(src.params)
    WeightVersion.refresh(src)

    def make_ref(new=False):
        eng = InferenceEngineV2(model, config=cfg)
        if new:
            eng.params = _perturb(eng.params)
        return DSScheduler(eng)

    return fe, src, make_ref


def scenario_weight_swap_kill(workdir, writer=None):
    """Kill the weight donor mid-stream during a rolling update, under
    live traffic.  The updater must retry the stream (capped backoff,
    fresh channel), the pool must lose NO request, and every replica must
    land on the new version with greedy outputs matching the same-weights
    reference for whichever version served each request."""
    import numpy as np
    from deeperspeed_tpu.inference.v2 import RequestState
    from deeperspeed_tpu.inference.v2 import deploy as deploy_mod
    from deeperspeed_tpu.inference.v2.config import DeployConfig
    from deeperspeed_tpu.inference.v2.deploy import (RollingUpdater,
                                                     WeightVersion)

    results = []
    reg, restore = _serving_registry()
    try:
        fe, src, make_ref = _deploy_pool(n=3)
        new_v = WeightVersion.of_engine(src).version
        rng = np.random.default_rng(23)
        prompts = [list(rng.integers(1, 250, size=m))
                   for m in (9, 12, 7, 14, 10, 8)]
        max_new = 5
        exp_old = [np.asarray(o)[len(p):] for p, o in
                   zip(prompts, make_ref().generate(prompts, max_new))]
        exp_new = [np.asarray(o)[len(p):] for p, o in
                   zip(prompts, make_ref(new=True).generate(prompts,
                                                            max_new))]

        # the flipped weights genuinely diverge, so the canary reports
        # divergence by design; budget 1.0 keeps the gate informative
        # without blocking this scenario's swap-kill focus
        dcfg = DeployConfig(stream_retry_base_s=0.01,
                            stream_retry_cap_s=0.05,
                            divergence_budget=1.0, canary_requests=2,
                            canary_max_new_tokens=4)
        upd = RollingUpdater(fe, src, config=dcfg, pump_pool=True)

        def die_mid_stream(args, result):
            seam.armed = False
            raise RuntimeError("donor link dropped mid-stream (chaos)")

        with SeamPatcher(deploy_mod, "_donor_send", die_mid_stream) as seam:
            seam.armed = True
            tickets, i, rounds = [], 0, 0
            while ((not upd.done or fe.has_work or i < len(prompts))
                   and rounds < 200_000):
                if i < len(prompts):
                    tickets.append(fe.submit(prompts[i],
                                             max_new_tokens=max_new,
                                             deadline_s=120.0))
                    i += 1
                upd.step()
                rounds += 1
        s = upd.summary()
        assert s["phase"] == "done", s
        assert seam.fired == 1, f"seam fired {seam.fired}x"
        assert s["stream_retries"] >= 1, s
        assert len(s["rotations"]) == 3, s
        lost = [t.uid for t in tickets if t.state is not RequestState.DONE]
        assert not lost, f"rotation lost requests: {lost}"
        by_version = {"old": 0, "new": 0}
        for t, eo, en in zip(tickets, exp_old, exp_new):
            if t.weight_version == new_v:
                exp, by_version["new"] = en, by_version["new"] + 1
            else:
                exp, by_version["old"] = eo, by_version["old"] + 1
            np.testing.assert_array_equal(
                np.asarray(t.tokens, np.int32), exp,
                err_msg=f"{t.uid}: greedy parity broken for its version")
        assert all(r.weight_version == new_v for r in fe.replicas)
        assert fe.active_weight_version == new_v
        _pool_clean(fe, "weight_swap_kill")
        assert reg.counter("infer/deploy_rotations").total == 3
        assert reg.counter("infer/deploy_stream_retries").total >= 1
        results.append(
            f"donor killed mid-stream: {s['stream_retries']} retry, "
            f"3/3 replicas rotated, 0/{len(tickets)} requests lost, "
            f"greedy parity per version (old={by_version['old']} "
            f"new={by_version['new']})")
    finally:
        restore()
    return results


def scenario_weight_corrupt(workdir, writer=None):
    """Bit-flip a weight leaf on the donor wire mid-rotation.  The
    per-leaf digest must reject the stream, the transactional fetch must
    leave the victim's old weights bit-intact, the rotation must abort
    with a ``deploy_abort`` flight dump, and the victim must be
    readmitted serving the OLD version."""
    import jax
    import numpy as np
    from deeperspeed_tpu.inference.v2 import RequestState
    from deeperspeed_tpu.inference.v2 import deploy as deploy_mod
    from deeperspeed_tpu.inference.v2.config import DeployConfig
    from deeperspeed_tpu.inference.v2.deploy import RollingUpdater

    results = []
    reg, restore = _serving_registry()
    try:
        fe, src, _ = _deploy_pool(n=2)
        victim = fe.replicas[0]
        before = [np.asarray(l).copy() for l in
                  jax.tree_util.tree_leaves(victim.engine.params)]
        old_v = victim.weight_version

        def corrupt(args, result):
            seam.armed = False
            bad = np.array(result, copy=True)
            bad.flat[0] = bad.flat[0] + 1.0
            return bad

        upd = RollingUpdater(
            fe, src, config=DeployConfig(stream_retry_base_s=0.01,
                                         stream_retry_cap_s=0.05),
            pump_pool=True)
        with SeamPatcher(deploy_mod, "_donor_leaf", corrupt) as seam:
            seam.armed = True
            upd.run_until_done(max_rounds=200_000)
        s = upd.summary()
        assert s["phase"] == "aborted", s
        assert str(s["abort_reason"]).startswith("stream_corrupt"), s
        assert s["stream_retries"] == 0, \
            "a tampered stream must never be retried"
        after = [np.asarray(l) for l in
                 jax.tree_util.tree_leaves(victim.engine.params)]
        for i, (b, a) in enumerate(zip(before, after)):
            np.testing.assert_array_equal(
                a, b, err_msg=f"leaf {i}: corrupt fetch mutated weights")
        assert victim.weight_version == old_v
        results.append("tampered leaf rejected by digest: abort, victim "
                       "weights bit-intact on the old version")

        probe = fe.submit([3, 1, 4, 1, 5], max_new_tokens=3)
        fe.run_until_idle()
        assert probe.state is RequestState.DONE, \
            f"post-abort probe ended {probe.state}"
        _pool_clean(fe, "weight_corrupt")
        assert reg.counter("infer/deploy_aborts").total >= 1
        results.append("victim readmitted after abort; pool serving")
    finally:
        restore()
    return results


def scenario_canary_diverge(workdir, writer=None):
    """Shadow-canary gate: the new weights greedily diverge from the
    serving version on replayed recorded traffic.  With a zero divergence
    budget the rotation must roll the victim back BIT-EXACTLY from an
    old-version peer, abort with a ``deploy_abort`` dump, and leave the
    pool serving the old version with no shadow ticket leaked."""
    import jax
    import numpy as np
    from deeperspeed_tpu.inference.v2 import RequestState
    from deeperspeed_tpu.inference.v2.config import DeployConfig
    from deeperspeed_tpu.inference.v2.deploy import RollingUpdater

    results = []
    reg, restore = _serving_registry()
    try:
        fe, src, _ = _deploy_pool(n=2)
        # live traffic first, so the canary replays RECORDED workload
        # shapes (the run_scenario wrapper has the tracer enabled)
        rng = np.random.default_rng(31)
        warm = [fe.submit(list(rng.integers(1, 250, size=m)),
                          max_new_tokens=4, deadline_s=120.0)
                for m in (8, 11, 6, 9)]
        fe.run_until_idle()
        assert all(t.state is RequestState.DONE for t in warm)

        victim = fe.replicas[0]
        before = [np.asarray(l).copy() for l in
                  jax.tree_util.tree_leaves(victim.engine.params)]
        old_v = victim.weight_version

        upd = RollingUpdater(
            fe, src,
            config=DeployConfig(divergence_budget=0.0, canary_requests=3,
                                canary_max_new_tokens=4,
                                stream_retry_base_s=0.01,
                                stream_retry_cap_s=0.05),
            pump_pool=True)
        upd.run_until_done(max_rounds=200_000)
        s = upd.summary()
        assert s["phase"] == "aborted", s
        assert s["abort_reason"] == "canary_diverge", s
        assert s["canary"] and s["canary"]["diverged"] > 0, s
        assert s["canary"]["workload"] == "recorded", s["canary"]
        after = [np.asarray(l) for l in
                 jax.tree_util.tree_leaves(victim.engine.params)]
        for i, (b, a) in enumerate(zip(before, after)):
            np.testing.assert_array_equal(
                a, b, err_msg=f"leaf {i}: rollback not bit-exact")
        assert victim.weight_version == old_v
        assert fe.active_weight_version == old_v
        for rep in fe.replicas:
            leaked = [u for u in rep.frontend.tickets
                      if str(u).startswith("__canary")]
            assert not leaked, f"replica {rep.rid} leaked {leaked}"
        results.append(
            f"canary diverged {s['canary']['diverged']}/"
            f"{s['canary']['requests']} on recorded traffic: rolled back "
            "bit-exactly, pool pinned to the old version")

        probe = fe.submit([3, 1, 4, 1, 5], max_new_tokens=3)
        fe.run_until_idle()
        assert probe.state is RequestState.DONE, \
            f"post-rollback probe ended {probe.state}"
        _pool_clean(fe, "canary_diverge")
        assert reg.counter("infer/deploy_canary").total >= 1
        assert reg.counter("infer/deploy_rollbacks").total >= 1
        assert reg.counter("infer/deploy_aborts").total >= 1
        results.append("victim readmitted on old weights; pool serving")
    finally:
        restore()
    return results


STORAGE_SCENARIOS = {
    "kill": scenario_kill,
    "eio": scenario_eio,
    "torn_write": scenario_torn_write,
    "bitflip": scenario_bitflip,
}

SERVING_SCENARIOS = {
    "nan_logits": scenario_nan_logits,
    "oom_round": scenario_oom_round,
    "slow_step": scenario_slow_step,
    "flood": scenario_flood,
    "spec_reject_storm": scenario_spec_reject_storm,
}

POOL_SCENARIOS = {
    "replica_kill": scenario_replica_kill,
    "replica_slow": scenario_replica_slow,
    "replica_flap": scenario_replica_flap,
    "drain_under_load": scenario_drain_under_load,
}

def scenario_migration_drop_fp8(workdir, writer=None):
    """migration_drop with fp8 e4m3 block-scaled KV payloads on the wire:
    the recompute fallback and the post-fault migration path must hold
    under the 1-byte frame format too."""
    return scenario_migration_drop(workdir, writer=writer, kv_dtype="fp8")


def scenario_host_tier_corrupt_fp8(workdir, writer=None):
    """host_tier_corrupt with fp8 e4m3 block-scaled KV spilled to the host
    tier: a flipped byte in a 1-byte payload must still trip the digest
    check and read as a plain miss."""
    return scenario_host_tier_corrupt(workdir, writer=writer, kv_dtype="fp8")


DISAGG_SCENARIOS = {
    "migration_drop": scenario_migration_drop,
    "migration_drop_fp8": scenario_migration_drop_fp8,
    "host_tier_corrupt": scenario_host_tier_corrupt,
    "host_tier_corrupt_fp8": scenario_host_tier_corrupt_fp8,
}

# the tenant storm drives the full multi-tenant autoscaling bench (two
# arms plus a scale cycle plus a preemption phase), so like the fabric
# set it stays out of the generic SCENARIOS sweep and gets one dedicated
# tier-1 wrapper in tests/unit/inference/test_chaos_serving.py (with a
# bigger --runslow storm invoked directly)
ELASTIC_SCENARIOS = {
    "tenant_storm": scenario_tenant_storm,
}

# rolling-deployment faults (PR 18): donor kill mid-stream, tampered
# leaf, canary divergence.  Like the elastic/fabric sets they run full
# rotations, so they are kept out of the generic SCENARIOS sweep and get
# dedicated tier-1 wrappers (tests/unit/inference/test_chaos_deploy.py).
DEPLOY_SCENARIOS = {
    "weight_swap_kill": scenario_weight_swap_kill,
    "weight_corrupt": scenario_weight_corrupt,
    "canary_diverge": scenario_canary_diverge,
}

def _longctx_engine(model, num_blocks, tier_capacity=64, tier=True,
                    tier_capacity_bytes=0):
    from deeperspeed_tpu.inference.v2 import InferenceEngineV2

    cfg = {"dtype": "float32",
           "kv_cache": {"num_blocks": num_blocks, "block_size": 8,
                        "prefix_cache": True},
           "state_manager": {"max_context": 128, "max_decode_batch": 4},
           "longctx": {"enabled": True, "hot_prefix_blocks": 1,
                       "hot_recent_blocks": 2, "segment_blocks": 2,
                       "prefill_chunk_tokens": 16}}
    if tier:
        cfg["kv_tier"] = {"enabled": True,
                          "capacity_blocks": tier_capacity,
                          "capacity_bytes": tier_capacity_bytes,
                          "prefetch_depth": 2}
    return InferenceEngineV2(model, config=cfg)


def scenario_tier_thrash(workdir, writer=None):
    """Concurrent long-context + short traffic on one engine: the long
    sequence's PINNED cold blocks and the short prompts' prefix-cache
    spills churn the same byte-bounded host tier.  LRU eviction must only
    ever take unpinned (cache-copy) entries, both streams must stay
    greedy-bit-exact against their clean baselines, and the allocator and
    tier accounting must audit clean after the churn."""
    import numpy as np

    from deeperspeed_tpu.inference.v2 import DSScheduler
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    _force_cpu()
    results = []
    reg, restore = _serving_registry()
    try:
        model = GPTNeoX(GPTNeoXConfig.tiny(max_seq_len=128))
        rng = np.random.default_rng(7)
        long_prompt = [int(t) for t in rng.integers(1, 250, size=64)]
        shorts = [list(int(t) for t in rng.integers(1, 250, size=18))
                  for _ in range(6)]

        # clean baselines on an unconstrained engine
        ref = _longctx_engine(model, num_blocks=64, tier=False)
        want_long = [int(t) for t in
                     ref.generate([long_prompt], max_new_tokens=8)[0]][-8:]
        want_short = DSScheduler(
            _longctx_engine(model, num_blocks=64, tier=False)).generate(
            shorts, max_new_tokens=4)

        # thrash arm: 14-block pool, tier byte-capacity sized to ~6 blocks
        # so short-traffic prefix spills LRU-churn around the pinned
        # long-context middle
        engine = _longctx_engine(model, num_blocks=14, tier_capacity=64,
                                 tier_capacity_bytes=6 * 8 * 2
                                 * model.config.num_layers
                                 * model.config.num_heads
                                 * model.config.head_dim * 4)
        tier = engine.host_tier
        sess = engine.longctx_session(uid="thrash-long")
        sess.prefill(long_prompt)
        sched = DSScheduler(engine)
        got_long = []
        got_short = []
        for burst in range(3):
            got_long.extend(sess.generate(3))          # long decode churn
            got_short.extend(sched.generate(            # short churn
                shorts[burst * 2:burst * 2 + 2], max_new_tokens=4))
        got_long = got_long[:8] + sess.generate(max(0, 8 - len(got_long)))
        assert got_long[:8] == want_long, "tier_thrash: long stream diverged"
        for w, g in zip(want_short, got_short):
            assert np.array_equal(w, g), "tier_thrash: short stream diverged"
        assert tier.spills >= 1 and tier.stream_fetches >= 1
        assert tier.bytes_used <= max(
            tier.capacity_bytes,
            sum(nb for _, _, nb in tier._entries.values())), \
            "tier byte accounting inconsistent"
        for ref_blk in sess.blocks:
            if ref_blk.pool is None:
                assert ref_blk.key in tier, \
                    "tier_thrash: pinned live block evicted (data loss)"
        results.append(
            f"thrash survived: {tier.spills} spills, {tier.evictions} "
            f"evictions, {tier.stream_fetches} stream fetches, "
            f"pinned_overflow={tier.pinned_overflow}, both streams "
            f"bit-exact")
        sess.close()
        tier.audit()
        engine.state_manager.allocator.audit()
        free = engine.state_manager.free_blocks_with_evictable()
        assert free == engine.state_manager.allocator.total_blocks, \
            "tier_thrash: leaked KV blocks"
        results.append("zero leaked blocks after close")
    finally:
        restore()
    return results


def scenario_longctx_host_loss(workdir, writer=None):
    """A prefill shard host dies mid-stream during sequence-parallel
    prefill: the coordinator must roll the decode side back to the shard
    boundary, leave a flight dump, recompute the shard on a surviving
    engine, and finish with tokens bit-exact against the clean run --
    zero leaked blocks on every engine."""
    import numpy as np

    from deeperspeed_tpu.inference.v2 import SequenceParallelPrefill
    from deeperspeed_tpu.inference.v2 import longctx as longctx_mod
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    _force_cpu()
    results = []
    reg, restore = _serving_registry()
    try:
        model = GPTNeoX(GPTNeoXConfig.tiny(max_seq_len=256))
        rng = np.random.default_rng(11)
        prompt = [int(t) for t in rng.integers(1, 250, size=72)]

        def run(arm_loss):
            dec = _longctx_engine(model, num_blocks=10)
            p1 = _longctx_engine(model, num_blocks=16, tier=False)
            p2 = _longctx_engine(model, num_blocks=16, tier=False)
            sp = SequenceParallelPrefill(dec, [p1, p2], uid="chaos-seqpar")

            def _kill(args, res):
                if args[0] == 1:          # shard 1's first shipped block
                    patch.armed = False
                    raise RuntimeError("injected: shard host lost")
                return res

            with SeamPatcher(longctx_mod, "_shard_seam", _kill) as patch:
                patch.armed = arm_loss
                sess = sp.run(prompt)
                toks = sess.generate(8)
                fired = patch.fired
            sess.audit()
            sess.close()
            sess.audit()
            for e in (dec, p1, p2):
                e.state_manager.allocator.audit()
            return toks, sp, fired

        want, _, _ = run(arm_loss=False)
        got, sp, fired = run(arm_loss=True)
        assert fired >= 1, "host-loss seam never fired"
        assert any(e[1] == "shard_loss" for e in sp.events), \
            "coordinator never recorded the shard loss"
        assert got == want, "longctx_host_loss: recompute diverged"
        imports = [e for e in sp.events if e[1] == "decode_import"]
        commits = [e for e in sp.events if e[1] == "shard_commit"]
        assert imports and commits and imports[0][0] < commits[-1][0], \
            "decode admission did not overlap prefill"
        results.append(
            f"shard loss recovered: recompute bit-exact over 8 tokens, "
            f"{len(imports)} streamed blocks, decode overlap held")
        results.append("zero leaked blocks on decode + both prefill engines")
    finally:
        restore()
    return results


# long-context scenarios drive full multi-engine prefill pipelines, so
# like the elastic/fabric/deploy sets they stay out of the generic
# SCENARIOS sweep and get dedicated tier-1 wrappers
# (tests/unit/inference/test_chaos_longctx.py)
LONGCTX_SCENARIOS = {
    "tier_thrash": scenario_tier_thrash,
    "longctx_host_loss": scenario_longctx_host_loss,
}

# registered names run the deterministic loopback transport (tier-1); the
# socket variants are invoked directly with transport="socket" by the
# --runslow test wrappers
FABRIC_SCENARIOS = {
    "net_partition": scenario_net_partition,
    "slow_link": scenario_slow_link,
    "half_open_socket": scenario_half_open_socket,
    "peer_kill": scenario_peer_kill,
    "slo_burn": scenario_slo_burn,
}

# SCENARIOS is the set the generic chaos test sweep parametrizes over;
# fabric scenarios are kept out of it (they have their own dedicated test
# wrappers in tests/unit/inference/test_chaos_fabric.py, so listing them
# here would run each one twice per tier-1 pass).  run_scenario and the
# CLI resolve both sets.
SCENARIOS = {**STORAGE_SCENARIOS, **SERVING_SCENARIOS, **POOL_SCENARIOS,
             **DISAGG_SCENARIOS}

ALL_SCENARIOS = {**SCENARIOS, **ELASTIC_SCENARIOS, **FABRIC_SCENARIOS,
                 **DEPLOY_SCENARIOS, **LONGCTX_SCENARIOS}

GROUPS = {
    "all": sorted(ALL_SCENARIOS),
    "storage": sorted(STORAGE_SCENARIOS),
    "serving": sorted({**SERVING_SCENARIOS, **ELASTIC_SCENARIOS}),
    "pool": sorted(POOL_SCENARIOS),
    "disagg": sorted(DISAGG_SCENARIOS),
    "fabric": sorted(FABRIC_SCENARIOS),
    "deploy": sorted(DEPLOY_SCENARIOS),
    "longctx": sorted(LONGCTX_SCENARIOS),
}


# scenarios whose injected fault must leave a flight-recorder dump
# (telemetry.trace), mapped to the dump-reason prefixes that count as the
# fault being narrated.  run_scenario installs an enabled tracer around
# these and asserts a matching dump exists and parses afterwards.
FLIGHT_SCENARIOS = {
    "nan_logits": ("circuit_break", "quarantine"),
    "slow_step": ("stall_",),
    "tenant_storm": ("tenant_throttle",),
    "replica_kill": ("replica_eject", "failover"),
    "drain_under_load": ("drain_past_grace",),
    "migration_drop": ("recompute_fallback",),
    "migration_drop_fp8": ("recompute_fallback",),
    "host_tier_corrupt": ("kv_corrupt",),
    "host_tier_corrupt_fp8": ("kv_corrupt",),
    "peer_kill": ("replica_eject", "failover"),
    "slo_burn": ("slo_burn",),
    "weight_corrupt": ("deploy_abort",),
    "canary_diverge": ("deploy_abort",),
    "longctx_host_loss": ("longctx_shard_loss",),
}


def assert_flight_dump(tracer, scenario):
    """The observability contract: every injected fault leaves at least
    one parseable flight-recorder dump whose reason names the fault."""
    reasons = FLIGHT_SCENARIOS[scenario]
    dumps = tracer.flight_dumps
    assert dumps, (f"{scenario}: injected fault left no flight-recorder "
                   f"dump (expected reason in {reasons})")
    matched = []
    for path in dumps:
        assert os.path.exists(path), f"{scenario}: missing dump {path}"
        with open(path) as f:
            snap = json.load(f)        # must parse
        for key in ("ts", "reason", "extra", "spans"):
            assert key in snap, f"{scenario}: dump {path} lacks {key!r}"
        if any(str(snap["reason"]).startswith(r) for r in reasons):
            matched.append(snap["reason"])
    assert matched, (f"{scenario}: {len(dumps)} dump(s) but none with a "
                     f"reason in {reasons}")
    return (f"flight recorder: {len(dumps)} dump(s), "
            f"matched {sorted(set(matched))}")


def run_scenario(scenario, workdir, writer=None):
    os.makedirs(workdir, exist_ok=True)
    if scenario not in FLIGHT_SCENARIOS:
        return ALL_SCENARIOS[scenario](workdir, writer=writer)
    from deeperspeed_tpu.telemetry.trace import Tracer, get_tracer, set_tracer
    old = get_tracer()
    tracer = set_tracer(Tracer(
        enabled=True, run_dir=os.path.join(workdir, "flight"),
        job_name=scenario, jsonl=False))
    try:
        checks = ALL_SCENARIOS[scenario](workdir, writer=writer)
    finally:
        set_tracer(old)
    note = assert_flight_dump(tracer, scenario)
    if isinstance(checks, list):
        checks.append(note)
    return checks


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scenario", default="all",
                    choices=sorted(ALL_SCENARIOS) + sorted(GROUPS))
    ap.add_argument("--workdir", default=None,
                    help="scratch dir (default: a fresh tmpdir)")
    ap.add_argument("--writer", default=None, choices=["native", "async"],
                    help="checkpoint engine under test (default native)")
    ap.add_argument("--runtime-locks", action="store_true",
                    help="run pool/fabric scenarios with every discipline "
                         "lock wrapped in the analyzer's rank-checking "
                         "proxy; fail if any thread inverts the declared "
                         "lock order")
    args = ap.parse_args(argv)

    global RUNTIME_LOCKS
    RUNTIME_LOCKS = bool(args.runtime_locks)
    if RUNTIME_LOCKS:
        from deeperspeed_tpu.analysis import runtime_locks

        runtime_locks.reset()

    workdir = args.workdir or tempfile.mkdtemp(prefix="dst_chaos_")
    names = GROUPS.get(args.scenario, [args.scenario])
    report = {}
    failed = False
    for name in names:
        sub = os.path.join(workdir, name)
        try:
            report[name] = {"ok": True,
                            "checks": run_scenario(name, sub,
                                                   writer=args.writer)}
        except (KilledMidSave, Exception) as e:  # noqa: BLE001
            failed = True
            report[name] = {"ok": False, "error": f"{type(e).__name__}: {e}"}
    if RUNTIME_LOCKS:
        from deeperspeed_tpu.analysis import runtime_locks

        bad = runtime_locks.violations()
        report["runtime_locks"] = {"ok": not bad, "violations": bad}
        failed = failed or bool(bad)
    print(json.dumps(report, indent=2))
    if args.workdir is None:
        shutil.rmtree(workdir, ignore_errors=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

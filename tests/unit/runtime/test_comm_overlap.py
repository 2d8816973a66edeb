"""comm.overlap: deferred/bucketed grad-reduction parity against the
per-microbatch baseline, the traced wire-byte reduction, and the
donation-safe device-prefetching input pipeline."""

import numpy as np
import pytest

import deeperspeed_tpu as dst
from deeperspeed_tpu.models import SimpleMLP


def _cfg(gas=2, **overrides):
    cfg = {
        "train_batch_size": 8 * gas,
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "gradient_clipping": 1.0,
    }
    cfg.update(overrides)
    return cfg


def _train(cfg, steps=4, seed=0, training_data=None):
    model = SimpleMLP(hidden_dim=16)
    engine, _, _, _ = dst.initialize(model=model, config=cfg,
                                     training_data=training_data)
    if training_data is not None:
        losses = [float(engine.train_batch()) for _ in range(steps)]
    else:
        batch = model.example_batch(batch_size=cfg["train_batch_size"],
                                    seed=seed)
        losses = [float(engine.train_batch(batch=batch)) for _ in range(steps)]
    return engine, losses


@pytest.fixture(scope="module")
def baseline_losses():
    """Per-microbatch (GSPMD psum-per-scan-step) trajectories, one per gas,
    on a fresh pure-dp mesh."""
    from deeperspeed_tpu.parallel import topology as topo

    old = topo._GLOBAL_MESH
    topo.set_mesh(topo.MeshTopology())
    try:
        return {gas: _train(_cfg(gas=gas))[1] for gas in (1, 2, 4)}
    finally:
        topo._GLOBAL_MESH = old


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
@pytest.mark.parametrize("gas", [1, 2, 4])
def test_deferred_parity(mesh8, baseline_losses, stage, gas):
    """Deferred (once-per-batch) reduction matches the per-microbatch
    trajectory within accum-dtype tolerance at every ZeRO stage."""
    engine, losses = _train(_cfg(
        gas=gas,
        zero_optimization={"stage": stage, "param_persistence_threshold": 1},
        comm={"overlap": {"enabled": True}}))
    assert engine._reduction.name == "deferred"
    np.testing.assert_allclose(losses, baseline_losses[gas], rtol=2e-4)


@pytest.mark.parametrize("stage", [1, 3])
def test_deferred_bucketed_parity(mesh8, baseline_losses, stage):
    """A tiny bucket_mb (every leaf its own bucket group) must not change
    the numerics -- bucketing only changes collective issue order."""
    engine, losses = _train(_cfg(
        gas=2,
        zero_optimization={"stage": stage, "param_persistence_threshold": 1},
        comm={"overlap": {"enabled": True, "bucket_mb": 1e-4}}))
    assert engine._reduction.name == "deferred"
    np.testing.assert_allclose(losses, baseline_losses[2], rtol=2e-4)


def test_qgz_bucketed_parity(mesh8):
    """qgZ keeps its quantized schedule under comm.overlap; the bucketed
    fused issue only re-draws int8 group boundaries across leaf edges, so
    trajectories agree to quantization tolerance."""
    qgz = {"quantized": {"enabled": True}}
    _, plain = _train(_cfg(gas=2, comm=qgz))
    engine, bucketed = _train(_cfg(
        gas=2, comm={**qgz, "overlap": {"enabled": True, "bucket_mb": 1e-4}}))
    assert engine._reduction.name == "qgz"
    np.testing.assert_allclose(bucketed, plain, rtol=2e-2)


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
@pytest.mark.parametrize("gas", [1, 2, 4])
def test_auto_schedule_bitexact_vs_manual(mesh8, baseline_losses, stage, gas):
    """Acceptance: comm.overlap.schedule.mode=auto plans the same deferred
    schedule the manual path hand-places on dp-only meshes, and the jaxpr
    hoist pass is a pure dataflow reorder -- trajectories bit-identical
    to manual at every ZeRO stage x accumulation depth (and within
    accum-dtype tolerance of the per-microbatch baseline, which
    legitimately sums gradients in a different order)."""
    zero = {"stage": stage, "param_persistence_threshold": 1}
    _, manual = _train(_cfg(gas=gas, zero_optimization=zero,
                            comm={"overlap": {"enabled": True}}))
    engine, auto = _train(_cfg(
        gas=gas, zero_optimization=zero,
        comm={"overlap": {"enabled": True, "schedule": {"mode": "auto"}}}))
    assert engine._reduction.plan is not None
    assert not engine._reduction.plan.fallback
    assert engine._reduction.plan.grad_schedule == "deferred"
    assert engine._reduction.name == "deferred"
    assert auto == manual, (auto, manual)
    np.testing.assert_allclose(auto, baseline_losses[gas], rtol=2e-4)


def test_auto_schedule_plans_model_parallel(reset_mesh, tmp_path):
    """Where manual warns + falls back (tp>1 blocks the manual-dp deferred
    loop), auto must emit a PLANNED per-microbatch + hoist schedule: no
    fallback flag, bit-identical losses, traced wire bytes no worse than
    the manual fallback, and the schedule tag in the telemetry footprint."""
    topo = reset_mesh
    tele = {"enabled": True, "output_path": str(tmp_path), "flush_every": 1}

    def run(mode):
        mesh = topo.MeshTopology(dp=4, tp=2)
        topo.set_mesh(mesh)
        model = SimpleMLP(hidden_dim=16)
        engine, _, _, _ = dst.initialize(
            model=model, mesh=mesh,
            config={"train_batch_size": 8,
                    "train_micro_batch_size_per_gpu": 1,
                    "gradient_accumulation_steps": 2,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                    "mesh": {"model_parallel_size": 2},
                    "telemetry": tele,
                    "comm": {"overlap": {"enabled": True,
                                         "schedule": {"mode": mode}}}})
        batch = model.example_batch(batch_size=8, seed=0)
        losses = [float(engine.train_batch(batch=batch)) for _ in range(2)]
        return engine, losses

    manual_engine, manual = run("manual")
    assert manual_engine._reduction.name == "per_microbatch"
    auto_engine, auto = run("auto")
    plan = auto_engine._reduction.plan
    assert plan is not None and not plan.fallback
    assert plan.grad_schedule == "per_microbatch" and plan.hoist
    assert auto == manual, (auto, manual)
    manual_bytes, _ = _grad_reduce_bytes(manual_engine)
    auto_bytes, _ = _grad_reduce_bytes(auto_engine)
    assert auto_bytes <= manual_bytes + 1e-6
    tagged = [r for r in auto_engine._comm_footprint
              if r["op"] == "grad_reduce_dp"]
    assert all(r.get("schedule") == plan.tag for r in tagged)


def test_model_parallel_fallback_warns_once_naming_schedule(reset_mesh,
                                                            monkeypatch):
    """Satellite: the tp>1 manual fallback warning fires once per process
    (not once per engine) and names the schedule it falls back TO."""
    from deeperspeed_tpu.utils import logging as dlog

    calls = []
    monkeypatch.setattr(dlog.logger, "warning",
                        lambda msg, *a, **k: calls.append(str(msg)))
    monkeypatch.setattr(dlog.warning_once, "_warned", set(), raising=False)

    topo = reset_mesh
    for _ in range(2):
        mesh = topo.MeshTopology(dp=4, tp=2)
        topo.set_mesh(mesh)
        dst.initialize(
            model=SimpleMLP(hidden_dim=16), mesh=mesh,
            config={"train_batch_size": 8,
                    "train_micro_batch_size_per_gpu": 1,
                    "gradient_accumulation_steps": 2,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                    "mesh": {"model_parallel_size": 2},
                    "comm": {"overlap": {"enabled": True}}})
    warned = [m for m in calls
              if "comm.overlap.deferred_reduction disabled" in m]
    assert len(warned) == 1, calls
    assert "per-microbatch" in warned[0]
    assert "schedule.mode=auto" in warned[0]


def _grad_reduce_bytes(engine):
    recs = [r for r in (engine._comm_footprint or [])
            if r["op"] == "grad_reduce_dp"]
    assert recs, f"no grad_reduce_dp record in {engine._comm_footprint}"
    return sum(r["bytes"] for r in recs), sum(r["count"] for r in recs)


@pytest.mark.parametrize("stage", [0, 2])
def test_deferred_cuts_wire_bytes_by_gas(mesh8, tmp_path, stage):
    """Acceptance: at gas=4 the deferred schedule's traced dp grad-reduce
    bytes-on-wire are gas x smaller than the per-microbatch schedule's
    (one reduction per batch instead of one per microbatch)."""
    gas = 4
    tele = {"enabled": True, "output_path": str(tmp_path), "flush_every": 1}

    def bytes_for(overlap):
        cfg = _cfg(gas=gas, telemetry=tele,
                   zero_optimization={"stage": stage},
                   comm={"overlap": {"enabled": overlap}})
        engine, _ = _train(cfg, steps=1)
        assert (engine._reduction.name == "deferred") is overlap
        return _grad_reduce_bytes(engine)

    per_mb_bytes, per_mb_calls = bytes_for(False)
    deferred_bytes, deferred_calls = bytes_for(True)
    assert per_mb_bytes / deferred_bytes >= gas - 1e-6, (
        f"wire bytes per_microbatch={per_mb_bytes} deferred={deferred_bytes}")
    assert per_mb_calls == gas * deferred_calls


def _toy_data(n=64, dim=16):
    rs = np.random.RandomState(0)
    return {"x": rs.randn(n, dim).astype("float32"),
            "y": rs.randn(n, 1).astype("float32")}


def test_donation_prefetch_bitexact(mesh8):
    """Satellite: with buffer donation active (default state-donating jit),
    the bounded prefetch pool must round-trip the exact batches -- loss
    trajectories bit-identical to the unprefetched run.  Deferred reduction
    is off: it legitimately reorders the gradient summation; this test
    isolates the prefetch pool."""
    _, plain = _train(_cfg(gas=2), steps=6, training_data=_toy_data())
    engine, prefetched = _train(
        _cfg(gas=2, comm={"overlap": {"enabled": True,
                                      "deferred_reduction": False,
                                      "prefetch_depth": 2}}),
        steps=6, training_data=_toy_data())
    assert engine._prefetcher is not None
    assert plain == prefetched, (plain, prefetched)


def test_prefetch_depth_clamped_under_donation(mesh8, caplog):
    """depth > 2 with donation active clamps to the bounded pool size."""
    model = SimpleMLP(hidden_dim=16)
    engine, _, _, _ = dst.initialize(
        model=model,
        config=_cfg(gas=1, comm={"overlap": {"enabled": True,
                                             "prefetch_depth": 5}}))
    assert engine._prefetch_depth == 2


def test_deferred_falls_back_on_model_parallel(reset_mesh):
    """tp>1 blocks the manual-dp deferred path (full-manual shard_map would
    replicate tensor-parallel compute); the engine must warn + fall back,
    not produce wrong numerics."""
    topo = reset_mesh
    mesh = topo.MeshTopology(dp=4, tp=2)
    topo.set_mesh(mesh)
    model = SimpleMLP(hidden_dim=16)
    engine, _, _, _ = dst.initialize(
        model=model, mesh=mesh,
        config={"train_batch_size": 8,
                "train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "mesh": {"model_parallel_size": 2},
                "comm": {"overlap": {"enabled": True}}})
    assert engine._reduction.name == "per_microbatch"


def test_prefetch_checkpoint_position(mesh8, tmp_path):
    """A save taken while the prefetcher runs ahead must record the
    position of the first UNCONSUMED batch, so resume re-delivers the
    buffered batches instead of skipping them."""
    model = SimpleMLP(hidden_dim=16)
    cfg = _cfg(gas=1, comm={"overlap": {"enabled": True,
                                        "prefetch_depth": 2}})
    engine, _, _, _ = dst.initialize(model=model, config=cfg,
                                     training_data=_toy_data())
    for _ in range(3):
        engine.train_batch()
    # prefetcher pulled ahead: raw loader position > consumed position
    raw = engine.training_dataloader.state_dict()["batch_idx"]
    snap = engine._prefetcher.position()["batch_idx"]
    assert snap == 3
    assert raw > snap
    engine.save_checkpoint(str(tmp_path), tag="t")
    engine2, _, _, _ = dst.initialize(model=model, config=cfg,
                                      training_data=_toy_data())
    engine2.load_checkpoint(str(tmp_path), tag="t")
    assert engine2._prefetcher is None  # stale buffer dropped
    st = engine2.training_dataloader
    assert st._resume_batch_idx == 3 or st.state_dict()["batch_idx"] == 3

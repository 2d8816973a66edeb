"""Planned memory schedules are bit-exact vs static placement, and the
synthetic-HBM-budget config that OOMs under static ZeRO-3 trains via
planned offload (ISSUE 20 acceptance): ``memory_schedule="auto"`` on the
chunk-streamed engine, ``comm.overlap.schedule.memory`` on the main
engine, the residency ledger vs the planned peak bound, and the DST-G002
per-chunk kernel donation gate."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deeperspeed_tpu as dst
from deeperspeed_tpu.comm.memplan import Calibration, HBMBudgetError
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.models.gpt_neox_pipe import GPTNeoXPipe
from deeperspeed_tpu.parallel.topology import MeshTopology

pytest.importorskip("deeperspeed_tpu.ops.adam.cpu_adam")
from deeperspeed_tpu.ops.adam.cpu_adam import cpu_adam_available  # noqa: E402

pytestmark = pytest.mark.skipif(
    not cpu_adam_available(), reason="native cpu_adam not built")


def _make(tmp_path, seed=0, **kw):
    from deeperspeed_tpu.runtime.zero.infinity import ZeroInfinityEngine

    tiny = GPTNeoXConfig.tiny()
    eng = ZeroInfinityEngine(
        GPTNeoXPipe(tiny, num_stages=2), nvme_path=str(tmp_path), lr=1e-3,
        compute_dtype=jnp.float32, seed=seed, **kw)
    return eng, tiny


def _masters(eng):
    return {name: jax.tree_util.tree_leaves(eng.store.get("master", name))
            for name in sorted(eng._unit_bytes)}


@pytest.mark.parametrize("gas", [1, 2])
def test_planned_bitexact_vs_static(reset_mesh,
                                    tmp_path, gas):
    """The planner only moves WHEN bytes move: losses and masters after
    identical steps are bit-equal between static and planned schedules,
    with and without gradient accumulation."""
    eng_s, tiny = _make(tmp_path / "s", seed=7, memory_schedule="static")
    eng_p, _ = _make(tmp_path / "p", seed=7, memory_schedule="auto",
                     calibration=Calibration(compute_s=0.05, h2d_gbps=8.0))
    batch = GPTNeoX(tiny).example_batch(batch_size=8, seq_len=16)
    for _ in range(2):
        ls = eng_s.train_batch(batch, gradient_accumulation_steps=gas)
        lp = eng_p.train_batch(batch, gradient_accumulation_steps=gas)
        assert ls == lp
    for name, a in _masters(eng_s).items():
        for x, y in zip(a, _masters(eng_p)[name]):
            np.testing.assert_array_equal(x, y)
    assert eng_p.mem_plan is not None
    assert eng_p.mem_plan.prefetch_depth >= 1
    eng_s.close()
    eng_p.close()


def test_budget_that_ooms_static_trains_planned(
        reset_mesh, tmp_path):
    """The acceptance config: a synthetic HBM budget below the static
    2-chunk window raises at init under ``static``, while ``auto`` plans
    a depth-0 stream that trains within its modeled peak bound."""
    probe, tiny = _make(tmp_path / "probe", memory_schedule="off")
    max_chunk = max(probe._unit_bytes.values())
    total = sum(probe._unit_bytes.values())
    probe.close()
    budget = max_chunk + max_chunk // 2  # one chunk fits, two do not

    with pytest.raises(HBMBudgetError):
        _make(tmp_path / "s", memory_schedule="static",
              hbm_budget_bytes=budget)

    eng, _ = _make(tmp_path / "p", memory_schedule="auto",
                   hbm_budget_bytes=budget)
    assert eng.mem_plan.peak_bytes <= budget < total
    batch = GPTNeoX(tiny).example_batch(batch_size=4, seq_len=16)
    losses = [eng.train_batch(batch) for _ in range(3)]
    assert np.isfinite(losses).all()
    stats = eng.swap_stats
    assert stats["peak_device_param_bytes"] <= eng.mem_plan.peak_bytes
    assert stats["memory_schedule"] == "auto"
    assert stats["planned_peak_bound"] == eng.mem_plan.peak_bytes
    assert stats["planned_prefetch_depth"] == eng.mem_plan.prefetch_depth
    eng.close()


def test_generous_budget_pins_resident_and_stays_bitexact(
        reset_mesh, tmp_path):
    """With HBM to spare the planner pins everything resident (no per-pass
    streaming) -- and the result is still bit-equal to static."""
    eng_s, tiny = _make(tmp_path / "s", seed=2, memory_schedule="static")
    eng_p, _ = _make(tmp_path / "p", seed=2, memory_schedule="auto",
                     hbm_budget_bytes=1 << 30)
    assert eng_p.mem_plan.streamed == ()
    assert set(eng_p.mem_plan.resident) == set(eng_p._unit_bytes)
    batch = GPTNeoX(tiny).example_batch(batch_size=4, seq_len=16)
    for _ in range(2):
        assert eng_s.train_batch(batch) == eng_p.train_batch(batch)
    assert eng_p.swap_stats["resident_set_bytes"] \
        == eng_p.mem_plan.resident_bytes
    # resident units re-read NVMe only on the cold first fetch
    assert eng_p.swap_stats["bytes_read"] < eng_s.swap_stats["bytes_read"]
    eng_s.close()
    eng_p.close()


def test_chunk_kernel_donation_gate(reset_mesh, tmp_path):
    """Analyzer gate (DST-G002 extension): every per-chunk compiled kernel
    carries an explicit donation declaration after a real step."""
    from deeperspeed_tpu.analysis.graphcheck import check_chunk_kernel_donation
    from deeperspeed_tpu.runtime.zero.infinity import ZeroInfinityEngine

    eng, tiny = _make(tmp_path, memory_schedule="auto")
    batch = GPTNeoX(tiny).example_batch(batch_size=4, seq_len=16)
    eng.train_batch(batch)
    assert eng._fns, "no chunk kernels compiled"
    findings = check_chunk_kernel_donation(
        eng._fns, ZeroInfinityEngine.KERNEL_DONATION)
    assert findings == [], [f.message for f in findings]
    # an undeclared kernel key is a finding
    bad = check_chunk_kernel_donation({"mystery": None}, {})
    assert len(bad) == 1 and bad[0].rule == "DST-G002"
    eng.close()


# --------------------------------------------------- main engine (GSPMD path)

def _engine(mode, zero_stage, gas, budget=None):
    from deeperspeed_tpu.models import SimpleMLP

    n = len(jax.devices())
    cfg = {
        "train_batch_size": n * gas,
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": zero_stage},
        "comm": {"overlap": {"enabled": True, "schedule": {
            "mode": "auto", "memory": mode,
            **({"hbm_budget_bytes": budget} if budget is not None else {}),
        }}},
    }
    model = SimpleMLP(hidden_dim=32)
    engine, _, _, _ = dst.initialize(model=model, config=cfg,
                                     mesh=MeshTopology(dp=n))
    return engine, model


@pytest.mark.parametrize("zero_stage", [2, 3])
@pytest.mark.parametrize("gas", [1, 2])
def test_engine_memory_auto_matches_static(reset_mesh, zero_stage, gas):
    """comm.overlap.schedule.memory: auto vs static on the main engine is
    bit-exact across zero stages and accumulation -- the plan is analysis
    + telemetry on the GSPMD path, never a numeric rewrite."""
    losses = {}
    for mode in ("static", "auto"):
        engine, model = _engine(mode, zero_stage, gas)
        batch = model.example_batch(
            batch_size=engine.train_batch_size(), seed=0)
        losses[mode] = [float(engine.train_batch(batch=batch))
                        for _ in range(2)]
    assert losses["auto"] == losses["static"]


def test_engine_zero3_static_budget_raises_auto_plans(reset_mesh):
    """A synthetic budget below the full ZeRO-3 gathered residency refuses
    static placement at init; auto accepts it (streams) and publishes the
    movement plan after the first step."""
    from deeperspeed_tpu.runtime.zero.sharding import stage3_static_peak_bytes

    engine, model = _engine("auto", 3, 1)
    total = stage3_static_peak_bytes(engine.state["master_params"])
    budget = max(total // 2, 1)
    with pytest.raises(HBMBudgetError):
        _engine("static", 3, 1, budget=budget)

    engine2, model2 = _engine("auto", 3, 1, budget=budget)
    batch = model2.example_batch(batch_size=engine2.train_batch_size(),
                                 seed=0)
    l0 = float(engine2.train_batch(batch=batch))
    assert np.isfinite(l0)
    assert engine2.memory_plan, "movement plan not published after step"
    assert all(s.release_at >= s.first_use >= s.gather_at
               for s in engine2.memory_plan)

"""The one selection of the gradient reduction (``runtime/grad_reduce.py``):
config x mesh -> the reduction in effect, and each refusal's message."""

import jax
import pytest

import deeperspeed_tpu as dst
from deeperspeed_tpu.models import SimpleMLP
from deeperspeed_tpu.parallel.topology import MeshTopology

OVERLAP = {"comm": {"overlap": {"enabled": True}}}
AUTO = {"comm": {"overlap": {"enabled": True, "schedule": {"mode": "auto"}}}}
QGZ = {"comm": {"quantized": {"enabled": True}}}
ONEBIT = {"optimizer": {"type": "OneBitAdam",
                        "params": {"lr": 1e-2, "freeze_step": 2}}}
FP16 = {"fp16": {"enabled": True}}
QAT = {"compression_training": {"weight_quantization": {
    "shared_parameters": {"enabled": True},
    "different_groups": {"g": {"params": {"target_bits": 8},
                               "modules": ["*"]}}}}}
LTD = {"data_efficiency": {"enabled": True, "data_routing": {
    "enabled": True, "random_ltd": {"enabled": True}}}}


def zero(stage, **kw):
    return {"zero_optimization": {"stage": stage, **kw}}


def engine_for(mesh_axes, *overrides):
    mesh = MeshTopology(**mesh_axes) if "devices" not in mesh_axes else \
        MeshTopology(devices=jax.devices()[:mesh_axes["devices"]])
    cfg = {"train_micro_batch_size_per_gpu": 1,
           "gradient_accumulation_steps": 2,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "mesh": {"model_parallel_size": mesh.tp,
                    "sequence_parallel_size": mesh.sp,
                    "expert_parallel_size": mesh.ep}}
    for o in overrides:
        cfg.update(o)
    cfg["train_batch_size"] = 2 * mesh.data_parallel_size
    engine, _, _, _ = dst.initialize(model=SimpleMLP(hidden_dim=16),
                                     mesh=mesh, config=cfg)
    return engine


SELECTED = [
    # what every benchmark cell runs: no dp, nothing asked
    ("plain", {"devices": 1}, [], "per_microbatch"),
    ("plain_dp8", {}, [], "per_microbatch"),
    ("overlap", {}, [OVERLAP], "deferred"),
    ("overlap_zero3", {}, [OVERLAP, zero(3)], "deferred"),
    ("overlap_no_deferral", {}, [{"comm": {"overlap": {
        "enabled": True, "deferred_reduction": False}}}], "per_microbatch"),
    ("overlap_one_replica", {"devices": 1}, [OVERLAP], "per_microbatch"),
    ("overlap_tp_blocks", {"dp": 4, "tp": 2}, [OVERLAP], "per_microbatch"),
    ("overlap_qwz_blocks", {}, [OVERLAP, zero(
        3, zero_quantized_weights=True)], "per_microbatch"),
    ("auto", {}, [AUTO], "deferred"),
    ("auto_tp_planned", {"dp": 4, "tp": 2}, [AUTO], "per_microbatch"),
    ("onebit", {}, [ONEBIT], "onebit"),
    ("onebit_tp", {"dp": 4, "tp": 2}, [ONEBIT], "onebit"),
    ("onebit_one_replica", {"devices": 1}, [ONEBIT], "per_microbatch"),
    ("qgz", {}, [QGZ], "qgz"),
    ("qgz_keeps_its_loop_under_overlap", {}, [{"comm": {
        **QGZ["comm"], **OVERLAP["comm"]}}], "qgz"),
    ("qgz_by_zero_flag", {}, [zero(0, zero_quantized_gradients=True)], "qgz"),
    ("qgz_flag_ignored_above_stage0", {},
     [zero(2, zero_quantized_gradients=True)], "per_microbatch"),
    ("qgz_one_replica", {"devices": 1}, [QGZ], "per_microbatch"),
]


@pytest.mark.parametrize("mesh_axes,overrides,want", [r[1:] for r in SELECTED],
                         ids=[r[0] for r in SELECTED])
def test_selection(reset_mesh, mesh_axes, overrides, want):
    engine = engine_for(mesh_axes, *overrides)
    red = engine._reduction
    assert red.name == want
    # the state holds what the reduction carries, and nothing else of it
    assert ("onebit_error" in engine.state) == (want == "onebit")
    assert set(red.carries) <= set(engine._state_shardings)
    auto = any(o is AUTO for o in overrides)
    assert (red.plan is not None) == auto
    if auto:
        assert not red.plan.fallback and red.plan.hoist
        assert red.plan.grad_schedule == want and red.tag == red.plan.tag
    else:
        assert red.tag == want


REFUSED = [
    ("onebit_zero", {}, [ONEBIT, zero(2)], ValueError,
     "onebitadam requires zero stage 0"),
    ("onebit_fp16", {}, [ONEBIT, FP16], ValueError,
     "onebitadam supports fp32/bf16 only"),
    ("onebit_zshard", {"dp": 4, "zshard": 2}, [ONEBIT], ValueError,
     "ep/zshard must be 1"),
    ("onebit_sp_and_tp", {"dp": 2, "sp": 2, "tp": 2}, [ONEBIT],
     NotImplementedError, "onebitadam supports sp OR tp alongside dp"),
    ("onebit_qat", {}, [ONEBIT, QAT], NotImplementedError,
     r"onebitadam \+ compression_training is not supported"),
    ("onebit_ltd", {}, [ONEBIT, LTD], NotImplementedError,
     r"onebitadam \+ random-LTD is not supported"),
    ("qgz_and_onebit", {}, [ONEBIT, QGZ], ValueError,
     "mutually exclusive gradient compressions"),
    ("qgz_zero", {}, [QGZ, zero(1)], ValueError,
     "comm.quantized requires zero stage 0"),
    ("qgz_fp16", {}, [QGZ, FP16], ValueError,
     "comm.quantized supports fp32/bf16 only"),
    ("qgz_ep", {"dp": 4, "ep": 2}, [QGZ], ValueError,
     "comm.quantized: ep must be 1"),
    ("qgz_sp_and_tp", {"dp": 2, "sp": 2, "tp": 2}, [QGZ],
     NotImplementedError, "comm.quantized supports sp OR tp alongside dp"),
    ("qgz_qat", {}, [QGZ, QAT], NotImplementedError,
     r"comm.quantized \+ compression_training is not supported"),
]


@pytest.mark.parametrize("mesh_axes,overrides,error,message",
                         [r[1:] for r in REFUSED], ids=[r[0] for r in REFUSED])
def test_refusal(reset_mesh, mesh_axes, overrides, error, message):
    with pytest.raises(error, match=message):
        engine_for(mesh_axes, *overrides)

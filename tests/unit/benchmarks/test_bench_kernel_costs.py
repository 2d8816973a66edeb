"""Each kernel-cost function on hand-worked shapes."""

import pytest

from benchmarks import core

flash = core.load_kernel_cost("flash_attention")


def test_flash_forward_hand_worked():
    # one head, S = 4, D = 2: QK^T is 4x4x2 MACs = 64 FLOPs, PV the same;
    # causal halves it
    c = flash.forward(1, 1, 4, 2, itemsize=2, causal=False)
    assert c["flops"] == 128
    assert flash.forward(1, 1, 4, 2)["flops"] == 64
    # q k v o of 8 elements x 2 bytes + 4 float32 row statistics
    assert c["bytes"] == 4 * 16 + 16


def test_flash_backward_is_two_and_a_half_forwards():
    f, b = flash.forward(8, 16, 2048, 64), flash.backward(8, 16, 2048, 64)
    assert b["flops"] == 2.5 * f["flops"]
    assert f["flops"] == 4 * 8 * 16 * 2048 * 2048 * 64 / 2
    assert b["bytes"] > f["bytes"]


@pytest.mark.parametrize("remat,calls", [(False, 3.5), (True, 4.5)])
def test_flash_train_step_counts_the_recomputed_forward(remat, calls):
    f = flash.forward(8, 16, 2048, 64)
    step = flash.train_step(24, 8, 16, 2048, 64, remat)
    assert step["flops"] == pytest.approx(24 * calls * f["flops"])

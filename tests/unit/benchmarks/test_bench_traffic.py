"""The traffic generator is a pure function of the seed and honours every
clip; percentile and MFU arithmetic on hand-worked inputs."""

import math

import numpy as np
import pytest

from benchmarks import core, traffic_gen

PRETRAIN = core.load_json(core.BENCH_DIR + "/traffic/pretrain-2048-remat.json")
VOCAB = 50304


def test_token_batches_seeded_shifted_and_zipf():
    tb = traffic_gen.TokenBatches(PRETRAIN, VOCAB, 2**31 + 5)
    a, b = tb.batch(3), tb.batch(3)
    assert a["input_ids"].shape == (8, 2048)
    assert np.array_equal(a["input_ids"], b["input_ids"])
    assert np.array_equal(a["input_ids"][:, 1:], a["labels"][:, :-1])
    assert not np.array_equal(a["input_ids"], tb.batch(4)["input_ids"])
    ids = a["input_ids"]
    assert ids.min() >= 0 and ids.max() < VOCAB
    # Zipf(1.1): token 0 is the commonest; entropy well under ln V
    counts = np.bincount(ids.ravel(), minlength=VOCAB)
    assert counts.argmax() == 0
    p = counts[counts > 0] / counts.sum()
    assert -(p * np.log(p)).sum() < 0.8 * math.log(VOCAB)


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([10, 20, 30, 40], 90, 37.0),
    ([5], 95, 5.0),
    ([4, 1, 3, 2], 0, 1.0),
    ([4, 1, 3, 2], 100, 4.0),
])
def test_percentile_hand_worked(values, q, want):
    assert core.percentile(values, q) == pytest.approx(want)
    assert core.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


def test_mfu_and_roofline_hand_worked():
    # 100M non-embedding params, 10 layers of hidden 1000 at sequence 1000
    per_token = core.model_flops_per_token(100e6, 10, 1000, 1000)
    assert per_token == 6e8 + 1.2e8
    # 72k tokens/s on one chip with a 197e12 peak
    assert core.mfu_pct(per_token, 72_000, 1, 197e12) == pytest.approx(
        100 * 7.2e8 * 72_000 / 197e12)
    assert core.mfu_pct(per_token, 72_000, 4, 197e12) == pytest.approx(
        25 * 7.2e8 * 72_000 / 197e12)
    pct, bound = core.roofline_pct(2e12, 1e9, 0.02, 100e12, 1e12)
    assert (pct, bound) == (pytest.approx(100.0), "compute")
    pct, bound = core.roofline_pct(1e9, 8e9, 0.02, 100e12, 1e12)
    assert (pct, bound) == (pytest.approx(40.0), "memory")


def test_peaks_table_refuses_an_unknown_device():
    assert core.device_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        core.device_peaks("TPU v9 imaginary")


"""Host-RAM KV tier (``inference/v2/kv_tier.py``): spill-on-evict of
cache-only prefix blocks, restore-on-match, digest-verified integrity,
LRU capacity bounds, and prefetch issue-ahead -- with the spill->restore
round trip proven bit-exact at the payload level for fp32, int8 and fp8
(values + scales) pools.
"""

import numpy as np
import pytest

from deeperspeed_tpu.inference.v2 import (
    DSScheduler,
    HostKVTier,
    InferenceEngineV2,
    KVTierConfig,
)
from deeperspeed_tpu.inference.v2 import kv_tier as kv_tier_mod
from deeperspeed_tpu.inference.v2.ragged_manager import chain_key
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig


@pytest.fixture(scope="module")
def tiny_model():
    return GPTNeoX(GPTNeoXConfig.tiny(max_seq_len=64))


def _engine(tiny_model, num_blocks=16, kv_dtype="", tier=None, **sm_kw):
    cfg = {"dtype": "float32",
           "kv_cache": {"num_blocks": num_blocks, "block_size": 8,
                        "prefix_cache": True, "dtype": kv_dtype},
           "state_manager": {"max_context": 64, "max_decode_batch": 4,
                             **sm_kw}}
    if tier is not None:
        cfg["kv_tier"] = tier
    return InferenceEngineV2(tiny_model, config=cfg)


def _fake_tier(capacity=4, depth=2, verify=True):
    """Tier over synthetic read/write hooks -- unit tests that don't need
    a real engine behind the block ids."""
    store = {}

    def read(block):
        return [np.full((2, 3), float(block), np.float32),
                np.arange(6, dtype=np.float32).reshape(2, 3) + block]

    def write(block, payloads):
        store[block] = [np.asarray(p) for p in payloads]

    cfg = KVTierConfig(enabled=True, capacity_blocks=capacity,
                       prefetch_depth=depth, verify_digests=verify)
    return HostKVTier(cfg, read_block=read, write_block=write), store


# ------------------------------------------------------------- round trip
@pytest.mark.parametrize("kv_dtype", ["", "int8", "fp8"])
def test_spill_restore_roundtrip_bit_exact(tiny_model, kv_dtype):
    """Publish blocks, force-evict them all into the tier, and verify the
    host copies byte-match the pool; then a same-prefix rerun restores
    them and (a) the restored device blocks byte-match the originals,
    (b) the greedy continuation is identical to the pre-spill run."""
    eng = _engine(tiny_model, kv_dtype=kv_dtype,
                  tier={"enabled": True, "capacity_blocks": 64})
    sched = DSScheduler(eng)
    prompt = np.asarray(list(range(40, 60)), np.int32)   # 2 full blocks
    out1 = sched.generate([prompt], max_new_tokens=6)[0]

    cache = eng.state_manager.prefix_cache
    truth = {k: eng.export_kv_block(b)
             for k, b in list(cache._entries.items())}
    assert len(truth) >= 2
    assert cache.evict(len(truth)) == len(truth)
    tier = eng.host_tier
    assert tier.spills == len(truth) and len(tier) == len(truth)
    for key, want in truth.items():
        got, _digest, _nbytes = tier._entries[key]
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and np.array_equal(g, w)

    out2 = sched.generate([prompt], max_new_tokens=6)[0]
    assert np.array_equal(out1, out2)
    # generated tokens published a 3rd block, but match_prefix only walks
    # the PROMPT's full blocks (leaving >=1 recompute token) -- 2 restores
    assert tier.hits == (len(prompt) - 1) // 8
    assert tier.corrupt == 0
    # Byte-compare only the blocks the tier RESTORED (the prompt's full
    # blocks).  The 3rd block is never looked up in the tier: the rerun
    # RECOMPUTES it from a 4-token prefill tail where the first run
    # prefilled all 20 tokens at once, and XLA:CPU's matmul (jax 0.9) is not
    # shape-invariant to the last ulp -- so it is re-published, not restored,
    # and is held only to the token-level parity asserted above.
    restored, key = [], b""
    for idx in range((len(prompt) - 1) // 8):
        key = chain_key(key, prompt[idx * 8:(idx + 1) * 8])
        restored.append(key)
    assert set(restored) < set(truth)
    for key, want in truth.items():
        block = cache.lookup(key)          # restored / re-published
        assert block is not None
        if key in restored:
            for g, w in zip(eng.export_kv_block(block), want):
                assert np.array_equal(g, w)
    eng.state_manager.allocator.audit()


def test_corrupt_spill_is_a_plain_miss(tiny_model, monkeypatch):
    """A flipped byte on the restore path: digest verification rejects the
    entry, the walk recomputes, and the output still matches -- the tier
    can lose data but never corrupt a generation."""
    def _flip(key, payloads):
        out = [np.array(p) for p in payloads]
        out[0].view(np.uint8).reshape(-1)[0] ^= 0xFF
        return out
    eng = _engine(tiny_model, tier={"enabled": True})
    sched = DSScheduler(eng)
    prompt = np.asarray(list(range(100, 120)), np.int32)
    out1 = sched.generate([prompt], max_new_tokens=6)[0]
    cache = eng.state_manager.prefix_cache
    n = cache.evict(len(cache))
    assert n >= 2
    monkeypatch.setattr(kv_tier_mod, "_restore_seam", _flip)
    out2 = sched.generate([prompt], max_new_tokens=6)[0]
    assert np.array_equal(out1, out2)
    tier = eng.host_tier
    assert tier.corrupt >= 1 and tier.hits == 0
    eng.state_manager.allocator.audit()


# ------------------------------------------------------------ LRU + prefetch
def test_lru_capacity_bound_and_recency_refresh():
    tier, _ = _fake_tier(capacity=4)
    keys = [bytes([i]) for i in range(6)]
    for i, k in enumerate(keys):
        assert tier.spill(k, i)
    assert len(tier) == 4 and tier.evictions == 2
    assert keys[0] not in tier and keys[1] not in tier
    assert keys[5] in tier
    # re-spilling a resident key refreshes recency, never re-copies
    assert tier.spill(keys[2], 2) is False
    assert tier.spills == 6
    tier.spill(bytes([7]), 7)               # evicts keys[3], not keys[2]
    assert keys[2] in tier and keys[3] not in tier


def test_prefetch_issues_ahead_and_restore_consumes(monkeypatch):
    tier, store = _fake_tier(capacity=8, depth=2)
    keys = [bytes([i]) for i in range(4)]
    for i, k in enumerate(keys):
        tier.spill(k, i)
    assert tier.prefetch(keys) == 2         # bounded by prefetch_depth
    assert list(tier._inflight) == keys[:2]
    # a prefetched restore must not re-read host memory: corrupting the
    # seam now only affects NON-prefetched keys
    monkeypatch.setattr(kv_tier_mod, "_restore_seam",
                        lambda key, payloads: None)
    assert tier.restore(keys[0], 10) is True
    assert keys[0] not in tier._inflight
    assert np.array_equal(store[10][0], np.full((2, 3), 0.0, np.float32))
    assert tier.restore(keys[3], 11) is False    # seam dropped it
    assert tier.corrupt == 1 and keys[3] not in tier
    # prefetch stops at a chain gap (missing key breaks the walk)
    tier._inflight.clear()
    assert tier.prefetch([bytes([9]), keys[1]]) == 0


def test_restore_unknown_key_is_miss():
    tier, _ = _fake_tier()
    assert tier.restore(b"nope", 0) is False
    assert tier.misses == 1 and tier.hits == 0


# ---------------------------------------------------------------- churn
def test_audit_clean_after_spill_restore_churn(tiny_model):
    """Many shared-prefix prompts against a pool far smaller than the
    working set: spills and restores interleave with allocation pressure
    for several rounds, and the allocator's invariants hold throughout."""
    eng = _engine(tiny_model, num_blocks=12,
                  tier={"enabled": True, "capacity_blocks": 96})
    sched = DSScheduler(eng)
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, 256, size=20).astype(np.int32)
               for _ in range(10)]
    ref = _engine(tiny_model, num_blocks=64)
    want = DSScheduler(ref).generate(prompts, max_new_tokens=4)
    for _ in range(2):                      # second pass re-restores
        got = sched.generate(prompts, max_new_tokens=4)
        for g, w in zip(got, want):
            assert np.array_equal(g, w)
    tier = eng.host_tier
    assert tier.spills > 0 and tier.hits > 0 and tier.corrupt == 0
    assert len(tier) <= tier.capacity_blocks
    eng.state_manager.allocator.audit()

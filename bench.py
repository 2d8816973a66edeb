"""Benchmark: GPT-NeoX training throughput on the local accelerator.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.

Metric: model FLOPs utilization (MFU) of a Pythia-160M-architecture training
step (bf16, ZeRO-0 single chip) at seq 1024.  ``vs_baseline`` is the ratio to
the north-star target MFU of 0.45 (BASELINE.md: GPT-NeoX pretraining on TPU
at >= 0.45 MFU).

One process, in-process: a chip belongs to one process at a time, so nothing
here starts a child.  A regime that reports a time, a rate or an MFU needs
the chip and fails without one -- it never carries on on the CPU and never
replays a stored number.  Any regime that raises exits non-zero.
"""

import json
import os
import sys
import time

TARGET_MFU = 0.45


def _require_chip():
    """The accelerator, or an error: device metrics come only from a chip."""
    import jax

    from deeperspeed_tpu.accelerator import get_accelerator

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"this regime reports device metrics and found platform "
            f"{platform!r}; it needs a TPU and does not fall back to the CPU")
    return get_accelerator()


def run_bench():
    import jax
    import jax.numpy as jnp

    import deeperspeed_tpu as dst
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
    from deeperspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    # DST_CHAOS_INFER=1: the serving-resilience regime -- drives every
    # serving chaos scenario (nan_logits, oom_round, slow_step, flood,
    # spec_reject_storm) through the front end and reports pass/fail plus
    # the flood bench's goodput-under-deadline.  Chaos forces CPU internally: the regime is
    # a recovery contract, not a device throughput claim.
    if os.environ.get("DST_CHAOS_INFER") == "1":
        import shutil
        import tempfile

        from tools.chaos import SERVING_SCENARIOS, run_scenario

        workdir = tempfile.mkdtemp(prefix="dst_chaos_infer_")
        report, failed = {}, []
        for name in sorted(SERVING_SCENARIOS):
            try:
                report[name] = {"ok": True, "checks": run_scenario(
                    name, os.path.join(workdir, name))}
            except Exception as e:  # noqa: BLE001 - scenario verdicts
                failed.append(name)
                report[name] = {"ok": False,
                                "error": f"{type(e).__name__}: {e}"}
        shutil.rmtree(workdir, ignore_errors=True)
        print(json.dumps({
            "metric": "infer_chaos_cpu",
            "value": len(report) - len(failed),
            "unit": "scenarios_recovered",
            "scenarios": {k: v["ok"] for k, v in report.items()},
            "failed": failed,
            "device": "cpu",
        }))
        return 1 if failed else 0

    # DST_BENCH_INFER=1: the serving regime -- shared-prefix continuous
    # batching through DSScheduler/InferenceEngineV2 (prefix-cache TTFT,
    # decode tokens/s, one-dispatch rounds, int8 capacity).  Env var so it
    # like DST_BENCH_OVERLAP.  Reports tokens/s and TTFT: needs the chip.
    if os.environ.get("DST_BENCH_INFER") == "1":
        from tools.bench_inference import run_serving_bench

        _require_chip()
        print(json.dumps(run_serving_bench(on_tpu=True)))
        return 0

    # DST_BENCH_POOL=1: the multi-replica pool regime -- prefix-affinity
    # vs random routing on cached TTFT, plus kill-one-replica-mid-flood
    # goodput with transparent failover.  Pool routing is host-side, so
    # the regime is meaningful on CPU as well as TPU.
    if os.environ.get("DST_BENCH_POOL") == "1":
        from tools.bench_inference import run_pool_bench

        print(json.dumps(run_pool_bench()))
        return 0

    # DST_BENCH_DISAGG=1: the disaggregated-serving regime -- split
    # prefill/decode engines vs a colocated baseline (TTFT + delivered
    # tokens, early-issue KV-migration overlap fraction) plus the host
    # KV tier serving a working set 8x the HBM pool.  CPU-relative
    # comparisons, meaningful on any device.
    if os.environ.get("DST_BENCH_DISAGG") == "1":
        from tools.bench_inference import run_disagg_bench

        print(json.dumps(run_disagg_bench()))
        return 0

    # DST_BENCH_FABRIC=1: the cross-host fabric regime -- the identical
    # pool + disagg workloads served in-process vs over the loopback wire
    # path (serialized control plane, checksummed KV frames).  Reports
    # control-plane overhead and the migration overlap fraction surviving
    # framing; tokens must stay bit-exact.  Host-side, CPU-meaningful.
    if os.environ.get("DST_BENCH_FABRIC") == "1":
        from tools.bench_inference import run_fabric_bench

        print(json.dumps(run_fabric_bench()))
        return 0

    # DST_BENCH_FP8=1: the fp8 KV regime -- pool capacity vs fp32/int8 at
    # serving head dim 64 (the >= 3.5x acceptance bar), greedy parity
    # against the fp-path baseline on the pinned bench seed, and framed
    # KV-migration bytes over the loopback fabric (bf16 vs fp8 pools).
    # Byte ratios are geometry facts, so the regime is CPU-meaningful.
    if os.environ.get("DST_BENCH_FP8") == "1":
        from tools.bench_inference import run_fp8_bench

        print(json.dumps(run_fp8_bench()))
        return 0

    # DST_BENCH_TENANT=1: the multi-tenant + autoscaling regime -- one
    # tenant floods 10x while the others run nominal: per-tenant goodput
    # isolation ratio, token-bucket throttling with retry-after, the full
    # elastic cycle (warm standby scale-out, graceful scale-in, warm
    # readmit, zero executed flaps), and preemption hygiene (COW rollback
    # leaves the allocator audit clean).  Host-side, CPU-meaningful.
    if os.environ.get("DST_BENCH_TENANT") == "1":
        from tools.bench_inference import run_tenant_bench

        print(json.dumps(run_tenant_bench()))
        return 0

    # DST_BENCH_REPLAY=1: the trace-replay regime -- record a traced
    # serving run, parse its trace.jsonl back into a workload and replay
    # it open-loop against a loopback pool (tools/trace_replay.py); the
    # goodput ratio within tolerance of 1.0 is the claim that the trace
    # is a sufficient workload recording.  Host-side, CPU-meaningful.
    if os.environ.get("DST_BENCH_REPLAY") == "1":
        from tools.bench_inference import run_replay_bench

        report = run_replay_bench()
        print(json.dumps(report))
        return 0 if report["ok"] else 1

    # DST_BENCH_ROTATE=1: the rolling-deployment regime -- a full-pool
    # weight rotation (drain -> digest-verified stream -> warmup ->
    # canary -> readmit) under an open-loop Poisson flood: zero lost
    # requests, greedy parity per weight version, zero steady-state jit
    # misses, rotation wall time.  Host-side, CPU-meaningful.
    if os.environ.get("DST_BENCH_ROTATE") == "1":
        from tools.bench_inference import run_rotate_bench

        report = run_rotate_bench()
        print(json.dumps(report))
        return 0 if report["ok"] else 1

    # DST_BENCH_LONGCTX=1: the long-context serving regime -- decode-side
    # KV tier spill vs an all-resident baseline per context-ladder point
    # (TTFT, tokens/s, greedy bit-exact parity, HBM pinned to a constant
    # working set while context grows) plus sequence-parallel prefill
    # overlap across two prefill engines.  Host-side, CPU-meaningful.
    if os.environ.get("DST_BENCH_LONGCTX") == "1":
        from tools.bench_inference import run_longctx_bench

        report = run_longctx_bench()
        print(json.dumps(report))
        return 0 if report["ok"] else 1

    # DST_BENCH_MEMPLAN=1: the memory-planning regime -- planned vs static
    # vs no-offload chunk streaming under a synthetic HBM budget that
    # static ZeRO-3 residency cannot satisfy: per-variant step time,
    # resident-set bytes, exposed-vs-overlapped transfer estimate, and the
    # acceptance triplet (static raises / bit-exact / peak within bound).
    # Bit-exactness and the residency ledger are CPU-meaningful; the
    # throughput ratio needs a pod slice.
    if os.environ.get("DST_BENCH_MEMPLAN") == "1":
        from tools.bench_collectives import run_memplan_bench

        report = run_memplan_bench()
        return 0 if report and report["ok"] else 1

    # DST_BENCH_SPEC=1: the speculative-decoding regime -- spec off vs
    # n-gram self-speculation on over the same weights: tokens/s/seq
    # speedup, accept rate, tokens/round, bit-exact greedy parity, zero
    # steady-state jit cache misses.  Reports tokens/s: needs the chip.
    if os.environ.get("DST_BENCH_SPEC") == "1":
        from tools.bench_inference import run_spec_bench

        _require_chip()
        print(json.dumps(run_spec_bench(on_tpu=True)))
        return 0

    accel = _require_chip()
    seq = 1024
    # b16 sweeps best on v5e (b8 under-fills the MXU, b32 plateaus)
    batch = 16
    cfg = GPTNeoXConfig.pythia_160m(dtype=jnp.bfloat16, max_seq_len=seq)
    model = GPTNeoX(cfg)

    config = {
        "train_batch_size": batch,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 1000000,
    }
    # DST_BENCH_OVERLAP=1: the latency-hiding regime -- gas=2 deferred +
    # bucketed grad reduction, prefetching input, async-collective XLA
    # flags
    overlap = os.environ.get("DST_BENCH_OVERLAP") == "1"
    if overlap:
        config["gradient_accumulation_steps"] = 2
        config["train_batch_size"] = batch * 2
        config["comm"] = {"overlap": {
            "enabled": True, "bucket_mb": 4.0, "prefetch_depth": 2,
            "xla_latency_hiding": True}}
    engine, _, _, _ = dst.initialize(model=model, config=config)
    data = model.example_batch(batch_size=batch, seq_len=seq)

    # warmup / compile -- force completion so warmup execution cannot leak
    # into the timed window (dispatch is async; effects_barrier alone does
    # not drain compute)
    for _ in range(2):
        loss = engine.train_batch(batch=data)
    float(loss)

    n_steps = 20
    t0 = time.time()
    for _ in range(n_steps):
        loss = engine.train_batch(batch=data)
    loss = float(loss)  # forces completion
    dt = time.time() - t0

    tokens_per_step = config["train_batch_size"] * seq
    tokens_per_sec = tokens_per_step * n_steps / dt

    # fwd+bwd FLOPs: 6 * n_params * tokens + attention term.  The input
    # embedding is a gather (0 FLOPs) -- excluded, else MFU is inflated
    # (matches model.flops_per_token / the flops profiler).
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(
        engine.state["master_params"]))
    n_params -= cfg.vocab_size * cfg.hidden_size
    attn_flops_per_token = 12 * cfg.num_layers * cfg.hidden_size * seq
    flops_per_token = 6 * n_params + attn_flops_per_token
    model_flops_per_sec = flops_per_token * tokens_per_sec
    peak = accel.peak_flops_per_device() * max(1, accel.device_count())
    mfu = model_flops_per_sec / peak if peak else 0.0

    print(json.dumps({
        "metric": "pythia160m_train_mfu" + ("_overlap" if overlap else ""),
        "value": round(mfu, 4),
        "unit": "mfu",
        "vs_baseline": round(mfu / TARGET_MFU, 4),
        "tokens_per_sec_per_chip": round(tokens_per_sec / max(1, accel.device_count()), 1),
        "loss": round(loss, 4),
        "n_params": n_params,
        "seq_len": seq,
        "device": accel.name(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": accel.device_count(),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(run_bench())

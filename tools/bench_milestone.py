"""End-to-end train-step MFU at milestone-ish shapes (VERDICT r3 task 2).

Validates PROFILE.md's "bigger shapes sit closer to the matmul ceiling"
claim with FULL train steps (real remat/optimizer/epilogue mix), not
standalone kernels: same engine path and same timing methodology as
``bench.py`` (the loss readback at the end of the window waits for the
device).

Usage (real TPU):
    python tools/bench_milestone.py                      # 160m@1024 + 410m@2048
    python tools/bench_milestone.py --models pythia_410m --seq 2048 --offload

Prints one JSON line per config; record the table in PROFILE.md.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULTS = [
    # (preset, seq, batch, gas) — batch fills the MXU within v5e HBM; gas
    # holds the microbatch small enough that the fp32 logits buffer
    # ([mb, S, 50k] ~ 0.8 GB at mb=2, S=2048) fits during compile
    ("pythia_160m", 1024, 16, 1),
    ("pythia_410m", 2048, 8, 4),
]


def bench_one(preset, seq, batch, gas=1, offload=False, host_update=False,
              steps=10, wire_dtype=None):
    import jax
    import jax.numpy as jnp

    import deeperspeed_tpu as dst
    from deeperspeed_tpu.accelerator import get_accelerator
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    accel = get_accelerator()
    cfg = getattr(GPTNeoXConfig, preset)(dtype=jnp.bfloat16, max_seq_len=seq)
    model = GPTNeoX(cfg)
    if host_update:
        # native CPU Adam: optimizer state never touches the device --
        # the mode for state > HBM (see PROFILE.md 1.4B analysis)
        off = {"device": "cpu", "host_update": True}
        if wire_dtype:
            off["wire_dtype"] = wire_dtype
        zero = {"stage": 0, "offload_optimizer": off}
    elif offload:
        zero = {"stage": 2, "offload_optimizer": {"device": "cpu"}}
    else:
        zero = {"stage": 0}
    config = {
        "train_batch_size": batch,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "zero_optimization": zero,
        "steps_per_print": 1000000,
    }
    engine, _, _, _ = dst.initialize(model=model, config=config)
    data = model.example_batch(batch_size=batch, seq_len=seq)

    for _ in range(2):
        loss = engine.train_batch(batch=data)
    float(loss)  # drain warmup

    t0 = time.time()
    for _ in range(steps):
        loss = engine.train_batch(batch=data)
    loss = float(loss)
    dt = time.time() - t0

    tokens_per_sec = batch * seq * steps / dt
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(
        engine.state["master_params"]))
    n_params_flops = n_params - cfg.vocab_size * cfg.hidden_size
    flops_per_token = (6 * n_params_flops
                       + 12 * cfg.num_layers * cfg.hidden_size * seq)
    peak = accel.peak_flops_per_device() * max(1, accel.device_count())
    mfu = flops_per_token * tokens_per_sec / peak if peak else 0.0
    result = {
        "model": preset, "seq": seq, "batch": batch, "gas": gas,
        "offload": offload, "host_update": host_update,
        # only meaningful when the host-update path actually ran
        "wire_dtype": wire_dtype if host_update else None,
        "step_ms": round(1e3 * dt / steps, 1),
        "tokens_per_sec": round(tokens_per_sec, 1),
        "mfu": round(mfu, 4),
        "n_params_m": round(n_params / 1e6, 1),
        "device": accel.name(),
        "loss": round(loss, 4),
    }
    print(json.dumps(result), flush=True)
    engine.destroy()
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", nargs="*", default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--offload", action="store_true")
    ap.add_argument("--host-update", action="store_true")
    ap.add_argument("--wire-dtype", default=None,
                    help="host_update grads wire dtype (e.g. bf16)")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--gas", type=int, default=1)
    args = ap.parse_args()
    if args.models:
        runs = [(m, args.seq or 2048, args.batch or 8, args.gas)
                for m in args.models]
    else:
        runs = DEFAULTS
    for preset, seq, batch, gas in runs:
        try:
            bench_one(preset, seq, batch, gas=gas, offload=args.offload,
                      host_update=args.host_update, steps=args.steps,
                      wire_dtype=args.wire_dtype)
        except Exception as e:  # noqa: BLE001 — report and continue
            print(json.dumps({"model": preset, "seq": seq, "batch": batch,
                              "gas": gas,
                              "error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)


if __name__ == "__main__":
    main()

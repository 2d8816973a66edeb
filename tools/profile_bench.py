"""Decompose the Pythia-160M bench step on the real chip (VERDICT r2 #1).

Times each phase of the train step separately (full step, forward,
forward+backward, head+CE epilogue, optimizer update) and dumps the compiled
step's XLA cost analysis, so the residual between measured MFU and the 0.45
north star can be attributed to specific ops rather than guessed at.

Timing methodology: ``tputime.timed`` / ``timed_inner``, each ended by
``block_until_ready``.  Phase timings via ``timed`` include per-dispatch host
overhead (the same for every phase); kernel-level numbers belong in
profile_attn.py which amortizes dispatch with an in-jit loop.

Usage: python tools/profile_bench.py — prints one JSON line per measurement.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

from tputime import emit, timed, timed_inner


def main():
    import deeperspeed_tpu as dst
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    batch, seq = 16, 1024
    cfg = GPTNeoXConfig.pythia_160m(dtype=jnp.bfloat16, max_seq_len=seq)
    model = GPTNeoX(cfg)
    config = {
        "train_batch_size": batch,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 1000000,
    }
    engine, _, _, _ = dst.initialize(model=model, config=config)
    data = model.example_batch(batch_size=batch, seq_len=seq)
    stacked = engine._stack_microbatches(data)
    rng = jax.random.PRNGKey(0)

    # ---- full train step (donates state; train_batch threads it back)
    full = timed(lambda: engine.train_batch(batch=data), n=20)
    emit("full_step", full)

    # cost analysis of the whole compiled step
    step_fn = engine._get_train_step(None)
    try:
        ca = step_fn.lower(engine.state, stacked, rng).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        flops = float(ca.get("flops", 0.0))
        bytes_acc = float(ca.get("bytes accessed", 0.0))
        emit("cost_analysis", 0.0, flops=flops, bytes_accessed=bytes_acc,
             flops_time_at_peak_ms=round(flops / 197e12 * 1e3, 3),
             hbm_time_at_peak_ms=round(bytes_acc / 819e9 * 1e3, 3))
    except Exception as e:  # noqa: BLE001
        emit("cost_analysis_failed", 0.0, error=str(e)[:200])

    master = engine.state["master_params"]
    loss_fn = engine._loss_fn
    mb = jax.tree_util.tree_map(lambda x: x[0], stacked)

    # ---- forward only (loss), bf16 params like the real step
    params = jax.jit(lambda m: engine.precision.cast_for_compute(
        m, engine._no_cast))(master)
    t_fwd = timed(jax.jit(lambda p, b: loss_fn(p, b, None)), params, mb)
    emit("forward_loss", t_fwd)

    # ---- forward + backward (value_and_grad wrt bf16 params)
    fb = jax.jit(lambda p, b: jax.value_and_grad(
        lambda pp: loss_fn(pp, b, None))(p))
    t_fb = timed(fb, params, mb)
    emit("forward_backward", t_fb)

    # ---- head + CE epilogue alone (fwd+bwd) at bench shape
    h = jnp.zeros((batch, seq, cfg.hidden_size), jnp.bfloat16)
    w_head = jnp.zeros((cfg.hidden_size, cfg.vocab_size), jnp.bfloat16)
    labels = mb["labels"]

    def head_ce(hh, ww, ll):
        logits = (hh @ ww).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ll[..., None], axis=-1)[..., 0]
        return -jnp.mean(gold - lse)

    hc = jax.jit(lambda hh, ww, ll: jax.value_and_grad(
        head_ce, argnums=(0, 1))(hh, ww, ll))
    t_head = timed(hc, h, w_head, labels)
    emit("head_ce_fwd_bwd", t_head)

    # ---- optimizer update alone (in-jit loop: amortizes dispatch)
    def adam_chain(carry):
        p, o = carry
        g = jax.tree_util.tree_map(
            lambda x: jnp.full(x.shape, 1e-4, jnp.float32), p)
        upd, new_o = engine.tx.update(g, o, p)
        new_p = jax.tree_util.tree_map(lambda a, u: a - 1e-4 * u, p, upd)
        return (new_p, new_o)

    t_adam = timed_inner(adam_chain, (master, engine.state["opt_state"]),
                         iters=20)
    emit("adam_update", t_adam)

    emit("summary", full,
         fwd_ms=round(t_fwd * 1e3, 2), fb_ms=round(t_fb * 1e3, 2),
         head_ce_ms=round(t_head * 1e3, 2), adam_ms=round(t_adam * 1e3, 2))


if __name__ == "__main__":
    main()

"""Runner for training traffic: ``dst.initialize`` -> ``engine.train_batch``
on a fresh seeded batch every step.

``setup`` makes the weights on the device from the seed, builds the engine
through the normal entry point, warms the one step program, and keeps on the
host what the first step left (moments and masters of the sampled leaves).
``window`` trains whole steps for the given seconds.  ``check`` frees the
device and only then runs the plain reference: the first batch's loss,
per-token log-probabilities, gradient and Adam step against the program's.
"""

import gc
import time

import numpy as np

from benchmarks import core, traffic_gen
from benchmarks.reference import gpt_neox_ref as ref

#: Limits of the output comparison; the readings are in PERF.md §2.
#: RMS over one sequence's tokens of (program log-prob - reference log-prob):
#: sound runs read at most 0.0076, the fp8 control at least 0.052 (34 and 30
#: seeds over both cells, on the chip); the limit is their geometric mean
LOGPROB_RMS_LIMIT = 0.02
#: |engine's first-step loss - reference loss on the same batch and weights|:
#: sound runs read at most 0.0006; a mean over 16k tokens, so the control
#: does not separate (0.0002-0.011) -- it guards the engine's loss path
FIRST_LOSS_LIMIT = 0.003
#: The step program's own backward and optimizer passes have a limit per
#: cell, in ``limits/<cell>.json`` (written by ``calibrate.py --write`` from
#: readings on the chip at the cell's size; ``limits_from`` is the rule).
#: ``grad_rel_err``: relative distance of the clipped gradient the step fed to
#: Adam (first moment / (1 - b1)) from the reference's, over the sampled
#: leaves.  ``adam_update_rel_err``: relative distance of its change of the
#: float32 masters from the reference's first Adam step, over the elements
#: whose gradient is at least their leaf's RMS (their sign is then certain).
#: number -> the control that bounds its limit from above
CONTROL_OF = {"grad_rel_err": "control_fp8",
              "adam_update_rel_err": "control_bf16_masters"}


def program_model(config, traffic):
    """The program's model object for a configuration file."""
    import jax.numpy as jnp

    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    if config["intermediate_size"] != 4 * config["hidden_size"]:
        raise ValueError("the program's GPT-NeoX has a 4x MLP only")
    return GPTNeoX(GPTNeoXConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        max_seq_len=int(traffic.get("seq_len",
                                    config["max_position_embeddings"])),
        rotary_pct=config["rotary_pct"],
        rotary_emb_base=config["rotary_emb_base"],
        use_parallel_residual=config["use_parallel_residual"],
        layernorm_eps=config["layer_norm_eps"],
        dtype=getattr(jnp, traffic.get("dtype", "bfloat16")),
        remat=bool(traffic.get("remat", False))))


def cell_mesh(ctx, **axes):
    """A mesh over the chips the cell asks for, whatever the host holds."""
    import jax

    from deeperspeed_tpu.parallel.topology import MeshTopology

    return MeshTopology(devices=jax.devices()[:ctx.cell["chips"]], **axes)


def engine_config(traffic, seed):
    micro, gas = int(traffic["micro_batch"]), int(traffic.get("grad_accum", 1))
    opt = traffic["optimizer"]
    return {
        "train_batch_size": micro * gas,
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": opt["type"], "params": {
            "lr": opt["lr"], "betas": opt["betas"], "eps": opt["eps"]}},
        "bf16": {"enabled": traffic.get("dtype", "bfloat16") == "bfloat16"},
        "gradient_clipping": traffic["clip"],
        "zero_optimization": {"stage": int(traffic.get("zero_stage", 0))},
        "steps_per_print": 10 ** 9,
        "seed": int(seed) & 0x7FFFFFFF,
    }


def program_logprobs(model, params, ids, labels):
    """Per-token log-probabilities of ``labels`` from the program's own
    forward in its compute dtype -> [B, S] float32."""
    import jax
    import jax.numpy as jnp

    def fwd(p, x, y):
        lg = model.apply({"params": p}, x).astype(jnp.float32)
        return (jnp.take_along_axis(lg, y[..., None], axis=-1)[..., 0]
                - jax.nn.logsumexp(lg, axis=-1))

    return jax.jit(fwd)(params, ids, labels)


def cast_for_compute(params, traffic):
    """Seeded masters in the type the program computes in (matrices only)."""
    import jax
    import jax.numpy as jnp

    dtype = getattr(jnp, traffic.get("dtype", "bfloat16"))
    return jax.jit(lambda p: jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if a.ndim > 1 else a, p))(params)


# ---------------------------------------------- what the first step left
def sampled_tops(cfg):
    """Top-level names of the leaves whose first-step gradient and update
    are compared: both embedding tables, the final norm and every parameter
    of the first, middle and last layer.  The first layer's gradient has
    been through every layer's backward pass."""
    last = cfg["num_hidden_layers"] - 1
    return {"embed_in", "embed_out", "final_layer_norm"} | {
        f"layers_{i}" for i in (0, last // 2, last)}


def sample_leaves(tree, tops, dtype=np.float32):
    """Host copies of the sampled leaves -> {path tuple: array}."""
    import jax

    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        keys = tuple(getattr(k, "key", getattr(k, "name", None)) for k in path)
        if keys[0] in tops:
            out[keys] = np.asarray(leaf).astype(dtype, copy=False)
    return out


def engine_first_step(engine, cfg):
    """After the engine's first step from the seeded weights: Adam's first
    moment and the float32 masters of the sampled leaves, on the host."""
    import jax

    tops = sampled_tops(cfg)
    adam = [s for s in jax.tree_util.tree_leaves(
        engine.state["opt_state"], is_leaf=lambda s: hasattr(s, "mu"))
        if hasattr(s, "mu")]
    return {"moment": sample_leaves(adam[0].mu, tops),
            "master": sample_leaves(engine.state["master_params"], tops),
            "grad_norm": engine.get_global_grad_norm()}


def plain_first_step(cfg, traffic, params, grads, master_dtype="float32"):
    """The same from the plain reference: ``grads`` (the reference's, or a
    control's) clipped by global norm, one Adam step on ``params``.
    ``master_dtype`` "bfloat16" is the optimizer's control: the updated
    masters kept in bfloat16."""
    import jax
    import jax.numpy as jnp

    opt, tops = traffic["optimizer"], sorted(sampled_tops(cfg))
    b1, b2 = opt["betas"]
    norm = jax.jit(ref.global_norm)(grads)

    def step(p, g, norm):
        scale = ref.clip_scale(norm, float(traffic["clip"]))
        g = jax.tree_util.tree_map(lambda x: x * scale, g)
        return g, ref.adam_first_step(p, g, float(opt["lr"]), b1, b2,
                                      float(opt["eps"]))

    clipped, new = jax.jit(step)({k: params[k] for k in tops},
                                {k: grads[k] for k in tops}, norm)
    master = sample_leaves(new, tops, getattr(jnp, master_dtype))
    return {"moment": {k: (1 - b1) * g for k, g in
                       sample_leaves(clipped, tops).items()},
            "master": {k: v.astype(np.float32) for k, v in master.items()},
            "grad_norm": float(norm)}


def compare_first_step(got, want, init):
    """``got`` (the program's first step, or a control's) against ``want``
    (the reference's); ``init`` holds the seeded weights of the same leaves.
    -> the two numbers the limits stand on, and the gradient norms."""
    sq = {"g_diff": 0.0, "g": 0.0, "d_diff": 0.0, "d": 0.0}
    for path, want_m in want["moment"].items():
        diff = got["moment"][path] - want_m
        sq["g_diff"] += float(np.sum(diff * diff, dtype=np.float64))
        sq["g"] += float(np.sum(want_m * want_m, dtype=np.float64))
        sure = np.abs(want_m) >= np.sqrt(np.mean(want_m * want_m,
                                                 dtype=np.float64))
        moved = (want["master"][path] - init[path])[sure]
        diff = (got["master"][path] - init[path])[sure] - moved
        sq["d_diff"] += float(np.sum(diff * diff, dtype=np.float64))
        sq["d"] += float(np.sum(moved * moved, dtype=np.float64))
    return {"grad_rel_err": (sq["g_diff"] / sq["g"]) ** 0.5,
            "adam_update_rel_err": (sq["d_diff"] / sq["d"]) ** 0.5,
            "grad_norm": got["grad_norm"],
            "grad_norm_reference": want["grad_norm"]}


def free_device():
    """Collect what is no longer referenced (the engine, once dropped, goes
    whole: no run on the chip found an array of it left) -> bytes that are
    still on the device when the reference starts again from the seed."""
    import jax

    gc.collect()
    return sum(a.nbytes for a in jax.live_arrays())


def start_engine(ctx, seed):
    """Seeded weights -> the engine, after its first step on the seed's
    first batch.  -> (engine, batches, first loss, what the step left)."""
    import deeperspeed_tpu as dst

    cfg, traffic = ctx.config, ctx.traffic
    batches = traffic_gen.TokenBatches(traffic, cfg["vocab_size"], seed)
    params = ref.init_params(cfg, seed)
    engine, _, _, _ = dst.initialize(
        model=program_model(cfg, traffic), model_parameters=params,
        mesh=cell_mesh(ctx), config=engine_config(traffic, seed))
    del params
    first_loss = float(engine.train_batch(batch=batches.batch(0)))
    return engine, batches, first_loss, engine_first_step(engine, cfg)


def setup(ctx):
    # warm the one step program: step 0 gives the first loss and what the
    # check compares, step 1 shows that nothing more compiles
    engine, batches, first_loss, left = start_engine(ctx, ctx.seed)
    c0 = ctx.compiles.count
    warm_loss = float(engine.train_batch(batch=batches.batch(1)))
    ctx.log("warmup", first_loss=first_loss, second_loss=warm_loss,
            grad_norm=left["grad_norm"],
            compiles_in_second_step=ctx.compiles.count - c0)
    rows, seq = batches.shape[0], batches.shape[1] - 1
    return {"engine": engine, "batches": batches, "next_step": 2,
            "first_loss": first_loss, "first_step": left,
            "tokens_per_step": rows * seq}


def window(ctx, state):
    import jax

    engine, batches = state["engine"], state["batches"]
    step = state["next_step"]
    losses, ready_at = [], []
    with ctx.spans.span("batch_prep"):
        batch = batches.batch(step)
    t0 = time.perf_counter()
    deadline = t0 + ctx.seconds
    pending = None
    while True:
        ctx.trace.tick()
        with ctx.spans.span("train_batch"):
            loss = engine.train_batch(batch=batch)
        step += 1
        with ctx.spans.span("batch_prep"):
            batch = batches.batch(step)
        if pending is not None:
            # one step in flight: wait for the one before the one just sent
            with ctx.spans.span("wait_step"):
                jax.block_until_ready(pending)
            ready_at.append(time.perf_counter())
        losses.append(loss)
        pending = loss
        if time.perf_counter() >= deadline:
            break
    jax.block_until_ready(pending)
    t1 = time.perf_counter()
    ready_at.append(t1)
    ctx.trace.stop()
    losses = [float(x) for x in losses]
    steps = len(losses)
    tokens = steps * state["tokens_per_step"]
    chips = ctx.cell["chips"]
    record = {
        "t0": t0, "t1": t1, "attempted": steps,
        "failed": sum(1 for x in losses if not np.isfinite(x)),
        "losses": losses, "step_ready_at": ready_at,
        "tokens": tokens, "chips": chips,
        "seq_len": int(ctx.traffic["seq_len"]),
        "micro_batch": int(ctx.traffic["micro_batch"]),
        "remat": bool(ctx.traffic.get("remat", False)),
        "end_to_end": {
            "train_tokens_per_s_chip": tokens / (t1 - t0) / chips},
    }
    record["counts"] = {"steps": steps, "tokens": tokens}
    edges = [t0] + ready_at
    gaps = np.diff(edges)
    # the slowest step, where the host spent it, and how many steps took over
    # twice the median say whether a low rate is a slower step or a stall
    slow = int(gaps.argmax())
    timing = {
        "seconds": t1 - t0,
        "step_ms_median": 1e3 * core.median(gaps),
        "step_ms_max": 1e3 * float(gaps[slow]),
        "slowest_step": slow,
        "slowest_step_host_ms": {
            k: round(1e3 * v, 1) for k, v in ctx.spans.seconds_by_name(
                edges[slow], edges[slow + 1]).items()},
        "steps_over_twice_median": int(
            (gaps > 2 * core.median(gaps)).sum()),
        # where a stall fell (the first gap holds two steps, the last none)
        "late_steps": [[int(i), round(1e3 * float(gaps[i]), 1)]
                       for i in range(1, len(gaps) - 1)
                       if gaps[i] > 1.25 * core.median(gaps)][:8]}
    # a CPU rehearsal reports counts only, no time
    ctx.log("window", steps=steps, first_loss=losses[0],
            last_loss=losses[-1], **({} if ctx.rehearse else timing))
    return record


def compare_logprobs(prog_lp, ref_lp):
    """The number the precision check stands on: RMS difference of one
    sequence's per-token log-probabilities."""
    d = np.asarray(prog_lp, np.float64) - np.asarray(ref_lp, np.float64)
    return float(np.sqrt(np.mean(d * d)))


def against_reference(ctx, seed, first_loss, left, controls=False):
    """The program's first step against the plain reference, on a device the
    program has left.  ``controls`` adds what the controls read in the
    program's place: the reference in fp8 (forward and backward), and its
    Adam step with the masters kept in bfloat16.  -> dict of numbers."""
    import jax.numpy as jnp

    cfg, traffic = ctx.config, ctx.traffic
    first = traffic_gen.TokenBatches(traffic, cfg["vocab_size"], seed).batch(0)
    ids, labels = jnp.asarray(first["input_ids"]), jnp.asarray(first["labels"])
    params = ref.init_params(cfg, seed)
    prog_lp = np.asarray(program_logprobs(
        program_model(cfg, traffic), cast_for_compute(params, traffic),
        ids[:1], labels[:1]))[0]
    ref_loss, grads, ref_lp = ref.loss_and_grads(params, cfg, ids, labels)
    ref_lp = np.asarray(ref_lp)
    init = sample_leaves(params, sampled_tops(cfg))
    want = plain_first_step(cfg, traffic, params, grads)
    out = {"program": dict(
        compare_first_step(left, want, init),
        logprob_rms=compare_logprobs(prog_lp, ref_lp),
        first_loss_abs_diff=abs(first_loss - float(ref_loss)))}
    if controls:
        low = plain_first_step(cfg, traffic, params, grads, "bfloat16")
        out["control_bf16_masters"] = {"adam_update_rel_err":
                                       compare_first_step(low, want, init)[
                                           "adam_update_rel_err"]}
        del grads, low
        ctl_loss, grads, ctl_lp = ref.loss_and_grads(params, cfg, ids, labels,
                                                     "fp8")
        low = plain_first_step(cfg, traffic, params, grads)
        out["control_fp8"] = {
            "grad_rel_err": compare_first_step(low, want, init)[
                "grad_rel_err"],
            "logprob_rms": compare_logprobs(np.asarray(ctl_lp), ref_lp),
            "first_loss_abs_diff": abs(float(ctl_loss) - float(ref_loss))}
    return out


def calibrate(ctx, seeds, control_seeds=3):
    """Readings for the limits, many seeds in one process: the program's
    first step, and on the first ``control_seeds`` seeds the controls,
    against the plain reference.  One JSON line per seed -> the readings."""
    readings = []
    for n, seed in enumerate(seeds):
        engine, _, first_loss, left = start_engine(ctx, seed)
        del engine
        live = free_device()
        readings.append(dict(seed=seed, **against_reference(
            ctx, seed, first_loss, left, controls=n < control_seeds)))
        ctx.log("calibrate", live_bytes_after_engine=live, **readings[-1])
    return readings


def limits_from(readings):
    """A cell's limits from its readings: the geometric mean of the largest
    the sound runs gave and the smallest its control gave, so with the same
    room on both sides; refused where the control reads under three times
    the sound runs.  The carried limits must hold in every reading too."""
    out = {}
    for number, control in CONTROL_OF.items():
        sound = [r["program"][number] for r in readings]
        low = [r[control][number] for r in readings if control in r]
        if len(low) < 3 or min(low) < 3 * max(sound):
            raise SystemExit(f"{number}: control {low} does not stand three "
                             f"times clear of the sound runs {sound}")
        out[number] = {"limit": (max(sound) * min(low)) ** 0.5,
                       "sound_largest": max(sound), "sound_seeds": len(sound),
                       "control": control, "control_smallest": min(low),
                       "control_seeds": len(low)}
    for r in readings:
        if (r["program"]["logprob_rms"] > LOGPROB_RMS_LIMIT
                or r["program"]["first_loss_abs_diff"] > FIRST_LOSS_LIMIT):
            raise SystemExit(f"a carried limit does not hold in {r}")
    return out


def check(ctx, state, record):
    losses = record["losses"]
    k = max(1, min(3, len(losses) // 2))
    head, tail = core.median(losses[:k]), core.median(losses[-k:])
    # the engine gives way to the reference's float32 weights and gradient
    del state["engine"]
    ctx.log("freed", live_bytes_after_engine=free_device())
    got = against_reference(ctx, ctx.seed, state["first_loss"],
                            state["first_step"])["program"]
    ctx.log("reference", **got)
    limits = core.load_limits(ctx.cell["name"], ctx.rehearse)
    return [
        core.check("grad_rel_err_vs_reference", got["grad_rel_err"],
                   limits["grad_rel_err"]["limit"]),
        core.check("adam_update_rel_err_vs_reference",
                   got["adam_update_rel_err"],
                   limits["adam_update_rel_err"]["limit"]),
        core.check("logprob_rms_vs_reference", got["logprob_rms"],
                   LOGPROB_RMS_LIMIT),
        core.check("first_loss_abs_diff_vs_reference",
                   got["first_loss_abs_diff"], FIRST_LOSS_LIMIT),
        core.check("nonfinite_losses", record["failed"], 0),
        core.check("loss_fall_over_window", head - tail, 0.0,
                   ok=len(losses) < 2 or tail < head, better="higher"),
    ]

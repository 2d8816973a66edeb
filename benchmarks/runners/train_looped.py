"""Runner for training a looped language model (Ouro: one stack of layers run
``total_ut_steps`` times on shared weights, an exit after every pass, a
learned gate over the exits): ``dst.initialize`` -> ``engine.train_batch`` on
a fresh seeded batch every step, exactly as ``runners/train.py`` runs the
Pythia cells.

What is model-free comes from that runner (the engine's JSON config, the
mesh, the timed window, the comparison of a first step's gradient and
update); the model, the leaves that are sampled, the plain reference
(``reference/ouro_ref.py``) and the check are this file's.  ``check``
compares, at the timed sizes, what the timed engine's first step left
(clipped gradient, change of the float32 masters) and the program's forward
on the seeded weights (per-token log-probabilities of EVERY exit, the exit
distribution) with the reference.

The CPU rehearsal's limits are in ``limits/rehearsal-ouro.json`` (never
``calibrate.py --rehearse --write`` for this cell: that writes
``limits/rehearsal.json``, the Pythia rehearsal's); it is rewritten by
``python3 benchmarks/runners/train_looped.py``.
"""

import os
import sys

import numpy as np

if __name__ == "__main__":      # the rehearsal's limits, see the foot
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks import core, traffic_gen
from benchmarks.reference import ouro_ref as ref
# a program that has no looped model fails here, as the runner is loaded:
# before anything is put on the device
from deeperspeed_tpu.models.ouro import Ouro, OuroConfig

train = core.load_runner("train")
window = train.window

#: Limits of the output comparison kept here; those of the gradient and of
#: the update are per cell in ``limits/<cell>.json`` (``limits_from``).  All
#: were set by one rule from readings on the chip at the cell's size (PERF.md
#: section 2): the geometric mean of the largest a sound run gave and the
#: smallest the fp8 control gave, the control three times clear or more.
#: The largest over the T exits of the RMS over one sequence's tokens of
#: (program log-prob - reference log-prob) of the label: sound runs read
#: 0.020-0.031 (32 layer applications deep in bfloat16), the control
#: 0.30-0.39.
EXIT_LOGPROB_RMS_LIMIT = 0.1
#: The largest |p^t_i(program) - p^t_i(reference)| over one sequence's tokens
#: and the T exits: sound runs read 0.008-0.013, the control 0.084-0.094.
EXIT_SHARE_ABS_LIMIT = 0.033
#: |engine's first-step loss - reference loss on the same batch|: a mean over
#: the step's 16k tokens that the precision hardly moves (sound runs read at
#: most 0.0018, the control 0.0006-0.028: it does not separate), so about
#: three times the sound reading; it guards the loss path (the weighting of
#: the exits, the entropy term's sign, the reduction).
FIRST_LOSS_LIMIT = 0.005
REHEARSAL_LIMITS = os.path.join(core.BENCH_DIR, "limits",
                                "rehearsal-ouro.json")
CONTROL_OF = train.CONTROL_OF


def program_model(config, traffic):
    """The program's model object for a configuration file."""
    import jax.numpy as jnp

    heads = config["num_attention_heads"]
    if config["head_dim"] * heads != config["hidden_size"]:
        raise ValueError("the program's heads share the hidden size evenly")
    return Ouro(OuroConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=ref.depth(config), num_heads=heads,
        num_kv_heads=config["num_key_value_heads"],
        intermediate_size=config["intermediate_size"],
        max_seq_len=int(traffic["seq_len"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        total_ut_steps=ref.passes(config),
        exit_entropy_beta=ref.beta(config),
        ce_chunk_tokens=int(traffic["ce_chunk_tokens"]),
        dtype=getattr(jnp, traffic.get("dtype", "bfloat16")),
        remat=bool(traffic.get("remat", False))))


def sampled_tops(cfg):
    """Top-level names of the leaves whose first-step gradient and update
    are compared: both tables, the final norm, the gate, and every parameter
    of the first, middle and last layer (each the sum over T uses)."""
    last = ref.depth(cfg) - 1
    return {"embed_tokens", "lm_head", "final_norm", "exit_gate"} | {
        f"layers_{i}" for i in (0, last // 2, last)}


def program_exits(model, params, ids, labels):
    """Per-token log-probabilities of ``labels`` at every exit and the exit
    distribution, from the program's own training forward in its compute
    dtype -> ([T, B, S], [T, B, S]) float32."""
    import jax

    return jax.jit(lambda p, x, y: model.exits(p, x, y)[:2])(params, ids,
                                                             labels)


def cast_for_compute(params, traffic):
    """Seeded masters in the types the engine computes in: matrices in the
    compute type, but not those the model keeps in float32."""
    import jax
    import jax.numpy as jnp

    dtype = getattr(jnp, traffic.get("dtype", "bfloat16"))
    keep = ("embed_tokens", "exit_gate")
    return jax.jit(lambda p: {
        top: jax.tree_util.tree_map(
            lambda a: a if top in keep or a.ndim < 2 else a.astype(dtype), sub)
        for top, sub in p.items()})(params)


# ---------------------------------------------- what the first step left
def engine_first_step(engine, cfg):
    """After the engine's first step from the seeded weights: Adam's first
    moment and the float32 masters of the sampled leaves, on the host."""
    import jax

    tops = sampled_tops(cfg)
    adam = [s for s in jax.tree_util.tree_leaves(
        engine.state["opt_state"], is_leaf=lambda s: hasattr(s, "mu"))
        if hasattr(s, "mu")]
    return {"moment": train.sample_leaves(adam[0].mu, tops),
            "master": train.sample_leaves(engine.state["master_params"], tops),
            "grad_norm": engine.get_global_grad_norm()}


def plain_first_step(cfg, traffic, params, grads, master_dtype="float32"):
    """The same from the plain reference: ``grads`` (the reference's, or a
    control's) clipped by global norm, one Adam step on ``params``;
    ``master_dtype`` "bfloat16" is the optimizer's control."""
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
    master = train.sample_leaves(new, tops, getattr(jnp, master_dtype))
    return {"moment": {k: (1 - b1) * g for k, g in
                       train.sample_leaves(clipped, tops).items()},
            "master": {k: v.astype(np.float32) for k, v in master.items()},
            "grad_norm": float(norm)}


def start_engine(ctx, seed):
    """Seeded weights -> the engine, after its first step on the seed's
    first batch.  -> (engine, batches, first loss, what the step left)."""
    import deeperspeed_tpu as dst

    cfg, traffic = ctx.config, ctx.traffic
    batches = traffic_gen.TokenBatches(traffic, cfg["vocab_size"], seed)
    params = ref.init_params(cfg, seed)
    engine, _, _, _ = dst.initialize(
        model=program_model(cfg, traffic), model_parameters=params,
        mesh=train.cell_mesh(ctx), config=train.engine_config(traffic, seed))
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


def compare_exits(got, want):
    """(log-probs [T, S], p [T, S]) of the program (or a control) against
    the reference's -> the two numbers their limits stand on."""
    (got_lp, got_p), (want_lp, want_p) = (
        [np.asarray(a, np.float64) for a in pair] for pair in (got, want))
    return {"exit_logprob_rms": float(np.sqrt(np.mean(
                np.square(got_lp - want_lp), axis=1)).max()),
            "exit_share_abs": float(np.abs(got_p - want_p).max())}


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
    prog = [np.asarray(a)[:, 0] for a in program_exits(
        program_model(cfg, traffic), cast_for_compute(params, traffic),
        ids[:1], labels[:1])]
    ref_loss, grads, ref_lp, ref_p = ref.loss_and_grads(params, cfg, ids,
                                                        labels)
    want_exits = (np.asarray(ref_lp), np.asarray(ref_p))
    init = train.sample_leaves(params, sampled_tops(cfg))
    want = plain_first_step(cfg, traffic, params, grads)
    out = {"program": dict(
        train.compare_first_step(left, want, init),
        **compare_exits(prog, want_exits),
        first_loss_abs_diff=abs(first_loss - float(ref_loss)))}
    if controls:
        low = plain_first_step(cfg, traffic, params, grads, "bfloat16")
        out["control_bf16_masters"] = {
            "adam_update_rel_err": train.compare_first_step(
                low, want, init)["adam_update_rel_err"]}
        del grads, low
        ctl_loss, grads, ctl_lp, ctl_p = ref.loss_and_grads(
            params, cfg, ids, labels, "fp8")
        low = plain_first_step(cfg, traffic, params, grads)
        out["control_fp8"] = dict(
            grad_rel_err=train.compare_first_step(low, want, init)[
                "grad_rel_err"],
            **compare_exits((ctl_lp, ctl_p), want_exits),
            first_loss_abs_diff=abs(float(ctl_loss) - float(ref_loss)))
    return out


def calibrate(ctx, seeds, control_seeds=3):
    """Readings for the limits, many seeds in one process: the program's
    first step, and on the first ``control_seeds`` seeds the controls,
    against the plain reference.  One JSON line per seed -> the readings."""
    readings = []
    for n, seed in enumerate(seeds):
        engine, _, first_loss, left = start_engine(ctx, seed)
        del engine
        live = train.free_device()
        readings.append(dict(seed=seed, **against_reference(
            ctx, seed, first_loss, left, controls=n < control_seeds)))
        ctx.log("calibrate", live_bytes_after_engine=live, **readings[-1])
    return readings


def limits_from(readings):
    """A cell's limits from its readings, by ``runners/train.py``'s rule: the
    geometric mean of the largest the sound runs gave and the smallest the
    control gave, refused where the control reads under three times the sound
    runs.  The limits kept in this file must hold in every reading too, and
    stand as clear of the fp8 control where it separates."""
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
    import jax

    # the kept limits were set on the chip at the cell's size: there they
    # stand between the readings; a CPU rehearsal only has to stay under them
    on_chip = jax.default_backend() == "tpu"
    for number, limit in (("exit_logprob_rms", EXIT_LOGPROB_RMS_LIMIT),
                          ("exit_share_abs", EXIT_SHARE_ABS_LIMIT)):
        sound = max(r["program"][number] for r in readings)
        low = min(r["control_fp8"][number] for r in readings
                  if "control_fp8" in r)
        if sound >= limit or (on_chip and (limit >= low or low < 3 * sound)):
            raise SystemExit(f"{number}: the kept limit {limit} does not "
                             f"stand between {sound} and {low}")
    if max(r["program"]["first_loss_abs_diff"] for r in readings) \
            > FIRST_LOSS_LIMIT:
        raise SystemExit("the first-loss limit does not hold in a reading")
    return out


def check(ctx, state, record):
    losses = record["losses"]
    k = max(1, min(3, len(losses) // 2))
    head, tail = core.median(losses[:k]), core.median(losses[-k:])
    # the engine gives way to the reference's float32 weights and gradient
    del state["engine"]
    ctx.log("freed", live_bytes_after_engine=train.free_device())
    got = against_reference(ctx, ctx.seed, state["first_loss"],
                            state["first_step"])["program"]
    ctx.log("reference", **got)
    limits = (core.load_json(REHEARSAL_LIMITS) if ctx.rehearse
              else core.load_limits(ctx.cell["name"]))
    return [
        core.check("grad_rel_err_vs_reference", got["grad_rel_err"],
                   limits["grad_rel_err"]["limit"]),
        core.check("adam_update_rel_err_vs_reference",
                   got["adam_update_rel_err"],
                   limits["adam_update_rel_err"]["limit"]),
        core.check("exit_logprob_rms_vs_reference", got["exit_logprob_rms"],
                   EXIT_LOGPROB_RMS_LIMIT),
        core.check("exit_share_abs_diff_vs_reference", got["exit_share_abs"],
                   EXIT_SHARE_ABS_LIMIT),
        core.check("first_loss_abs_diff_vs_reference",
                   got["first_loss_abs_diff"], FIRST_LOSS_LIMIT),
        core.check("nonfinite_losses", record["failed"], 0),
        core.check("loss_fall_over_window", head - tail, 0.0,
                   ok=len(losses) < 2 or tail < head, better="higher"),
    ]


if __name__ == "__main__":
    # the CPU rehearsal's limits: ``calibrate.py --rehearse --write`` at the
    # tiny preset, with its output sent to this cell's own file
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from benchmarks import calibrate as calibrate_cli

    core.limits_path = lambda *_a, **_k: REHEARSAL_LIMITS
    sys.exit(calibrate_cli.main([
        "--workload", "train-ouro-2.6b-loop4", "--seeds", "8",
        "--control-seeds", "4", "--rehearse", "--write"]))

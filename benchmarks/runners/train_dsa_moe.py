"""Runner for training a decoder whose attention is learned sparse (a
lightning indexer scores every earlier token, each row keeps its ``topk``
best, one softmax over those; the indexer learns from a loss of its own)
over a softmax-routed mixture (Keye-VL-2.0-30B-A3B's language model: one
chip's share of the experts and of the vocabulary, six layers of a pipeline
stage): ``dst.initialize`` -> ``engine.train_batch`` on a fresh seeded batch
every step, under the traffic file's schedule and ``world``, by the route
``runners/train_laguna.py`` takes.

``runners/train_swa_moe.py``'s steps are model-free but for the names they
read from their own module; ``core.load_runner`` executes a runner's file
anew for every caller, so ``swa`` below is this file's own copy, and those
names are given it here: its ``start_engine``, ``setup``, ``calibrate``,
``engine_first_step``, ``plain_first_step`` and ``seeded_params`` then run
this model.  The timed window that keeps every step's counters is
``runners/train_hybrid.py``'s.  The model, the plain reference
(``reference/keye_ref.py``), its controls and the check are this file's.

What is compared (``against_reference``), each beside a limit that
``calibrate.py``'s readings on the chip at the cell's size set (the
gradient's and the update's in ``limits/<cell>.json``, the others kept in
this file beside their readings, as ``runners/train_laguna.py`` keeps its
own; none is a constant copied from another cell):

* the first step's two losses, ``L_LM`` and the sum of the indexers'
  losses, each against the reference's on the same batch;
* the first sequence's per-token log-probabilities (``logprob_rms``), which
  held experts its tokens chose (``routed_set_mismatch_share``) and which
  keys its rows chose (``dsa_selected_set_mismatch``: the share of the
  reference's chosen (row, key) pairs, over the layers, that the program did
  not choose; scores near the 2048th swap on a bfloat16 rounding, as the
  router's 8th and 9th do);
* the first step's gradient (Adam's first moment) over the sampled leaves,
  and over the INDEXER's leaves as a group of their own (they alone see the
  indexers' loss: ``indexer_grad_rel_err``), and the Adam update;
* the routed slots the step counted, and EXACTLY the pairs it selected:
  ``layers x rows x sum_t min(t + 1, topk)``.

The controls the limits must refuse: the reference in fp8, its Adam step
with bfloat16 masters, a state left unchanged, and the reference with one
MECHANISM LEFT OUT at a time (``ref.MECHANISMS``: no selection, no q/k norm,
no ReLU in the indexer, ``topk`` halved, the indexer's loss dropped, the
indexer's input not detached); the last two are read through the backward
pass (``BY_GRADIENT``), the others on the first sequence's forward pass.

The CPU rehearsal's limits are in ``limits/rehearsal-keye.json``, rewritten
by ``python3 benchmarks/runners/train_dsa_moe.py``.
"""

import os
import sys

import numpy as np

if __name__ == "__main__":      # the rehearsal's limits, see the foot
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks import core, traffic_gen
from benchmarks.reference import keye_ref as ref
# a program that has no such model fails here, as the runner is loaded:
# before anything is put on the device
from deeperspeed_tpu.models.keye import Keye, KeyeConfig

train = core.load_runner("train")
hybrid = core.load_runner("train_hybrid")
swa = core.load_runner("train_swa_moe")

REHEARSAL_LIMITS = os.path.join(core.BENCH_DIR, "limits",
                                "rehearsal-keye.json")
UNCHANGED = swa.UNCHANGED
#: control -> the mechanism the reference leaves out for it
LEFT_OUT = {"control_selection_left_out": "selection",
            "control_qk_norm_left_out": "qk_norm",
            "control_indexer_relu_left_out": "indexer_relu",
            "control_topk_halved": "topk_halved",
            "control_indexer_loss_left_out": "indexer_loss",
            "control_indexer_input_not_detached": "indexer_detach"}
#: the controls read through the backward pass: what they change is a
#: gradient (the indexer's leaves get none | the trunk gets the indexer's)
BY_GRADIENT = ("control_indexer_loss_left_out",
               "control_indexer_input_not_detached")
#: Limits of the output comparison kept here; those of the gradient and of
#: the update are per cell in ``limits/<cell>.json`` (``limits_from``).  Each
#: by one rule from readings on the chip at the cell's size: the geometric
#: mean of the largest a sound run gave and the smallest its control gave.
#: The readings quoted are of the program that ships (my chip runs, PR 53;
#: PERF.md section 2): ``calibrate.py --seeds 8 --control-seeds 4`` on the
#: final tree (eight sound seeds, every seed a world of its own; four control
#: seeds) and the cell's own world, seed 5: nine sets of weights.  What the
#: FORWARD pass alone decides (``logprob_rms``, the two set mismatches, the
#: first step's ``L_LM``, the slots) does not depend on how the indexers'
#: loss is walked: the calibration of an earlier form of the program, whose
#: loss pass walked the whole square, read those five bit for bit the same
#: in all eight worlds, so its nine more worlds (the cell's seeds 0-8 before
#: a world was chosen) count for those five: seventeen sets of weights.
#: RMS over the first sequence's 16,384 tokens of (program log-prob -
#: reference log-prob) of the label: sound runs read 0.0069-0.0090 (17), the
#: fp8 control 0.0788-0.0859 (8.7 times clear); with a mechanism left out:
#: no selection 0.102-0.123, ``topk`` halved 0.086-0.093, no q/k norm
#: 0.051-0.057, no ReLU in the indexer 0.053-0.056.
LOGPROB_RMS_LIMIT = 0.0267
#: Share of the (token, layer) pairs of the first sequence whose set of
#: chosen held experts differs from the reference's: the 8th and 9th of 128
#: softmax scores swap on a bfloat16 rounding of the router's input.  Sound
#: runs read 0.0110-0.0364 (17; by the world: the cell's, seed 5, 0.0278),
#: the fp8 control 0.1099-0.2885: three times clear and no more.
ROUTED_SET_MISMATCH_LIMIT = 0.0633
#: Share of the reference's chosen (row, key) pairs of the first sequence,
#: all six layers (188,749,824 pairs), that the program did not choose:
#: scores near a row's 2048th swap on a bfloat16 rounding of the indexer's
#: operands; both sides choose EXACTLY as many.  Sound runs read
#: 0.0088-0.0112 (17), the fp8 control 0.0749-0.0857 (6.7 times clear), no
#: q/k norm 0.057-0.072 (the stream under the next layers' indexers moves),
#: no ReLU in the indexer 0.252-0.339, ``topk`` halved 0.502-0.511.
SELECTED_SET_MISMATCH_LIMIT = 0.0290
#: ``grad_rel_err`` over the INDEXER's five leaves of the sampled layer
#: alone (they alone see the indexers' loss; their gradient is about a
#: thousandth of the whole gradient's norm, so the whole gradient's number
#: cannot see them).  Sound runs read 0.0050-0.0092 (9), the fp8 control
#: 0.0524-0.0822 (5.7 times clear); the reference that DROPPED the indexers'
#: loss reads 1 (its leaves get no gradient), the one whose indexer input
#: is NOT DETACHED 0.017-0.026 (under this limit in one of four: that
#: control fails in all four by the whole gradient, 0.252-0.300 against the
#: file's 0.0220).
INDEXER_GRAD_REL_LIMIT = 0.0220
#: |the step's ``dsa_indexer_kl`` - the reference's sum of the six indexers'
#: losses on the same batch| (0.23-0.31 itself): sound runs read
#: 0.00001-0.00032 (9); its control is the reference that dropped the loss,
#: which reads the whole of it, 0.239-0.305 (the fp8 control reads
#: 0.00008-0.0044: the precision does not separate here).
FIRST_INDEXER_KL_LIMIT = 0.0088
#: |the engine's first-step ``L_LM`` (the timed step's own counter
#: ``lm_loss``) - the reference's on the same batch|, a mean over 16,384
#: tokens: what holds the loss path of the step that is timed (layout,
#: reduction, the chunked head), which ``logprob_rms`` (a jit of the check's
#: own) does not run.  Sound runs read 0.000001-0.0021 (17; the
#: calibration's eight 0.0001-0.0011, the cell's world 0.000001), the fp8
#: control 0.0076-0.0190 (3.6 times clear).
FIRST_LOSS_LIMIT = 0.0040
#: |slots the program's first step counted - slots the reference counts on
#: the same batch| / the reference's, the mean a layer: the count the FLOPs
#: of ``train.dsa_moe_mfu_pct`` stand on.  Sound runs read 0.0003-0.0092
#: (17; one expert of the sixteen held takes almost every slot here, so a
#: layer's count is a few thousand flips of that one expert's rank) and the
#: fp8 control 0.0027-0.1275: the precision does NOT separate, so the limit
#: is three times the largest sound reading and guards the counter (a layer
#: not counted reads 0.17), not the precision.
SLOTS_HELD_REL_LIMIT = 0.0277
#: the two numbers whose limits are the cell's file's (``limits/<cell>.json``,
#: ``limits_from``) -> the control each stands against.  The update's is the
#: state LEFT UNCHANGED, as the Mellum cell's.
CONTROL_OF = {"grad_rel_err": "control_fp8",
              "adam_update_rel_err": UNCHANGED}
#: the numbers with a limit in this file that stands between the sound runs
#: and a control -> (that limit, that control)
KEPT = {"logprob_rms": (LOGPROB_RMS_LIMIT, "control_fp8"),
        "routed_set_mismatch_share": (ROUTED_SET_MISMATCH_LIMIT,
                                      "control_fp8"),
        "dsa_selected_set_mismatch": (SELECTED_SET_MISMATCH_LIMIT,
                                      "control_fp8"),
        "indexer_grad_rel_err": (INDEXER_GRAD_REL_LIMIT, "control_fp8"),
        "first_loss_abs_diff": (FIRST_LOSS_LIMIT, "control_fp8"),
        "first_indexer_kl_abs_diff": (FIRST_INDEXER_KL_LIMIT,
                                      "control_indexer_loss_left_out")}
#: that no control bounds, three times a sound reading -> that limit
GUARDS = {"slots_held_rel_diff": SLOTS_HELD_REL_LIMIT}


def program_model(config, traffic):
    """The program's model object for a configuration file."""
    import jax.numpy as jnp

    ref.layers_held(config), ref.share(config)  # refuse what neither runs
    sa = config["sa_config"]
    if sa.get("indexer_num_kv_heads", 1) != 1:
        raise ValueError("the indexer has one key head")
    return Keye(KeyeConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        rms_norm_eps=config["rms_norm_eps"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rope_theta=float(config["rope_theta"]),
        mrope_section=tuple(config["rope_scaling"]["mrope_section"]),
        indexer_num_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"], topk=sa["topk"],
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        layers_held=config.get("layers_held"),
        first_layer_held=int(config.get("first_layer_held", 0)),
        routed_experts_held=config.get("routed_experts_held"),
        first_expert_held=int(config.get("first_expert_held", 0)),
        vocab_rows_held=config.get("vocab_rows_held"),
        max_seq_len=int(traffic["seq_len"]),
        ce_chunk_tokens=int(traffic["ce_chunk_tokens"]),
        dtype=getattr(jnp, traffic.get("dtype", "bfloat16")),
        remat=bool(traffic.get("remat", False))))


def sampled_tops(cfg):
    """Top-level names of the leaves whose first-step gradient and update
    are compared: both tables, the final norm, and every parameter of the
    first layer held (the layers are alike)."""
    return {"embed_tokens", "lm_head_kernel", "final_norm_scale", "layers_0"}


def vocab(cfg):
    return ref.share(cfg)["vocab"]


def pairs_expected(cfg, rows, seq):
    """The (row, key) pairs a step selects, exactly: every layer, every
    sequence, ``sum_t min(t + 1, topk)``."""
    return ref.layers_held(cfg) * rows * ref.pairs(cfg, seq)[0]


def chosen_keys(model, params, ids):
    """Which keys the first sequence's rows chose in the program, every
    layer, as the reference packs them: [layers, S, ceil(S / 8)] uint8."""
    import jax
    import jax.numpy as jnp

    from deeperspeed_tpu.ops.attention import pallas_dsa

    seq = ids.shape[1]
    words = [w[0] for w in jax.jit(model.selections)(params, ids[:1])]
    chunks = pallas_dsa.sel_layout(seq).chunks
    pack = jax.jit(lambda w: jnp.packbits(
        pallas_dsa.unpack_rows(w, chunks)[:seq, :seq], axis=-1))
    return jnp.stack([pack(w) for w in words])


def compare_keys(got, want):
    """The packed chosen keys of a program (or a control) against the
    reference's -> (the share of the reference's chosen pairs that are not
    chosen, how many pairs are chosen)."""
    import jax
    import jax.numpy as jnp

    count = jax.jit(lambda a: jnp.sum(
        jax.lax.population_count(a).astype(jnp.int32)))
    both = sum(int(count(a & b)) for a, b in zip(got, want))
    wanted = sum(int(count(b)) for b in want)
    return 1.0 - both / max(wanted, 1), sum(int(count(a)) for a in got)


def against_reference(ctx, seed, first_loss, left, controls=False):
    """The program's first step against the plain reference, on a device the
    program has left.  ``controls`` adds what the controls read in the
    program's place.  -> dict of numbers."""
    import jax
    import jax.numpy as jnp

    cfg, traffic = ctx.config, ctx.traffic
    batches = traffic_gen.TokenBatches(traffic, vocab(cfg), seed)
    first = batches.batch(0)
    ids, labels = jnp.asarray(first["input_ids"]), jnp.asarray(first["labels"])
    rows, seq = ids.shape
    params = swa.seeded_params(cfg, batches)
    model = program_model(cfg, traffic)
    cast = hybrid.cast_for_compute(model, params, traffic)
    prog_lp, prog_chosen, _ = jax.jit(model.logprobs)(cast, ids[:1],
                                                      labels[:1])
    prog_lp, prog_chosen = np.asarray(prog_lp)[0], np.asarray(prog_chosen)[:, 0]
    prog_keys = chosen_keys(model, cast, ids)
    del cast
    (ref_lm, ref_kl), grads, ref_lp, ref_chosen, ref_keys = (
        ref.loss_and_grads(params, cfg, ids, labels))
    ref_lm, ref_kl = float(ref_lm), float(ref_kl)
    ref_lp, ref_chosen = np.asarray(ref_lp), np.asarray(ref_chosen)
    # the mean number of slots a layer held over the whole batch
    ref_slots = float(ref_chosen.sum()) / ref_chosen.shape[1]
    init = train.sample_leaves(params, sampled_tops(cfg))
    want = swa.plain_first_step(cfg, traffic, params, grads)
    counters = left["counters"]

    def indexer_grad(step):
        """``grad_rel_err`` of a first step over the indexer's leaves."""
        own = {k: v for k, v in want["moment"].items() if ref.INDEXER in k}
        return train.compare_first_step(step, dict(want, moment=own),
                                        init)["grad_rel_err"]

    def forward_numbers(lp, chosen, keys):
        """A forward pass of the first sequence against the reference's."""
        mismatch, selected = compare_keys(keys, ref_keys)
        return dict(
            logprob_rms=train.compare_logprobs(lp, ref_lp),
            routed_set_mismatch_share=hybrid.compare_routing(chosen,
                                                             ref_chosen[0]),
            dsa_selected_set_mismatch=mismatch,
            dsa_pairs_selected_first_sequence=selected)

    def backward_numbers(step, lm, kl):
        return dict(
            grad_rel_err=train.compare_first_step(step, want, init)[
                "grad_rel_err"],
            indexer_grad_rel_err=indexer_grad(step),
            first_loss_abs_diff=abs(lm - ref_lm),
            first_indexer_kl_abs_diff=abs(kl - ref_kl))

    lm = counters.get("lm_loss", first_loss)
    kl = counters.get("dsa_indexer_kl", 0.0)
    out = {"program": dict(
        train.compare_first_step(left, want, init),
        **forward_numbers(prog_lp, prog_chosen, prog_keys),
        **{k: v for k, v in backward_numbers(left, lm, kl).items()
           if k != "grad_rel_err"},
        first_total_abs_diff=abs(first_loss - ref_lm - ref_kl),
        lm_loss=lm, indexer_kl=kl, lm_loss_reference=ref_lm,
        indexer_kl_reference=ref_kl,
        slots_held_rel_diff=abs(counters.get("moe_slots_held", 0.0)
                                - ref_slots) / max(ref_slots, 1.0),
        slots_held=counters.get("moe_slots_held"),
        slots_held_reference=ref_slots,
        dsa_pairs_selected=counters.get("dsa_pairs_selected"),
        dsa_pairs_expected=pairs_expected(cfg, rows, seq))}
    del prog_keys
    if not controls:
        return out
    low = swa.plain_first_step(cfg, traffic, params, grads, "bfloat16")
    out["control_bf16_masters"] = {
        "adam_update_rel_err": train.compare_first_step(
            low, want, init)["adam_update_rel_err"]}
    out[UNCHANGED] = {
        "adam_update_rel_err": train.compare_first_step(
            dict(want, master=init), want, init)["adam_update_rel_err"]}
    del grads, low

    def through_the_backward(**changed):
        (c_lm, c_kl), grads, lp, chosen, keys = ref.loss_and_grads(
            params, cfg, ids, labels, **changed)
        chosen = np.asarray(chosen)
        step = swa.plain_first_step(cfg, traffic, params, grads)
        return dict(
            backward_numbers(step, float(c_lm), float(c_kl)),
            **forward_numbers(np.asarray(lp), chosen[0], keys),
            slots_held_rel_diff=abs(float(chosen.sum())
                                    - float(ref_chosen.sum()))
            / max(float(ref_chosen.sum()), 1.0))

    out["control_fp8"] = through_the_backward(precision="fp8")
    for name, mechanism in LEFT_OUT.items():
        if name in BY_GRADIENT:
            out[name] = through_the_backward(without=(mechanism,))
            continue
        lp, _, chosen, keys = jax.jit(
            lambda p, x, y, m=mechanism: ref.token_logprobs(
                p, cfg, x, y, without=(m,)))(params, ids[0], labels[0])
        out[name] = forward_numbers(np.asarray(lp), np.asarray(chosen), keys)
        # what the step's exact count would read of such a program
        out[name]["dsa_pairs_selected_first_sequence_expected"] = (
            pairs_expected(cfg, 1, seq))
    return out


# this file's own copy of the Mellum cell's runner runs this model
swa.ref, swa.program_model, swa.sampled_tops = ref, program_model, sampled_tops
swa.vocab, swa.against_reference = vocab, against_reference
engine_config, first_rate = swa.engine_config, swa.first_rate
plain_first_step, seeded_params = swa.plain_first_step, swa.seeded_params
start_engine, setup, calibrate = swa.start_engine, swa.setup, swa.calibrate
#: the timed window, with every step's counters kept and the routed load by
#: step in the progress line ``window_counters``: the hybrid runner's
window = hybrid.window


def held_limits(limits):
    """number -> limit: the cell's file's two and those kept here."""
    return dict({k: limit for k, (limit, _) in KEPT.items()}, **GUARDS,
                **{k: v["limit"] for k, v in limits.items()
                   if k in CONTROL_OF})


def refused(numbers, limits):
    """The names of the limits a set of numbers (a control's) breaks; the
    exact count of chosen pairs among them."""
    held = held_limits(limits)
    broken = [k for k, v in numbers.items() if k in held and v > held[k]]
    expected = numbers.get("dsa_pairs_selected_first_sequence_expected")
    if expected is not None and numbers[
            "dsa_pairs_selected_first_sequence"] != expected:
        broken.append("dsa_pairs_selected")
    return sorted(broken)


def limits_from(readings):
    """A cell's limits from its readings, by ``runners/train.py``'s rule: the
    geometric mean of the largest the sound runs gave and the smallest the
    control gave, refused where the control reads under three times the sound
    runs (the update's control is the state left unchanged, as the Mellum
    cell's).  The limits kept in this file must hold in every reading too,
    and stand as clear of their control (``KEPT``) or leave the sound
    readings three times of room (``GUARDS``); and every reading of the
    bfloat16-masters control, of the fp8 control and of each mechanism left
    out must break a limit."""
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
    for number, (limit, control) in KEPT.items():
        sound = max(r["program"][number] for r in readings)
        low = min(r[control][number] for r in readings if control in r)
        if sound >= limit or (on_chip and (limit >= low or low < 3 * sound)):
            raise SystemExit(f"{number}: the kept limit {limit} does not "
                             f"stand between {sound} and {low}")
    for number, limit in GUARDS.items():
        sound = max(r["program"][number] for r in readings)
        if sound > limit or (on_chip and 3 * sound > limit):
            raise SystemExit(f"{number}: the guard {limit} does not leave "
                             f"{sound} three times of room")
    # a control is refused by the kept limits, so where those stand: at the
    # cell's size (at the tiny preset the precision hardly separates)
    for r in readings:
        for control in ("control_bf16_masters", *(
                ("control_fp8", *LEFT_OUT) if on_chip else ())):
            if control in r and not refused(r[control], out):
                raise SystemExit(f"{control} would pass: {r[control]}")
    return out


def layers_counted(cfg, *counters):
    """Whether every set of step counters counted the held layers."""
    depth = ref.layers_held(cfg)
    return all(c.get("dsa_layer_applications") == depth
               and c.get("moe_layer_applications") == depth for c in counters)


def check(ctx, state, record):
    losses = record["losses"]
    k = max(1, min(3, len(losses) // 2))
    head, tail = core.median(losses[:k]), core.median(losses[-k:])
    # what the window's steps counted of themselves (``window``)
    in_window = record["step_counters"]
    first = state["first_step"]["counters"]
    batches = state["batches"]
    # the engine gives way to the reference's float32 weights and gradient
    del state["engine"]
    ctx.log("freed", live_bytes_after_engine=train.free_device(),
            step_counters=in_window)
    got = against_reference(ctx, ctx.seed, state["first_loss"],
                            state["first_step"])["program"]
    ctx.log("reference", **got)
    limits = held_limits(core.load_json(REHEARSAL_LIMITS) if ctx.rehearse
                         else core.load_limits(ctx.cell["name"]))
    counted = layers_counted(ctx.config, in_window, first)
    dropped = max(c.get("moe_slots_dropped", -1.0) for c in (in_window, first))
    expected = pairs_expected(ctx.config, batches.shape[0],
                              batches.shape[1] - 1)
    exact = all(c.get("dsa_pairs_selected") == expected
                for c in (in_window, first))
    return [
        *(core.check(f"{number}_vs_reference", got[number], limits[number])
          for number in (*CONTROL_OF, *KEPT, *GUARDS)),
        core.check("dsa_pairs_selected", in_window.get("dsa_pairs_selected"),
                   expected, ok=exact),
        core.check("moe_slots_dropped", dropped, 0.0, ok=dropped == 0.0),
        core.check("layers_of_every_kind_counted", int(counted), 1,
                   ok=counted, better="higher"),
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
        "--workload", "train-keye-vl2-ep8-16k", "--seeds", "8",
        "--control-seeds", "4", "--rehearse", "--write"]))

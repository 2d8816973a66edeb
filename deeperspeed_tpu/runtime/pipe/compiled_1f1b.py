"""Compiled 1F1B pipeline: one jitted program, 1F1B memory + FLOPs.

The reference executes 1F1B by interpreting an instruction stream
(``runtime/pipe/schedule.py:189`` ``TrainSchedule.steps``, dispatched by
``runtime/pipe/engine.py:633,710`` fwd/bwd handlers).  ``compiled.py``'s
GPipe-shaped scan already removed the dispatch, but paid two taxes the
reference does not: activation carries grow with the microbatch count M
(GPipe memory), and every stage executes every tick, so the pipeline
bubble burns real FLOPs instead of idling.

This module compiles the *1F1B schedule itself* into one ``lax.scan``:

* Global half-tick clock ``t = 0 .. 2(M+S-1)-1``.  Stage ``s`` runs the
  forward of microbatch ``m`` at tick ``s + 2m`` and its backward at tick
  ``2(S-1) - s + 2m + 1``.  Forward ticks for stage ``s`` have parity
  ``s % 2`` and backward ticks the opposite parity, so each stage does at
  most ONE of {forward, backward} per tick -- the classic non-interleaved
  1F1B interleave (PipeDream-flush), reproduced in lockstep SPMD.
* Idle ticks (the warmup/drain bubble) hit the no-op branch of a
  ``lax.switch``: XLA's conditional executes only the taken branch at
  runtime, so the bubble costs control-flow, not matmuls -- matching the
  interpreted executor's FLOP count with zero per-instruction dispatch.
* Backward is MANUAL (the scan is never differentiated): each stage saves
  only the [B, S, H] *input* of every in-flight microbatch in a depth-S
  ring buffer and re-runs the stage forward under ``jax.vjp`` at backward
  time -- stage-granular activation recomputation, the exact policy of the
  interpreted executor and of the reference's activation-checkpointed
  pipeline.  In-flight microbatches at stage ``s`` number ``S - s`` (the
  1F1B bound), so live activation memory is O(S * B*S_q*H), independent
  of M; the GPipe scan's was O(M + S).
* Stage-to-stage traffic stays ``ppermute`` over the manual ``pp`` axis:
  activations forward each tick, input-cotangents backward each tick.
  Static shapes: no tensor-meta handshake (reference ``pipe/p2p.py``).

Loss/grad convention matches the flat engine's gas loop
(``runtime/engine.py:_grads_for_batch``): loss = mean over microbatches of
the per-microbatch masked mean, and grads are d(scale * loss)/d(params),
realized by seeding each microbatch's backward with cotangent
``scale / M``.
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...parallel import topology as topo
from ...utils.tree import tree_cast


def make_pipeline_grad_fn(model, mesh, n_micro, compute_dtype=None):
    """Build grad_fn(params, batch, rng, cot_scale) -> (grads, loss).

    ``params`` = {"stages": [pp, L, ...], "embed": ..., "head": ...} fp32
    masters; ``batch`` fields are [M, B, S_q] with M == n_micro.  ``grads``
    matches ``params`` (fp32 accumulation).  ``cot_scale`` seeds each
    microbatch backward (loss-scale * 1; the 1/M mean factor is applied
    inside), so fp16 dynamic loss scaling composes exactly as on the flat
    engine.
    """
    S = model.num_stages
    M = n_micro
    D = S  # ring depth >= max in-flight (S - stage_id <= S)
    K = 2 * (M + S - 1)  # half-ticks: last backward at 2(S-1)+2(M-1)+1

    act_dtype = model.config.dtype

    def manual_fn(stage_params, embed_params, head_params, tokens, labels,
                  loss_mask, cot_scale, stage_ids, rng):
        sp = jax.tree_util.tree_map(lambda x: x[0], stage_params)
        if compute_dtype is not None:
            cast = lambda t: jax.tree_util.tree_map(
                lambda x: x.astype(compute_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, t)
            sp = cast(sp)
            head_params = cast(head_params)
            # embed table stays fp32 (f32 gather/scatter; see _EmbedIn)
        # pp-sharded iota operand instead of jax.lax.axis_index: a jaxlib
        # 0.4.37 work-around that jax 0.9.0 no longer needs (see the note in
        # compiled.py); it goes with ROADMAP D3
        stage_id = stage_ids[0]
        is_last = stage_id == S - 1
        is_first = stage_id == 0
        m, b, sq = tokens.shape
        h = model.config.hidden_size
        positions = jnp.broadcast_to(jnp.arange(sq), (b, sq))
        perm_fwd = [(i, (i + 1) % S) for i in range(S)]
        perm_bwd = [(i, (i - 1) % S) for i in range(S)]

        zeros_sp = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), sp)
        zeros_head = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), head_params)

        def run_stage(sp_, head_, x_, micro, labels_t, mask_t):
            """Differentiated core: stage blocks (+ head/loss on last stage).

            Returns (y, mean); the caller seeds (dy, dmean) so one vjp
            covers both the mid-pipeline and the loss-bearing stage.
            ``head_mean`` sits under ``lax.cond`` -- non-last stages skip the
            vocab GEMM at runtime and its pullback contributes exact zeros.
            """
            r = None
            if rng is not None:
                r = jax.random.fold_in(jax.random.fold_in(rng, micro), stage_id)
            y = model.stage_forward(sp_, x_, positions,
                                    deterministic=rng is None, rng=r)

            def head_mean(args):
                x, head_p, labels_t_, mask_t_ = args
                logits = model.head({"head": head_p}, x)
                mean = model.loss_from_logits(logits, labels_t_,
                                              loss_mask=mask_t_)
                return mean.astype(jnp.float32)

            # head_ must flow through the cond OPERANDS (not a closure), or
            # the vjp w.r.t. the head params sees a constant and returns 0.
            mean = jax.lax.cond(
                is_last, head_mean, lambda args: jnp.float32(0.0),
                (y, head_, labels_t, mask_t))
            return y, mean

        def tick(carry, t):
            (x_buf, rx_act, rx_cot, g_sp, g_embed, g_head, num) = carry

            # ---- schedule arithmetic (static S/M, traced stage_id/t)
            f_off = t - stage_id
            fwd_m = jnp.clip(f_off // 2, 0, M - 1)
            fwd_active = (f_off >= 0) & (f_off % 2 == 0) & (f_off // 2 < M)
            b_off = t - (2 * (S - 1) - stage_id + 1)
            bwd_m = jnp.clip(b_off // 2, 0, M - 1)
            bwd_active = (b_off >= 0) & (b_off % 2 == 0) & (b_off // 2 < M)

            # ---- forward input: stage 0 embeds its microbatch's tokens
            # (masked lookup outside any cond: gather/scatter in a manual-
            # region conditional aborts XLA:CPU); later stages consume the
            # activation ppermuted in at the previous tick.
            toks_f = jax.lax.dynamic_index_in_dim(tokens, fwd_m, 0,
                                                  keepdims=False)
            toks_f = jnp.where(is_first & fwd_active, toks_f,
                               jnp.zeros_like(toks_f))
            emb = model.embed({"embed": embed_params}, toks_f)
            x_in = jnp.where(is_first, emb, rx_act).astype(act_dtype)

            # ---- backward operands: saved input + labels of microbatch bwd_m
            slot_b = bwd_m % D
            x_saved = jax.lax.dynamic_index_in_dim(x_buf, slot_b, 0,
                                                   keepdims=False)
            labels_b = jax.lax.dynamic_index_in_dim(labels, bwd_m, 0,
                                                    keepdims=False)
            mask_b = jax.lax.dynamic_index_in_dim(loss_mask, bwd_m, 0,
                                                  keepdims=False)

            zeros_y = jnp.zeros((b, sq, h), act_dtype)

            def br_noop(_):
                return (zeros_y, zeros_y, zeros_sp, zeros_head,
                        jnp.float32(0.0))

            def br_fwd(_):
                # blocks only -- the head GEMM + loss run on the backward
                # tick (whose vjp re-runs the stage anyway), so the last
                # stage pays the vocab projection once per microbatch, not
                # twice.
                r = None
                if rng is not None:
                    r = jax.random.fold_in(jax.random.fold_in(rng, fwd_m),
                                           stage_id)
                y = model.stage_forward(sp, x_in, positions,
                                        deterministic=rng is None, rng=r)
                return (y.astype(act_dtype), zeros_y, zeros_sp, zeros_head,
                        jnp.float32(0.0))

            def br_bwd(_):
                f = lambda sp_, head_, x_: run_stage(sp_, head_, x_, bwd_m,
                                                     labels_b, mask_b)
                (y, mean), pull = jax.vjp(f, sp, head_params, x_saved)
                dy = jnp.where(is_last, jnp.zeros_like(y),
                               rx_cot.astype(y.dtype))
                dmean = jnp.where(is_last, cot_scale / M, 0.0).astype(
                    jnp.float32)
                d_sp, d_head, d_x = pull((dy, dmean))
                return (zeros_y, d_x.astype(act_dtype),
                        tree_cast(d_sp, jnp.float32),
                        tree_cast(d_head, jnp.float32),
                        mean)

            # the last stage's forward-tick output is consumed by nobody
            # (its backward tick, one half-tick later, recomputes the stage
            # under vjp from the saved input) -- skip the compute, keep the
            # ring-buffer write below.
            branch = jnp.where(fwd_active & ~is_last, 1,
                               jnp.where(bwd_active, 2, 0))
            y_out, gx, d_sp, d_head, mean = jax.lax.switch(
                branch, (br_noop, br_fwd, br_bwd), None)

            # ---- transfers, issued as soon as their operands exist:
            # activations ride forward, cotangents backward.  Nothing below
            # depends on the received values, so placing the ppermutes
            # before the embedding backward lets the async-collective
            # scheduler run the ICI hop under the scatter-add instead of
            # serializing after it.
            rx_act = jax.lax.ppermute(y_out, topo.PP_AXIS, perm_fwd)
            rx_cot = jax.lax.ppermute(gx, topo.PP_AXIS, perm_bwd)

            # ---- embedding backward, outside the switch: the scatter-add
            # runs every tick on masked operands (zero cotangent except on
            # stage 0's backward ticks), sidestepping the scatter-in-cond
            # abort while charging one table row of work.
            toks_b = jax.lax.dynamic_index_in_dim(tokens, bwd_m, 0,
                                                  keepdims=False)
            emb_live = is_first & bwd_active
            toks_b = jnp.where(emb_live, toks_b, jnp.zeros_like(toks_b))
            d_emb_out = jnp.where(emb_live, gx, jnp.zeros_like(gx))
            _, pull_e = jax.vjp(
                lambda ep: model.embed({"embed": ep}, toks_b), embed_params)
            (d_embed,) = pull_e(d_emb_out)

            # ---- ring buffer write (read-modify-write keeps the index
            # in-range and the update a no-op on inactive ticks)
            slot_f = fwd_m % D
            old = jax.lax.dynamic_index_in_dim(x_buf, slot_f, 0,
                                               keepdims=False)
            x_buf = jax.lax.dynamic_update_index_in_dim(
                x_buf, jnp.where(fwd_active, x_in, old), slot_f, 0)

            g_sp = jax.tree_util.tree_map(jnp.add, g_sp, d_sp)
            g_embed = jax.tree_util.tree_map(jnp.add, g_embed, d_embed)
            g_head = jax.tree_util.tree_map(jnp.add, g_head, d_head)
            return ((x_buf, rx_act, rx_cot, g_sp, g_embed, g_head,
                     num + mean), None)

        zeros_embed = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), embed_params)
        init = (
            jnp.zeros((D, b, sq, h), act_dtype),
            jnp.zeros((b, sq, h), act_dtype),
            jnp.zeros((b, sq, h), act_dtype),
            zeros_sp,
            zeros_embed,
            zeros_head,
            jnp.float32(0.0),
        )
        (_, _, _, g_sp, g_embed, g_head, num), _ = jax.lax.scan(
            tick, init, jnp.arange(K))

        # embed/head grads are pp-replicated leaves: sum each stage's
        # contribution (embed: stage 0 only; head: last stage only) so the
        # replicated out_spec sees an invariant value.
        g_embed = jax.tree_util.tree_map(
            lambda x: jax.lax.psum(x, topo.PP_AXIS), g_embed)
        g_head = jax.tree_util.tree_map(
            lambda x: jax.lax.psum(x, topo.PP_AXIS), g_head)
        loss = jax.lax.psum(num, topo.PP_AXIS) / M
        g_sp = jax.tree_util.tree_map(lambda x: x[None], g_sp)
        return {"stages": g_sp, "embed": g_embed, "head": g_head}, loss

    def grad_fn(params, batch, rng=None, cot_scale=1.0):
        stage_specs = jax.tree_util.tree_map(
            lambda x: P(topo.PP_AXIS), params["stages"])
        labels = batch["labels"]
        loss_mask = batch.get("loss_mask")
        if loss_mask is None:
            loss_mask = jnp.ones(labels.shape, jnp.float32)
        dropout_on = (getattr(model.config, "hidden_dropout", 0.0) > 0.0
                      or getattr(model.config, "attention_dropout", 0.0) > 0.0)
        use_rng = rng if (rng is not None and dropout_on) else None
        rng_specs = () if use_rng is None else (P(),)
        grad_specs = {"stages": stage_specs,
                      "embed": jax.tree_util.tree_map(
                          lambda x: P(), params["embed"]),
                      "head": jax.tree_util.tree_map(
                          lambda x: P(), params["head"])}
        fn = jax.shard_map(
            manual_fn if use_rng is not None else
            (lambda sp_, e_, h_, t_, l_, m_, c_, i_:
             manual_fn(sp_, e_, h_, t_, l_, m_, c_, i_, None)),
            mesh=mesh.mesh,
            in_specs=(stage_specs, P(), P(), P(), P(), P(), P(),
                      P(topo.PP_AXIS)) + rng_specs,
            out_specs=(grad_specs, P()),
            # manual over ALL mesh axes: a size->1 auto axis alongside the
            # manual pp collectives trips an SPMD-partitioner manual-subgroup
            # check in this jax (hard abort); non-pp axes carry replicated
            # operands here, so full-manual is semantically identical
            axis_names=set(mesh.mesh.axis_names),
            check_vma=False,
        )
        args = (params["stages"], params["embed"], params["head"],
                batch["input_ids"], labels, loss_mask,
                jnp.asarray(cot_scale, jnp.float32),
                jnp.arange(S, dtype=jnp.int32))
        if use_rng is not None:
            args = args + (use_rng,)
        return fn(*args)

    return grad_fn

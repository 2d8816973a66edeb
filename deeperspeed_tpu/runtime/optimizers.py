"""Optimizer factory (equivalent of reference ``engine.py:1259``
``_configure_basic_optimizer`` + the fork's mu-optimizers at
``engine.py:1336-1350``).

Built on optax transformations.  The reference's choice between
FusedAdam/CPUAdam/torch Adam (``engine.py:1259-1334``) is one optax chain
here: "fused" on TPU is XLA's fusion of that chain (see ``build_optimizer``).
"""

import jax
import jax.numpy as jnp
import optax

from .constants import (
    ADAGRAD_OPTIMIZER,
    ADAM_OPTIMIZER,
    ADAMW_OPTIMIZER,
    CPU_ADAM_OPTIMIZER,
    FUSED_ADAM_OPTIMIZER,
    FUSED_LION_OPTIMIZER,
    LAMB_OPTIMIZER,
    LION_OPTIMIZER,
    MUADAM_OPTIMIZER,
    MUADAMW_OPTIMIZER,
    MUSGD_OPTIMIZER,
    ONEBIT_ADAM_OPTIMIZER,
    SGD_OPTIMIZER,
)
from ..utils.logging import logger


def default_weight_decay_mask(params):
    """Decay matrices/embeddings; skip vectors (biases, norm scales)."""
    return jax.tree_util.tree_map(lambda p: jnp.ndim(p) >= 2, params)


def scale_by_mup(multipliers):
    """Per-leaf LR multiplier transformation -- the μP width-scaling applied
    by MuAdam/MuSGD (fork delta, reference ``engine.py:1336-1350``).

    ``multipliers`` is a pytree (matching params) of scalars, typically
    ``1/width_mult`` for matrix-like params produced by the model's
    ``mup_multipliers()``.
    """

    def init_fn(params):
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        updates = jax.tree_util.tree_map(lambda u, m: u * m, updates, multipliers)
        return updates, state

    return optax.GradientTransformation(init_fn, update_fn)


def _adam_like(params_cfg, adamw=False, mup_multipliers=None):
    chain = [optax.scale_by_adam(b1=params_cfg.betas[0], b2=params_cfg.betas[1],
                                 eps=params_cfg.eps)]
    if mup_multipliers is not None:
        chain.append(scale_by_mup(mup_multipliers))
    if params_cfg.weight_decay and adamw:
        chain.append(optax.add_decayed_weights(params_cfg.weight_decay,
                                               mask=default_weight_decay_mask))
    elif params_cfg.weight_decay and not adamw:
        # plain Adam applies L2 to the gradient before the moment update;
        # optax models that by decaying before scale_by_adam.
        chain.insert(0, optax.add_decayed_weights(params_cfg.weight_decay,
                                                  mask=default_weight_decay_mask))
    return optax.chain(*chain)


def build_optimizer(name, params_cfg, mup_multipliers=None):
    """name + OptimizerParams -> optax.GradientTransformation (lr excluded).

    LR is applied separately by the engine (``optax.scale_by_learning_rate``
    over the schedule) so the on-device schedule stays a pure fn of step.
    """
    name = name.lower()
    if name in (ADAM_OPTIMIZER, FUSED_ADAM_OPTIMIZER, CPU_ADAM_OPTIMIZER,
                ONEBIT_ADAM_OPTIMIZER, MUADAM_OPTIMIZER):
        # onebitadam: the LOCAL update is exact Adam -- the 1-bit part is the
        # gradient *reduction*, which the engine swaps in (error-feedback
        # sign compression over the dp axis after freeze_step; see
        # grad_reduce.OneBit and comm/compressed.py).
        #
        # "Fused" on TPU means XLA's fusion of the whole optax chain: measured
        # on v5e (tools/profile_bench.py, r3), a per-leaf Pallas kernel ran
        # at ~160 GB/s vs ~280 GB/s for the XLA elementwise fusion -- grid-step
        # overhead on (512,128) blocks loses to XLA's own loop fusion.  The
        # Pallas Adam and Lion kernels went for that reason (PR 31); the
        # names "FusedAdam" / "FusedLion" stay accepted and build this chain.
        return _adam_like(params_cfg, adamw=False, mup_multipliers=mup_multipliers)
    if name in (ADAMW_OPTIMIZER, MUADAMW_OPTIMIZER):
        return _adam_like(params_cfg, adamw=True, mup_multipliers=mup_multipliers)
    if name == SGD_OPTIMIZER:
        chain = [optax.trace(decay=params_cfg.momentum)] if params_cfg.momentum else []
        if params_cfg.weight_decay:
            chain.insert(0, optax.add_decayed_weights(params_cfg.weight_decay,
                                                      mask=default_weight_decay_mask))
        return optax.chain(*chain) if chain else optax.identity()
    if name == MUSGD_OPTIMIZER:
        chain = [optax.trace(decay=params_cfg.momentum)] if params_cfg.momentum else []
        if mup_multipliers is not None:
            chain.append(scale_by_mup(mup_multipliers))
        return optax.chain(*chain) if chain else optax.identity()
    if name == LAMB_OPTIMIZER:
        return optax.chain(
            optax.scale_by_adam(b1=params_cfg.betas[0], b2=params_cfg.betas[1],
                                eps=params_cfg.eps),
            optax.add_decayed_weights(params_cfg.weight_decay,
                                      mask=default_weight_decay_mask),
            optax.scale_by_trust_ratio(min_norm=0.0),
        )
    if name in (LION_OPTIMIZER, FUSED_LION_OPTIMIZER):
        chain = [optax.scale_by_lion(b1=params_cfg.betas[0],
                                     b2=params_cfg.betas[1])]
        if params_cfg.weight_decay:
            chain.append(optax.add_decayed_weights(params_cfg.weight_decay,
                                                   mask=default_weight_decay_mask))
        return optax.chain(*chain)
    if name == ADAGRAD_OPTIMIZER:
        return optax.scale_by_rss(initial_accumulator_value=0.1, eps=params_cfg.eps)
    raise ValueError(f"Unknown optimizer name {name!r}")

"""Fused Adam (equivalent of reference ``csrc/adam/multi_tensor_adam.cu`` +
``ops/adam/fused_adam.py``).

On TPU the moment update is a Pallas kernel fusing m/v updates + bias
correction + the normalized update into one VMEM pass per leaf (saving HBM
round-trips of m and v); off-TPU it falls back to the identical jnp math so
numerics match everywhere.  Exposed as an optax transformation so the engine
treats it like ``optax.scale_by_adam``.
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax


class ScaleByFusedAdamState(NamedTuple):
    count: jnp.ndarray
    mu: optax.Updates
    nu: optax.Updates


def _adam_leaf_update_jnp(g, m, v, count, b1, b2, eps):
    g32 = g.astype(jnp.float32)
    m = b1 * m + (1.0 - b1) * g32
    v = b2 * v + (1.0 - b2) * g32 * g32
    bc1 = 1.0 - b1 ** count
    bc2 = 1.0 - b2 ** count
    update = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    return update, m, v


def _adam_leaf_update(g, m, v, count, b1, b2, eps):
    from ...accelerator import get_accelerator

    if get_accelerator().use_pallas_kernels() and g.size >= 1024:
        from .pallas_adam import fused_adam_kernel

        return fused_adam_kernel(g, m, v, count, b1, b2, eps)
    return _adam_leaf_update_jnp(g, m, v, count, b1, b2, eps)


def scale_by_fused_adam(b1=0.9, b2=0.999, eps=1e-8):
    def init_fn(params):
        mu = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        nu = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return ScaleByFusedAdamState(count=jnp.zeros((), jnp.int32), mu=mu, nu=nu)

    def update_fn(updates, state, params=None):
        count = state.count + 1
        flat_u, treedef = jax.tree_util.tree_flatten(updates)
        flat_m = treedef.flatten_up_to(state.mu)
        flat_v = treedef.flatten_up_to(state.nu)
        out_u, out_m, out_v = [], [], []
        for g, m, v in zip(flat_u, flat_m, flat_v):
            u, m2, v2 = _adam_leaf_update(g, m, v, count.astype(jnp.float32), b1, b2, eps)
            out_u.append(u.astype(g.dtype))
            out_m.append(m2)
            out_v.append(v2)
        return (
            jax.tree_util.tree_unflatten(treedef, out_u),
            ScaleByFusedAdamState(
                count=count,
                mu=jax.tree_util.tree_unflatten(treedef, out_m),
                nu=jax.tree_util.tree_unflatten(treedef, out_v),
            ),
        )

    return optax.GradientTransformation(init_fn, update_fn)

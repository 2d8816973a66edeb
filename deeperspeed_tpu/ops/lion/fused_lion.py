"""Fused Lion (equivalent of reference ``csrc/lion/`` + ``ops/lion/fused_lion.py``).

Lion's update is ``u = sign(b1*m + (1-b1)*g)`` with moment
``m' = b2*m + (1-b2)*g`` -- one elementwise VMEM pass on TPU via Pallas,
identical jnp math elsewhere.  Exposed as an optax transformation mirroring
``optax.scale_by_lion``.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax

from ..pallas_utils import elementwise_call

BLOCK_ROWS = 512


class ScaleByFusedLionState(NamedTuple):
    mu: optax.Updates


def _lion_leaf_jnp(g, m, b1, b2):
    g32 = g.astype(jnp.float32)
    update = jnp.sign(b1 * m + (1.0 - b1) * g32)
    m = b2 * m + (1.0 - b2) * g32
    return update, m


def _lion_kernel(g_ref, m_ref, u_out, m_out, *, b1, b2):
    g = g_ref[:].astype(jnp.float32)
    m = m_ref[:]
    u_out[:] = jnp.sign(b1 * m + (1.0 - b1) * g)
    m_out[:] = b2 * m + (1.0 - b2) * g


@functools.partial(jax.jit, static_argnames=("b1", "b2"))
def fused_lion_kernel(g, m, b1, b2):
    u, m2 = elementwise_call(
        functools.partial(_lion_kernel, b1=b1, b2=b2),
        [jnp.float32, jnp.float32],
        [g.astype(jnp.float32), m], BLOCK_ROWS)
    return u, m2


def _lion_leaf(g, m, b1, b2):
    from ...accelerator import get_accelerator

    if get_accelerator().use_pallas_kernels() and g.size >= 1024:
        return fused_lion_kernel(g, m, b1, b2)
    return _lion_leaf_jnp(g, m, b1, b2)


def scale_by_fused_lion(b1=0.9, b2=0.99):
    def init_fn(params):
        mu = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return ScaleByFusedLionState(mu=mu)

    def update_fn(updates, state, params=None):
        flat_u, treedef = jax.tree_util.tree_flatten(updates)
        flat_m = treedef.flatten_up_to(state.mu)
        out_u, out_m = [], []
        for g, m in zip(flat_u, flat_m):
            u, m2 = _lion_leaf(g, m, b1, b2)
            out_u.append(u.astype(g.dtype))
            out_m.append(m2)
        return (
            jax.tree_util.tree_unflatten(treedef, out_u),
            ScaleByFusedLionState(mu=jax.tree_util.tree_unflatten(treedef, out_m)),
        )

    return optax.GradientTransformation(init_fn, update_fn)

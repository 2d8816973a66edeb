"""Ring attention: blockwise context parallelism over the ``sp`` axis.

Not present in the reference snapshot (SURVEY.md §2.7 — its long-context
story is Ulysses + sparse attention); provided here because a ppermute ring
over ICI is the idiomatic TPU long-context mechanism: sequence length scales
with the number of chips while K/V blocks stream neighbor-to-neighbor,
overlapping with the blockwise attention compute.

Algorithm (Liu et al., Ring Attention; flash-style online softmax):
each rank holds Q/K/V for its sequence block.  For ``p`` steps, accumulate
blockwise attention of the local Q against the currently-held K/V block
(tracking running max ``m``, denominator ``l``, numerator ``o`` in fp32),
then ``ppermute`` K/V to the next rank on the ring.  Causal masking is by
absolute block position, so later-block K/V contribute nothing to earlier
queries (their mask zeroes the probabilities).

Backward is automatic: the scan + ppermute differentiate (ppermute's
transpose is the inverse permute), and ``jax.checkpoint`` on the step keeps
residual memory at one K/V block instead of ``p``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel import topology as topo

_NEG_INF = -1e30  # finite: avoids (-inf) - (-inf) = nan in the online softmax


def _block_accum(q, k, v, o, m, l, q_start, k_start, causal, scale):
    """One blockwise-attention accumulation step (all stats fp32).

    q: [B, Sq, N, D]; k/v: [B, Sk, N, D]; o: [B, Sq, N, D] fp32;
    m/l: [B, N, Sq] fp32. ``q_start``/``k_start`` are absolute sequence
    offsets of the blocks (traced ints ok).
    """
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_start + jnp.arange(q.shape[1])
        k_pos = k_start + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]          # [Sq, Sk]
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1))      # [B, N, Sq]
    alpha = jnp.exp(m - m_new)                            # correction for old stats
    probs = jnp.exp(scores - m_new[..., None])
    if causal:
        probs = jnp.where(mask[None, None], probs, 0.0)
    l_new = l * alpha + jnp.sum(probs, axis=-1)
    pv = jnp.einsum("bnqk,bknd->bqnd", probs, v.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    o_new = o * jnp.swapaxes(alpha, 1, 2)[..., None] + pv
    return o_new, m_new, l_new


def ring_attention(q, k, v, axis_name=topo.SP_AXIS, causal=True, scale=None,
                   axis_size=None):
    """Ring attention inside a shard_map manual over ``axis_name``.

    q/k/v: local blocks [B, S_local, N, D].  Returns [B, S_local, N, D] in
    q's dtype.  ``axis_size`` must be the static size of the ring (defaults
    to the global mesh's axis size).
    """
    p = axis_size if axis_size is not None else topo.axis_size(axis_name)
    B, S, N, D = q.shape
    if scale is None:
        scale = float(D) ** -0.5
    if p == 1:
        o, m, l = _block_accum(
            q, k, v,
            jnp.zeros((B, S, N, D), jnp.float32),
            jnp.full((B, N, S), _NEG_INF, jnp.float32),
            jnp.zeros((B, N, S), jnp.float32),
            0, 0, causal, scale)
        return (o / jnp.swapaxes(jnp.maximum(l, 1e-30), 1, 2)[..., None]).astype(q.dtype)

    my = jax.lax.axis_index(axis_name)
    q_start = my * S
    # send my K/V to the next rank each step => at step i I hold block (my - i) % p
    perm = [(r, (r + 1) % p) for r in range(p)]

    def step(carry, i):
        o, m, l, k_cur, v_cur = carry
        k_block = (my - i) % p
        # issue the next block's K/V transfer BEFORE the blockwise attention
        # of the current one: the ppermutes have no data dependence on the
        # accumulate, so program order here is what lets the latency-hiding
        # scheduler run the ICI hop under the einsums instead of after them
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        o, m, l = _block_accum(q, k_cur, v_cur, o, m, l,
                               q_start, k_block * S, causal, scale)
        return (o, m, l, k_nxt, v_nxt), None

    init = (
        jnp.zeros((B, S, N, D), jnp.float32),
        jnp.full((B, N, S), _NEG_INF, jnp.float32),
        jnp.zeros((B, N, S), jnp.float32),
        k, v,
    )
    (o, _, l, _, _), _ = jax.lax.scan(
        jax.checkpoint(step), init, jnp.arange(p))
    out = o / jnp.swapaxes(jnp.maximum(l, 1e-30), 1, 2)[..., None]
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, causal=True, scale=None,
                           sp_axis=topo.SP_AXIS):
    """Ring attention for code under plain ``jit``: wraps itself in a
    shard_map manual over ``sp`` (other mesh axes stay GSPMD-auto)."""
    mesh = topo._GLOBAL_MESH
    if mesh is None or mesh.sizes[sp_axis] == 1:
        # no ring: single-block accumulate (numerics identical)
        return ring_attention(q, k, v, axis_name=sp_axis, causal=causal,
                              scale=scale, axis_size=1)
    spec = P(None, sp_axis, None, None)
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=sp_axis, causal=causal,
                          scale=scale, axis_size=mesh.sizes[sp_axis]),
        mesh=mesh.mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # manual over ALL axes, not just sp.  On jax 0.9 this is what a
        # Pallas kernel in the body needs anyway ("Mosaic kernels cannot be
        # automatically partitioned" unless every mesh axis is manual; see
        # ops/pallas_utils.shard_kernel).  Non-sp axes carry replicated
        # operands here, so full-manual is semantically identical.
        axis_names=set(mesh.mesh.axis_names),
        check_vma=False,
    )
    return fn(q, k, v)

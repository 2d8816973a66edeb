"""Rotary position embedding, NeoX-style partial rotation.

Equivalent of the reference's rotary kernels
(``csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu``).  The rotation
is a pure elementwise pattern over the head dim, which XLA fuses into the
surrounding QKV reshape on TPU -- a hand-written Pallas kernel measured no
better, so this is the canonical XLA-fused implementation (the
``ops.transformer`` op surface matches the reference; the *mechanism* is
compiler fusion).
"""

import math

import jax.numpy as jnp


def yarn_inv_freq(rot_dim, base, factor, original_max_position, beta_fast=32,
                  beta_slow=1):
    """YaRN's inverse frequencies [rot_dim / 2] (Peng et al. 2023, as the
    ``transformers`` library computes them): dimensions that turn more than
    ``beta_fast`` times over the original context keep their frequency,
    those that turn less than ``beta_slow`` times have it divided by
    ``factor``, and a linear ramp joins the two."""
    def correction(rotations):
        return (rot_dim * math.log(original_max_position
                                   / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), rot_dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(rot_dim // 2, dtype=jnp.float32)
    freq = base ** (2 * i / rot_dim)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return ramp / (factor * freq) + (1.0 - ramp) / freq


def rotary_tables(positions, rot_dim, base=10000, dtype=jnp.float32,
                  inv_freq=None, scale=None):
    """cos/sin tables [..., seq, 1, rot_dim] for integer positions [..., seq].
    ``inv_freq`` [rot_dim / 2] replaces the plain ``base^(-2i/d)`` (YaRN's);
    ``scale`` multiplies both tables (YaRN's attention factor)."""
    if inv_freq is None:
        inv_freq = 1.0 / (base ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim))
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb), jnp.sin(emb)
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    return (cos[..., None, :].astype(dtype), sin[..., None, :].astype(dtype))


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rotary_pos_emb(q, k, cos, sin):
    """Rotate the first ``rot_dim`` dims of each head of q and k."""
    rot_dim = cos.shape[-1]
    q_rot, q_pass = q[..., :rot_dim], q[..., rot_dim:]
    k_rot, k_pass = k[..., :rot_dim], k[..., rot_dim:]
    q_rot = q_rot * cos + _rotate_half(q_rot) * sin
    k_rot = k_rot * cos + _rotate_half(k_rot) * sin
    return (jnp.concatenate([q_rot, q_pass], -1),
            jnp.concatenate([k_rot, k_pass], -1))


def mrope_tables(positions, sections, rot_dim, base=10000, dtype=jnp.float32):
    """cos/sin tables [B, seq, 1, rot_dim] of multi-axis rotary: positions
    [axes, B, seq] (temporal, height, width), and frequency pair ``i`` of the
    ``rot_dim / 2`` turns by ``base^(-2i/rot_dim)`` times the position on
    the axis whose section ``i`` falls in (``sections``: pairs an axis, in
    order, summing to ``rot_dim / 2``).  Where the axes' positions coincide
    (a text token's) these are ``rotary_tables``'s."""
    if sum(sections) != rot_dim // 2 or len(sections) != positions.shape[0]:
        raise ValueError(f"sections {tuple(sections)} are not the "
                         f"{rot_dim // 2} pairs of {positions.shape[0]} axes")
    inv_freq = 1.0 / (base ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32)
                               / rot_dim))
    axis_of = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections),
                         total_repeat_length=rot_dim // 2)
    # [B, seq, pairs]: each pair reads its own axis's position
    at = jnp.take(jnp.moveaxis(positions.astype(jnp.float32), 0, -1),
                  axis_of, axis=-1)
    freqs = at * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return (jnp.cos(emb)[..., None, :].astype(dtype),
            jnp.sin(emb)[..., None, :].astype(dtype))

"""State-space (Mamba-2) operators: the chunked SSD scan, the causal
depthwise convolution in front of it and the gated group-wise RMSNorm behind.

The recurrence, per head ``h`` with its B/C group ``g`` (a group serves
``heads / groups`` consecutive heads)::

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t        # [P, N] a head
    y_t = H_t C_t + D x_t

``ssd_scan`` computes it in the chunked ("state-space dual") matmul form:
inside a chunk of ``Q`` steps the outputs are a masked ``[Q, Q]`` product,
like attention with a decay in place of a softmax; across chunks a state of
``[P, N]`` a head is carried by a short scan.  Every matmul has the chunk
or the state on its contraction, so it runs on the MXU in the operands' type
with float32 sums; the decays (``dt A``, their running sums, every
``exp``) and the carried state stay float32 whatever the operands are.

One algorithm, two implementations, chosen from the shapes alone
(``pallas_ssd.scan_plan``): where chunk, head width and state width are whole
128-lane blocks and the backend has Pallas kernels, the repo's own kernel
pair (``ops/pallas_ssd.py``: a chunk's ``[Q, Q]`` products and the state stay
in VMEM, forward and backward); anything else runs the plain ``jnp`` form
below, whose backward pass is its own transpose recomputed from the operands
(``jax.checkpoint``): nothing of size ``[chunks, heads, Q, Q]`` is kept.
``telemetry.kernel_paths()["ssd_scan"]`` says which a traced call took.

Heads are independent, so a chip that holds a range of heads (with the
groups that serve them) calls these functions on its range and gets its
part: nothing here knows how many heads the whole layer has.
"""

import jax
import jax.numpy as jnp


def causal_depthwise_conv1d(x, kernel, bias=None):
    """``y[t, c] = sum_k kernel[k, c] x[t - (K-1) + k, c] (+ bias[c])`` over
    ``x`` [B, S, C] with ``kernel`` [K, C]: every channel its own filter of
    width K that sees the present and the K - 1 steps before it (zeros
    before the sequence starts).  K shifted multiply-adds, float32 sums."""
    width = kernel.shape[0]
    seq = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    y = sum(padded[:, k:k + seq].astype(jnp.float32)
            * kernel[k].astype(jnp.float32) for k in range(width))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def causal_headwise_conv1d(x, kernel, bias=None):
    """``y[t, h] = sum_k x[t - (K-1) + k, h] @ kernel[k, h] (+ bias[h])``
    over ``x`` [B, S, heads * d] with ``kernel`` [K, heads, d, d]: a causal
    filter of width K whose channels mix inside each head of d and not
    across heads (zeros before the sequence starts).  A head at a time: its
    slice of the stream and of each shift against the head's own ``[d, d]``,
    in ``x``'s type (a product's sums float32 inside the MXU), the K
    products and the bias summed in float32.  Forward + backward on a v5e
    at ``[4, 8192, 10 x 128]`` bfloat16 (`tools/profile_cca_mix.py`, PR 56;
    each form's products leaving the MXU as float32 in that timing):
    0.97 ms this way, where a head is a lane block and a slice costs
    nothing; 1.77 ms as one ``einsum`` a shift over ``[B, S, heads, d]``
    (a dimension of 10 second to last is another tiling: a copy each way);
    1.31 ms as ``lax.conv_general_dilated`` with ``feature_group_count =
    heads`` (whose transpose refuses a float32 result of bfloat16
    operands)."""
    width, heads, d, _ = kernel.shape
    seq = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    kernel = kernel.astype(x.dtype)
    y = jnp.concatenate([
        sum(jnp.dot(padded[:, k:k + seq, h * d:(h + 1) * d], kernel[k, h],
                    preferred_element_type=x.dtype).astype(jnp.float32)
            for k in range(width)) for h in range(heads)], axis=-1)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def gated_group_rms_norm(y, gate, scale, groups, eps):
    """``GroupRMSNorm(y * silu(gate))``: the gated product normalised over
    each of ``groups`` equal runs of the last axis apart (gate before norm),
    times ``scale`` [C]; float32 inside, ``y``'s type out."""
    g = (y.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32)))
    # a run at a time, as slices of the last axis: a reshape to [..., groups,
    # width / groups] puts a dimension of ``groups`` second to last, which on
    # the TPU is another tiling and a copy of the float32 array each way
    g = jnp.concatenate(
        [run * jax.lax.rsqrt(jnp.mean(jnp.square(run), -1, keepdims=True)
                             + eps) for run in jnp.split(g, groups, axis=-1)],
        axis=-1)
    return (g * scale.astype(jnp.float32)).astype(y.dtype)


def _ssd(x, dt, a, b, c, chunk):
    """The chunked form on whole chunks: x [B, S, Hd, P], dt [B, S, Hd]
    float32, a [Hd] float32 (negative), b and c [B, S, G, N]; S a multiple
    of ``chunk`` -> y [B, S, Hd, P] float32 (without the ``D x`` term)."""
    B, S, Hd, P = x.shape
    G, N = b.shape[2:]
    n, Q, R = S // chunk, chunk, Hd // G
    f32 = jnp.float32
    with jax.named_scope("ssm_scan"):
        # heads as [group, heads of the group]: B and C are a group's
        x = x.reshape(B, n, Q, G, R, P)
        dt = dt.reshape(B, n, Q, G, R)
        b = b.reshape(B, n, Q, G, N)
        c = c.reshape(B, n, Q, G, N)
        # running log-decay inside each chunk, float32, steps minor
        dth = jnp.transpose(dt, (0, 1, 3, 4, 2))            # [B,n,G,R,Q]
        cs = jnp.cumsum(dth * a.reshape(G, R, 1), axis=-1)
        xdt = (x.astype(f32) * dt[..., None]).astype(x.dtype)

        # inside a chunk: y_i += sum_{j<=i} (C_i . B_j) exp(cs_i - cs_j) dt_j x_j
        scores = jnp.einsum("bnigs,bnjgs->bngij", c, b,
                            preferred_element_type=f32)     # [B,n,G,Q,Q]
        lag = cs[..., :, None] - cs[..., None, :]           # [B,n,G,R,i,j]
        decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), lag,
                                  -jnp.inf))                # 0 above diagonal
        mixed = (scores[:, :, :, None] * decay).astype(x.dtype)
        y = jnp.einsum("bngrij,bnjgrp->bnigrp", mixed, xdt,
                       preferred_element_type=f32)

        # what each chunk adds to the state by its end
        last = cs[..., -1]                                  # [B,n,G,R]
        to_end = jnp.transpose(jnp.exp(last[..., None] - cs),
                               (0, 1, 4, 2, 3))             # [B,n,Q,G,R]
        pushed = jnp.einsum(
            "bnjgrp,bnjgs->bngrps",
            (xdt.astype(f32) * to_end[..., None]).astype(x.dtype), b,
            preferred_element_type=f32)                     # [B,n,G,R,P,N]

        # the state entering each chunk: a scan over chunks, float32
        def carry(state, op):
            keep, add = op
            return state * keep[..., None, None] + add, state

        _, entering = jax.lax.scan(
            carry, jnp.zeros((B, G, R, P, N), f32),
            (jnp.moveaxis(jnp.exp(last), 1, 0), jnp.moveaxis(pushed, 1, 0)))
        entering = jnp.moveaxis(entering, 0, 1)             # [B,n,G,R,P,N]

        # what the entering state gives each step of the chunk
        since = jnp.transpose(jnp.exp(cs), (0, 1, 4, 2, 3))  # [B,n,Q,G,R]
        y = y + jnp.einsum("bnigs,bngrps->bnigrp", c,
                           entering.astype(x.dtype),
                           preferred_element_type=f32) * since[..., None]
        return y.reshape(B, S, Hd, P)


def ssd_scan(x, dt, a, b, c, d=None, chunk=128):
    """Mamba-2's selective scan in the chunked matmul form.

    ``x`` [B, S, Hd, P] (the heads' inputs), ``dt`` [B, S, Hd] (step sizes,
    after softplus), ``a`` [Hd] (negative decay rates), ``b`` and ``c``
    [B, S, G, N] (``Hd`` a multiple of ``G``), ``d`` [Hd] or None (the skip)
    -> y [B, S, Hd, P] in ``x``'s type.  Any ``S``: the tail chunk is padded
    with ``dt = 0`` steps, which leave the state as it is and whose outputs
    are dropped."""
    from ..accelerator import get_accelerator
    from ..telemetry.trace import count_kernel_path
    from . import pallas_ssd

    B, S, Hd, P = x.shape
    G, N = b.shape[2:]
    f32 = jnp.float32
    pad = -S % chunk
    dt, a = dt.astype(f32), a.astype(f32)
    ops = (x, dt, b, c)
    if pad:
        ops = tuple(jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                    for t in ops)
    plan = pallas_ssd.scan_plan(Hd, P, G, N, chunk,
                                (x.dtype, b.dtype, c.dtype))
    if plan is None or not get_accelerator().use_pallas_kernels():
        count_kernel_path("ssd_scan", "plain")
        y = jax.checkpoint(_ssd, static_argnums=5)(
            ops[0], ops[1], a, ops[2], ops[3], chunk)[:, :S]
        if d is not None:
            y = y + x.astype(f32) * d.astype(f32)[:, None]
        return y.astype(x.dtype)
    count_kernel_path("ssd_scan", "pallas")
    return _through_the_kernels(ops[0], ops[1], a, ops[2], ops[3], d, plan)[
        :, :S]


def _through_the_kernels(x, dt, a, b, c, d, plan):
    """Whole chunks through ``pallas_ssd``: the operands flat, as the
    kernels read them (reshapes of contiguous dimensions); under a mesh each
    device runs the kernels on its own batch rows."""
    from ..parallel.topology import BATCH_AXES
    from . import pallas_ssd
    from .pallas_utils import shard_kernel

    B, Sp, Hd, P = x.shape
    d = jnp.zeros_like(a) if d is None else d.astype(jnp.float32)
    rows, whole = (BATCH_AXES, None, None), (None,)
    with jax.named_scope("ssm_scan"):
        y = shard_kernel(
            lambda *t: pallas_ssd.ssd_scan_kernel(*t, plan),
            (x.reshape(B, Sp, Hd * P), dt, a, b.reshape(B, Sp, -1),
             c.reshape(B, Sp, -1), d),
            (rows, rows, whole, rows, rows, whole))
        return y.reshape(B, Sp, Hd, P)

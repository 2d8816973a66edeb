"""Plain reference for EvaByte (``EvaByte/EvaByte`` ``config.json``: a
byte-level decoder, 32 layers of hidden 4096, 32 heads of 128, SwiGLU 11008,
a vocabulary of 320 bytes, ``attention_class`` "eva", ``window_size`` 2048,
``chunk_size`` 16, ``num_pred_heads`` 8).  Forward pass, the logits of all
eight slices, the loss and its gradient in straightforward ``jax.numpy``.

The equations, for one sequence ``ids`` [S] with next bytes ``y`` [S]
(``y_t = ids_{t+1}``).  Lines marked *assumed* are not settled by the
``config.json``; each is also in the configuration file's ``assumed``.

* Layer, on a float32 stream ``x`` (``fp32_skip_add``):
  ``h = x + Attn(N1(x))``, ``y = h + MLP(N2(h))``, each sum in float32.
  ``N(x) = x / sqrt(mean(x^2) + eps) * (1 + g)`` (``norm_add_unit_offset``,
  ``rms_norm_eps`` 1e-5; ``g`` starts at 0).
  ``MLP(u) = W_down(silu(W_gate u) * (W_up u))``, width 11008, no bias.
* ``Attn(u)``, per head (``D`` = 128, scale ``s = D^-1/2``, no bias):
  ``q, k, v = W_q u, W_k u, W_v u``; rotary over the whole head of ``q`` and
  ``k`` (``rope_theta`` 100000, no scaling, half-split convention) before
  anything else.  With ``W`` = ``window_size`` and ``C`` = ``chunk_size``,
  position ``t`` lies in window ``w(t) = t // W``; chunk ``c`` holds the
  positions ``cC .. cC + C - 1`` and lies in window ``c // (W / C)``.
  - Chunk summaries, by learned ``mu, phi`` in R^D per head
    (``adaptive_mu_k``, ``adaptive_phi``):
    ``kb_c = sum_{j in c} softmax_{j in c}(mu . k_j) k_j``,
    ``vb_c = sum_{j in c} softmax_{j in c}(phi . k_j) v_j``.
    *Assumed*: the pooling logits are unscaled and read the ROTATED keys.
  - One softmax over two sets: ``L_t = {j : w(j) = w(t), j <= t}`` (exact
    keys, causal, inside the row's own window) and ``R_t = {c : c // (W / C)
    < w(t)}`` (the summaries of every earlier window, all of a window's or
    none).  ``Z_t = sum_{j in L_t} exp(s q_t . k_j) + sum_{c in R_t} exp(s
    q_t . kb_c)``, ``o_t = (sum_{j in L_t} exp(s q_t . k_j) v_j + sum_{c in
    R_t} exp(s q_t . kb_c) vb_c) / Z_t``, statistics in float32
    (``mixedp_attn``), then ``W_o``.
    *Assumed*: a window's summaries are seen only by LATER windows, never by
    later rows of the same window; the first window has ``R_t`` empty; no
    random features, no dropout.
* Head: ``N_f``, then ``logits = u W_head`` with ``W_head`` [4096, 8 * 320]
  in float32 (``fp32_logits``), untied (``tie_word_embeddings`` false);
  slice ``i`` (0..7) predicts byte ``t + 1 + i``.  The loss is the mean of
  the cross entropy over all (position, slice) pairs whose target lies
  inside the sequence (``y`` included: the last eight positions lose the
  targets past its end).
  *Assumed*: the eight slices are ONE matrix and weigh alike in the loss.
* Weights (*assumed*): seeded normal(0, ``init_std`` 0.01275) matrices and
  embedding; ``mu, phi`` normal(0, 1) clipped to [-1, 1] times ``D^-1/2``;
  norm weights ``g`` zero.

A chip's share.  ``layers_held`` layers are run (one pipeline stage), and of
every layer's heads ``attention_heads_held`` from ``first_head_held``: the
columns of ``W_q, W_k, W_v``, the rows of ``W_o`` and the entries of ``mu,
phi`` of those heads; norms, MLP and both tables whole.  What the absent
heads would add through ``W_o`` is left out (``take_heads`` cuts a whole
model's weights so; the halves' outputs of ``W_o`` add up to the whole's).

No kernels, no chunked loss; float32 with
``jax.default_matmul_precision("highest")`` on every matmul of the forward,
recomputed and backward passes.  The scores are materialised a window block
at a time (a window's rows against their window and the earlier summaries)
and the layers, the window blocks and the MLP's row blocks are recomputed in
the backward pass (``jax.checkpoint``: recomputation changes no arithmetic),
so that 16k bytes at the published widths fit a chip.  Lower precisions
exist only as *controls* of the benchmark's output check: ``precision``
("fp8", "bfloat16": every matmul's inputs, and in the backward pass the
incoming gradient too, rounded to that type) and ``islands`` "bfloat16" (the
residual sums, the softmax statistics and the logits rounded to bfloat16:
what a program without its float32 islands computes); ``without`` leaves a
mechanism out (``MECHANISMS``).  Imports nothing from the program under
test; weights come from :func:`init_params`, i.e. from the seed.
"""

import functools
import math

import jax
import jax.numpy as jnp

# what every reference shares: the controls' rounded matmul, and training
# made plain (global norm, clip, Adam's first step)
from benchmarks.reference.gpt_neox_ref import (  # noqa: F401
    PRECISIONS, _einsum, _nest, adam_first_step, clip_scale, global_norm)

#: what ``without`` may leave out: the summaries (``R_t`` empty for every
#: row: windowed attention), the pooling weights (a chunk's plain mean)
MECHANISMS = ("summaries", "pooling")
ISLANDS = ("float32", "bfloat16")
#: rows of the MLP that are live at once in the backward pass
MLP_ROWS = 4096


def depth(cfg):
    return int(cfg.get("layers_held", cfg["num_hidden_layers"]))


def heads(cfg):
    """Heads of a layer that are held here."""
    return int(cfg.get("attention_heads_held", cfg["num_attention_heads"]))


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def slices(cfg):
    return int(cfg["num_pred_heads"])


# ---------------------------------------------------------------- weights
def param_shapes(cfg):
    """The parameter tree as ``{path tuple: shape}`` (the program's flax
    tree)."""
    h, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    n, d = heads(cfg), head_dim(cfg)
    shapes = {("embed_tokens", "embedding"): (v, h)}
    for i in range(depth(cfg)):
        p = f"layers_{i}"
        shapes[(p, "input_norm_weight")] = (h,)
        for name in ("q_proj", "k_proj", "v_proj"):
            shapes[(p, "attn", name, "kernel")] = (h, n * d)
        shapes[(p, "attn", "o_proj", "kernel")] = (n * d, h)
        shapes[(p, "attn", "adaptive_mu_k")] = (n, d)
        shapes[(p, "attn", "adaptive_phi")] = (n, d)
        shapes[(p, "post_norm_weight")] = (h,)
        shapes[(p, "mlp", "gate_proj", "kernel")] = (h, f)
        shapes[(p, "mlp", "up_proj", "kernel")] = (h, f)
        shapes[(p, "mlp", "down_proj", "kernel")] = (f, h)
    shapes[("final_norm_weight",)] = (h,)
    shapes[("lm_head_kernel",)] = (h, slices(cfg) * v)
    return shapes


def num_params(cfg, with_input_embedding=True):
    n = sum(math.prod(s) for s in param_shapes(cfg).values())
    if not with_input_embedding:
        n -= cfg["vocab_size"] * cfg["hidden_size"]
    return n


def layer_matmul_params(cfg):
    h = cfg["hidden_size"]
    return 4 * h * heads(cfg) * head_dim(cfg) + 3 * h * cfg["intermediate_size"]


def pairs_needed(cfg, seq_len):
    """(row, key) pairs one head's rows see in a sequence of whole windows:
    the keys of a row's window up to itself, and ``W / C`` summaries for
    every earlier window."""
    w, c = int(cfg["window_size"]), int(cfg["chunk_size"])
    n = seq_len // w
    return n * w * (w + 1) // 2 + w * (w // c) * n * (n - 1) // 2


def flops_per_token(cfg, seq_len):
    """Forward + backward FLOPs one trained token needs: ``6 x`` every
    matmul weight it passes (the input embedding is a gather), plus EVA's
    scores and values, ``12 D`` a (row, key) pair a head (two matmuls
    forward, twice that backward: the customary count, over the pairs the
    equations need and not the square), plus the summaries (two pooling
    logits and two pooled sums a key: ``24 D`` a head).  Recomputed
    operations do not count."""
    n, d, layers = heads(cfg), head_dim(cfg), depth(cfg)
    matmul = (layers * layer_matmul_params(cfg)
              + cfg["hidden_size"] * slices(cfg) * cfg["vocab_size"])
    return (6 * matmul + 12 * layers * n * d * pairs_needed(cfg, seq_len)
            / seq_len + 24 * layers * n * d)


def init_params(cfg, seed):
    """Seeded float32 weights, made on the default device in ONE jitted
    call."""
    shapes = param_shapes(cfg)
    std, d = float(cfg.get("init_std", 0.01275)), head_dim(cfg)

    def make(key):
        flat = {}
        for i, (path, shape) in enumerate(shapes.items()):
            draw = jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32)
            if path[-1].endswith("norm_weight"):
                flat[path] = jnp.zeros(shape, jnp.float32)
            elif path[-1].startswith("adaptive_"):
                flat[path] = jnp.clip(draw, -1.0, 1.0) * d ** -0.5
            else:
                flat[path] = std * draw
        return _nest(flat)

    # any whole number up to a little over 2**31 (and beyond): two words
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(make)(key)


def take_heads(params, cfg, first, held):
    """A whole model's weights cut to the heads ``first .. first + held - 1``
    of every layer: the share a chip of ``num_attention_heads / held`` holds."""
    d = head_dim(cfg)
    cols = slice(first * d, (first + held) * d)
    out = dict(params)
    for i in range(depth(cfg)):
        layer = dict(params[f"layers_{i}"])
        attn = dict(layer["attn"])
        for name in ("q_proj", "k_proj", "v_proj"):
            attn[name] = {"kernel": attn[name]["kernel"][:, cols]}
        attn["o_proj"] = {"kernel": attn["o_proj"]["kernel"][cols]}
        for name in ("adaptive_mu_k", "adaptive_phi"):
            attn[name] = attn[name][first:first + held]
        layer["attn"] = attn
        out[f"layers_{i}"] = layer
    return out


# ---------------------------------------------------------------- forward
def _island(x, islands):
    """A value of a float32 island, or rounded as a program without it."""
    return x if islands == "float32" else x.astype(jnp.bfloat16).astype(
        jnp.float32)


def _dense(x, kernel, precision):
    return _einsum("si,io->so", x, kernel.astype(jnp.float32), precision)


def _rms_norm(x, g, eps):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
            * (1.0 + g.astype(jnp.float32)))


def _rotary(x, positions, base):
    """Rotary embedding over the whole head, half-split convention.
    x: [S, N, D]; positions: [S]."""
    d = x.shape[-1]
    inv_freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def chunk_summaries(k, v, mu, phi, chunk, without=()):
    """``k, v`` [S, N, D] -> ``(kb, vb)`` [S / C, N, D]."""
    s, n, d = k.shape
    kc, vc = (t.reshape(s // chunk, chunk, n, d) for t in (k, v))
    if "pooling" in without:
        return kc.mean(axis=1), vc.mean(axis=1)
    wk = jax.nn.softmax(jnp.sum(kc * mu, axis=-1), axis=1)
    wv = jax.nn.softmax(jnp.sum(kc * phi, axis=-1), axis=1)
    return (jnp.sum(wk[..., None] * kc, axis=1),
            jnp.sum(wv[..., None] * vc, axis=1))


def eva_attention(q, k, v, kb, vb, window, chunk, precision="float32",
                  islands="float32", without=()):
    """``q, k, v`` [S, N, D] (rotated), the summaries [S / C, N, D] ->
    [S, N, D]: one softmax a row over its window's keys up to itself and the
    summaries of every earlier window, a window block of rows at a time."""
    s, n, d = q.shape
    per = window // chunk
    scale = 1.0 / math.sqrt(d)

    def block(qw, kw, vw, kbw, vbw):
        rows = qw.shape[0]
        local = _einsum("qnd,knd->nqk", qw, kw, precision) * scale
        seen = jnp.arange(rows)[:, None] >= jnp.arange(rows)[None, :]
        local = jnp.where(seen[None], local, -jnp.inf)
        scores = local
        if kbw.shape[0]:    # the first window sees no summary
            far = _einsum("qnd,cnd->nqc", qw, kbw, precision) * scale
            scores = jnp.concatenate([local, far], axis=-1)
        scores = _island(scores, islands)
        if islands == "float32":
            probs = jax.nn.softmax(scores, axis=-1)
        else:
            probs = jax.nn.softmax(scores.astype(jnp.bfloat16),
                                   axis=-1).astype(jnp.float32)
        out = _einsum("nqk,knd->qnd", probs[..., :rows], vw, precision)
        if kbw.shape[0]:
            out = out + _einsum("nqc,cnd->qnd", probs[..., rows:], vbw,
                                precision)
        return out

    out = []
    for w0 in range(0, s, window):
        rows = slice(w0, min(w0 + window, s))
        # the summaries of the windows BEFORE this one: w0 / C of them
        far = 0 if "summaries" in without else (w0 // window) * per
        out.append(jax.checkpoint(block)(q[rows], k[rows], v[rows], kb[:far],
                                         vb[:far]))
    return jnp.concatenate(out)


def _attention(x, p, cfg, positions, precision, islands, without):
    s = x.shape[0]
    n, d = heads(cfg), head_dim(cfg)
    q, k, v = (_dense(x, p[name]["kernel"], precision).reshape(s, n, d)
               for name in ("q_proj", "k_proj", "v_proj"))
    q = _rotary(q, positions, cfg["rope_theta"])
    k = _rotary(k, positions, cfg["rope_theta"])
    chunk = int(cfg["chunk_size"])
    kb, vb = chunk_summaries(k, v, p["adaptive_mu_k"].astype(jnp.float32),
                             p["adaptive_phi"].astype(jnp.float32), chunk,
                             without)
    out = eva_attention(q, k, v, kb, vb, int(cfg["window_size"]), chunk,
                        precision, islands, without)
    return _dense(out.reshape(s, n * d), p["o_proj"]["kernel"], precision)


def _mlp(u, p, precision):
    def rows(ub):
        return _dense(
            jax.nn.silu(_dense(ub, p["gate_proj"]["kernel"], precision))
            * _dense(ub, p["up_proj"]["kernel"], precision),
            p["down_proj"]["kernel"], precision)

    blocks = max(1, u.shape[0] // MLP_ROWS)
    if blocks == 1 or u.shape[0] % blocks:
        return rows(u)
    return jnp.concatenate([jax.checkpoint(rows)(ub)
                            for ub in jnp.split(u, blocks)])


def _layer(x, p, cfg, positions, precision, islands, without):
    eps = cfg["rms_norm_eps"]
    a = _attention(_rms_norm(x, p["input_norm_weight"], eps), p["attn"], cfg,
                   positions, precision, islands, without)
    x = _island(x + a, islands)
    m = _mlp(_rms_norm(x, p["post_norm_weight"], eps), p["mlp"], precision)
    return _island(x + m, islands)


def attention_output(params, cfg, layer, u, precision="float32"):
    """One layer's attention sublayer alone on ``u`` [S, H] (its norm's
    output) -> ``W_o``'s output [S, H]: what the shares of the heads add up
    to."""
    with jax.default_matmul_precision("highest"):
        return _attention(u, params[f"layers_{layer}"]["attn"], cfg,
                          jnp.arange(u.shape[0]), precision, "float32", ())


def hidden_states(params, cfg, ids, precision="float32", islands="float32",
                  without=(), remat=False):
    """The closing norm's output [S, H] (float32) for ONE sequence [S]."""
    if precision not in PRECISIONS or islands not in ISLANDS:
        raise ValueError(f"precision {precision!r} / islands {islands!r}")
    if set(without) - set(MECHANISMS):
        raise ValueError(f"without {without!r}: {MECHANISMS}")
    if ids.shape[0] % int(cfg["chunk_size"]):
        raise ValueError("a sequence is whole chunks")
    layer = functools.partial(_layer, cfg=cfg, positions=jnp.arange(
        ids.shape[0]), precision=precision, islands=islands,
        without=tuple(without))
    if remat:
        layer = jax.checkpoint(layer)
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"].astype(jnp.float32)[ids]
        for i in range(depth(cfg)):
            x = layer(x, params[f"layers_{i}"])
        return _rms_norm(x, params["final_norm_weight"], cfg["rms_norm_eps"])


def logits(params, cfg, ids, precision="float32", islands="float32",
           without=(), remat=False):
    """The logits of all the slices [S, K, V] for one sequence: slice ``i``
    of position ``t`` scores byte ``t + 1 + i``."""
    h = hidden_states(params, cfg, ids, precision, islands, without, remat)
    with jax.default_matmul_precision("highest"):
        lg = _island(_dense(h, params["lm_head_kernel"], precision), islands)
    return lg.reshape(ids.shape[0], slices(cfg), cfg["vocab_size"])


def targets(labels, k):
    """``labels`` [S] (byte ``t + 1`` at ``t``) -> (targets [S, K]: byte
    ``t + 1 + i`` in slice ``i``, 0 past the end; which exist [S, K])."""
    s = labels.shape[0]
    ahead = jnp.arange(s)[:, None] + jnp.arange(k)[None, :]
    inside = ahead < s
    return jnp.where(inside, labels[jnp.minimum(ahead, s - 1)], 0), inside


def token_logprobs(params, cfg, ids, labels, precision="float32",
                   islands="float32", without=(), remat=False):
    """For one sequence: the log-probability of every target [S, K] (0
    where a position has no such target) and which exist [S, K]."""
    lg = logits(params, cfg, ids, precision, islands, without, remat)
    want, inside = targets(labels, slices(cfg))
    lp = (jnp.take_along_axis(lg, want[..., None], axis=-1)[..., 0]
          - jax.nn.logsumexp(lg, axis=-1))
    return jnp.where(inside, lp, 0.0), inside


def loss(params, cfg, ids, labels, precision="float32"):
    """Mean cross entropy over every (position, slice) pair of a batch
    [B, S] whose target exists -> (loss, [log-probs [S, K] per sequence])."""
    fn = jax.jit(lambda p, x, y: token_logprobs(p, cfg, x, y, precision))
    rows = [fn(params, ids[b], labels[b]) for b in range(ids.shape[0])]
    total = sum(jnp.sum(lp) for lp, _ in rows)
    count = sum(jnp.sum(inside) for _, inside in rows)
    return -total / count, [lp for lp, _ in rows]


# --------------------------------------------------------------- training
def loss_and_grads(params, cfg, ids, labels, precision="float32",
                   islands="float32", without=()):
    """The loss over a batch [B, S] and its gradient, one sequence at a
    time with every layer recomputed in the backward pass, summed.
    -> (loss, gradient tree, the first sequence's log-probs [S, K])."""
    n, k = ids.shape[0], slices(cfg)
    # every sequence has the same targets inside: 8 S less the 28 past it
    count = n * int(jnp.sum(targets(labels[0], k)[1]))

    def one(p, x, y):
        lp, _ = token_logprobs(p, cfg, x, y, precision, islands,
                               tuple(without), remat=True)
        return -jnp.sum(lp) / count, lp

    @functools.partial(jax.jit, donate_argnums=1)
    def add(p, total, x, y):
        (part, lp), g = jax.value_and_grad(one, has_aux=True)(p, x, y)
        return jax.tree_util.tree_map(jnp.add, total, g), part, lp

    total = jax.tree_util.tree_map(jnp.zeros_like, params)
    mean, first = 0.0, None
    for b in range(n):
        total, part, lp = add(params, total, ids[b], labels[b])
        mean = mean + part
        first = lp if first is None else first
    return mean, total, first

"""The main path's Pallas kernels, compiled by the TPU compiler for a
*described* v5e chip (nothing is attached, nothing runs).

Interpret-mode tests execute kernel bodies on the CPU but never lower them
for Mosaic, so a block shape the TPU lowering refuses, a kernel that
overflows VMEM, or one GSPMD cannot partition passes them all.  These cases
ask the installed TPU compiler at the real widths.  A compile that passes
is not a chip run.

The topology is described inside a module-scoped fixture and only there:
one process at a time may load libtpu, so it must not happen while any
module is imported (every xdist worker imports every test file).
``interpret_mode()`` sees the CPU backend here, so it is patched in the
modules that imported it by name; the program gets no option for this.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from deeperspeed_tpu.ops import pallas_gmm, pallas_ssd, pallas_utils, ssm
from deeperspeed_tpu.ops.attention import core as attn_core
from deeperspeed_tpu.ops.attention import (cca, dsa, eva, paged, pallas_cca,
                                           pallas_dsa, pallas_eva,
                                           pallas_eva_pool, pallas_flash,
                                           pallas_flash_mla)
from deeperspeed_tpu.ops.quantizer import fused as qfused
from deeperspeed_tpu.ops.sampling import topk
from deeperspeed_tpu.ops.transformer import normalize
from deeperspeed_tpu.parallel import topology as topo_mod
from deeperspeed_tpu.telemetry.hlo_cost import pallas_kernel_calls

_BY_NAME = (pallas_utils, pallas_flash, paged, qfused, topk, pallas_ssd,
            pallas_gmm, pallas_eva, pallas_eva_pool, pallas_dsa, pallas_cca,
            pallas_flash_mla)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable for a described chip is written to the persistent cache
    # but cannot be read back without the chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    saved = [(m, m.interpret_mode) for m in _BY_NAME]
    for m in _BY_NAME:
        m.interpret_mode = lambda: False
    yield desc
    for m, fn in saved:
        m.interpret_mode = fn
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
    # traces made while interpret_mode() said "chip" must not outlive it
    jax.clear_caches()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, **kw_shapes):
    """Compile ``fn`` for the shapes' (described) devices; the HLO text."""
    text = jax.jit(fn).lower(*shapes, **kw_shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _kernel_operand_shapes(text):
    """Operand shapes of every Pallas kernel call in compiled HLO text."""
    return [call for calls in pallas_kernel_calls(text).values()
            for call in calls]


def _scoped_vmem(text):
    """Of compiled text with ONE kernel call: the scoped VMEM the call
    states and what Mosaic laid out, in bytes."""
    import re

    call, = (line for line in text.splitlines() if "tpu_custom_call" in line)
    stated, used = (int(size) for size in re.findall(
        r'"memory_space":"1","offset":"0","size":"(\d+)"', call))
    return stated, used


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _sum_grad(fn, n_diff):
    """fwd+bwd of ``fn`` w.r.t. its first ``n_diff`` arguments."""
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
                    argnums=tuple(range(n_diff)))


# ------------------------------------------------------------------ one chip
@pytest.mark.parametrize("shape,dtype", [
    ((2, 2048, 16, 64), jnp.bfloat16),
    ((2, 2048, 32, 128), jnp.bfloat16),
    # the benchmark cells' own calls: train-410m, train-160m
    ((8, 2048, 16, 64), jnp.bfloat16),
    ((16, 1024, 12, 64), jnp.bfloat16),
    # train-ouro-2.6b-loop4's heads of 128: a head is a lane block (half
    # its micro-batch, and its own call)
    ((2, 4096, 16, 128), jnp.bfloat16),
    ((4, 4096, 16, 128), jnp.bfloat16),
    # heads of 96 (NeoX-20B) and the longest length whose backward still
    # keeps a head's q side in VMEM; fp32 once; a padded length
    ((4, 2048, 8, 96), jnp.bfloat16),
    ((2, 8192, 8, 128), jnp.bfloat16),
    # train-laguna-s-ep32-8k's full layers: 24 query heads of 128 (GQA
    # groups of 6 over the 4 KV heads held)
    ((2, 8192, 24, 128), jnp.bfloat16),
    ((2, 1024, 8, 64), jnp.float32),
    ((2, 1000, 8, 64), jnp.bfloat16),
    # two heads to a lane block over several owner blocks (running
    # statistics per head, a dq accumulator), and the folded fallbacks
    # (heads of 32; an odd head count at D = 64)
    ((1, 4096, 2, 64), jnp.bfloat16),
    ((2, 2048, 8, 32), jnp.bfloat16),
    ((2, 2048, 3, 64), jnp.bfloat16),
])
def test_flash_mha_fwd_bwd(one_chip, shape, dtype):
    """Forward + backward are exactly two kernel calls: what the benchmark's
    ``flash_attention_roofline`` counts on (one event per forward, one per
    backward, no third kernel under the scope).  Their operands are the
    projections' own ``[B, S, N*D]`` wherever heads are whole lane blocks,
    and ``[B*N, S, D]`` where not."""
    B, S, N, D = shape
    q = _sds(shape, dtype, one_chip)
    text = _compile(_sum_grad(pallas_flash.mha, 3), q, q, q)
    plan = pallas_flash.tile_plan(S, D, dtype, N=N)
    assert plan.resident_bwd
    calls = _kernel_operand_shapes(text)
    assert len(calls) == 2
    sp = -(-S // plan.block) * plan.block
    want = (B, sp, N * D) if plan.group else (B * N, sp, D)
    assert all(want in operands for operands in calls), (want, calls)
    # the backward reads the lse as the forward wrote it: one float a row,
    # rows on lanes, the heads of a lane block together
    heads = max(plan.group, 1)
    stats = [shape for operands in calls for shape in operands
             if shape != want]
    assert stats == [(B * N // heads, heads, sp)], stats


@pytest.fixture
def on_the_chip(topo, monkeypatch):
    """Models dispatch to the Pallas kernels, as on the chip."""
    from deeperspeed_tpu.accelerator import get_accelerator

    monkeypatch.setattr(type(get_accelerator()), "use_pallas_kernels",
                        lambda self: True)


def _model_gradient_passes(model, loss, one_chip):
    """``count_kernel_passes`` of the gradient of ``loss(params, ids)``,
    compiled for the chip, over [2, 256] tokens."""
    from deeperspeed_tpu.telemetry import count_kernel_passes

    ids = jnp.zeros((2, 256), jnp.int32)
    params = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(1), ids)))
    return count_kernel_passes(_compile(
        jax.grad(loss), params, _sds(ids.shape, ids.dtype, one_chip)))


def test_recomputed_gpt_neox_keeps_the_flash_kernels_residuals(
        one_chip, on_the_chip):
    """What ``telemetry.kernel_passes()`` reads in the compiled step: with
    remat each layer's kernel runs once forward and once backward, the
    recomputed block has the kernel's output and lse (``gpt_neox.py``'s
    remat policy); the fused norms are still recomputed."""
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    model = GPTNeoX(GPTNeoXConfig(
        vocab_size=256, hidden_size=256, num_heads=4, num_layers=2,
        max_seq_len=256, remat=True, dtype=jnp.bfloat16))
    passes = _model_gradient_passes(
        model, lambda p, ids: model.apply(p, ids).astype(jnp.float32).mean(),
        one_chip)
    assert passes["flash_attention"] == dict(forward=2, recomputed=0,
                                             backward=2)
    assert passes["fused_norm"]["recomputed"] == 4


def test_recomputed_looped_model_runs_the_forward_kernel_again(
        one_chip, on_the_chip):
    """``Ouro`` keeps its policy-less wrap: T * L = 3 * 2 recomputed calls,
    counted through the scan over passes."""
    from deeperspeed_tpu.models.ouro import Ouro, OuroConfig

    model = Ouro(OuroConfig.tiny(
        hidden_size=256, num_heads=2, num_kv_heads=2, intermediate_size=256,
        max_seq_len=256, ce_chunk_tokens=256, total_ut_steps=3, remat=True,
        dtype=jnp.bfloat16))
    loss = model.loss_fn()
    passes = _model_gradient_passes(
        model, lambda p, ids: loss(
            p["params"], {"input_ids": ids, "labels": ids})[0], one_chip)
    assert passes["flash_attention"] == dict(forward=6, recomputed=6,
                                             backward=6)


def test_recomputed_hybrid_model_compiles_with_its_kernels(
        one_chip, on_the_chip):
    """``NemotronH`` (a Mamba-2, an expert and an attention layer, remat):
    the chip's compiler takes the dropless walk (a loop as long as the slots
    routed here need, forward and backward); the attention layer keeps the
    flash kernel's residuals (one forward, one backward); the Mamba layer's
    scan is the kernel pair, which keeps nothing across the remat wrap: a
    forward call, the recomputed one (it writes the states) and the backward
    call for each M layer."""
    from deeperspeed_tpu.models.nemotron_h import NemotronH, NemotronHConfig

    model = NemotronH(NemotronHConfig.tiny(
        pattern="EM*", hidden_size=256, mamba_num_heads=4, mamba_head_dim=64,
        n_groups=2, ssm_state_size=128, chunk_size=128, num_heads=2,
        num_kv_heads=1, head_dim=128, moe_latent_size=128,
        moe_intermediate_size=256, moe_shared_expert_intermediate_size=256,
        max_seq_len=256, ce_chunk_tokens=256,
        remat=True, dtype=jnp.bfloat16))
    loss = model.loss_fn()
    passes = _model_gradient_passes(
        model, lambda p, ids: loss(
            p["params"], {"input_ids": ids, "labels": ids})[0], one_chip)
    assert passes["flash_attention"] == dict(forward=1, recomputed=0,
                                             backward=1)
    m_layers = model.config.pattern.count("M")
    assert passes["ssd_scan"] == dict(forward=m_layers, recomputed=m_layers,
                                      backward=m_layers)


@pytest.mark.parametrize("heads,head_dim,groups", [
    (32, 64, 2),        # train-nemotron3-super-ep64-8k: two heads a lane block
    (8, 128, 2),        # a head a lane block
    (128, 64, 8),       # the whole layer: four head blocks of two groups
])
def test_ssd_scan_fwd_bwd(one_chip, heads, head_dim, groups):
    """The scan's kernel pair at the hybrid cell's length and batch (``x``
    [2, 8192, 2048], ``b``, ``c`` [2, 8192, 256], chunk 128 in its first
    case) inside the default scoped-VMEM limit: an undifferentiated forward
    is one call that writes no states; forward + backward are two calls,
    and the states between them are the operands' type."""
    B, S, N, chunk = 2, 8192, 128, 128
    dtype = jnp.bfloat16
    plan = pallas_ssd.scan_plan(heads, head_dim, groups, N, chunk,
                                (dtype,) * 3)
    assert plan is not None
    assert pallas_flash._vmem_limit(
        pallas_ssd._vmem_need(plan, 2, wide_tensors=3)) is None

    scan = functools.partial(ssm._through_the_kernels, plan=plan)

    args = (_sds((B, S, heads, head_dim), dtype, one_chip),
            _sds((B, S, heads), jnp.float32, one_chip),
            _sds((heads,), jnp.float32, one_chip),
            _sds((B, S, groups, N), dtype, one_chip),
            _sds((B, S, groups, N), dtype, one_chip),
            _sds((heads,), jnp.float32, one_chip))
    flat = (B, S, heads * head_dim)
    calls = _kernel_operand_shapes(_compile(scan, *args))
    assert len(calls) == 1 and flat in calls[0], calls
    assert (B, S // chunk, heads * head_dim, N) not in calls[0]
    calls = _kernel_operand_shapes(_compile(_sum_grad(scan, 6), *args))
    assert len(calls) == 2, calls
    assert all(flat in operands for operands in calls), calls
    assert sum((B, S // chunk, heads * head_dim, N) in operands
               for operands in calls) == 1, calls


def test_flash_mha_long_sequence_two_pass(one_chip):
    """S = 16k: the forward still holds the whole k/v of a head; the
    backward's q side no longer fits and it goes two-pass (three calls)."""
    q = _sds((1, 16384, 4, 128), jnp.bfloat16, one_chip)
    text = _compile(_sum_grad(pallas_flash.mha, 3), q, q, q)
    assert not pallas_flash.tile_plan(16384, 128, jnp.bfloat16,
                                      N=4).resident_bwd
    assert len(_kernel_operand_shapes(text)) == 3


def test_flash_mha_two_heads_two_pass(one_chip):
    """Two heads to a block at S = 16k: each head's statistics double what
    the one-kernel backward would hold, so it goes two-pass, a row of
    ``delta`` per head."""
    q = _sds((1, 16384, 2, 64), jnp.bfloat16, one_chip)
    text = _compile(_sum_grad(pallas_flash.mha, 3), q, q, q)
    plan = pallas_flash.tile_plan(16384, 64, jnp.bfloat16, N=2)
    assert plan.group == 2 and not plan.resident_bwd
    assert len(_kernel_operand_shapes(text)) == 3


@pytest.mark.parametrize("shape,window", [
    # train-mellum2-ep4-8k's windowed layers (half its micro-batch, and its
    # own call): 32 query heads of 128 on GQA's copy of k and v
    ((2, 8192, 32, 128), 1024), ((4, 8192, 32, 128), 1024),
    # Mistral's window; a window within a block at two heads a lane block;
    # a padded length and a window that is no multiple of a tile
    ((1, 8192, 8, 128), 4096), ((2, 2048, 16, 64), 256),
    ((2, 1000, 8, 64), 200),
    # train-laguna-s-ep32-8k's windowed layers: 36 query heads of 128 (GQA
    # groups of 9 over the 4 KV heads held) under a window of a quarter block
    ((2, 8192, 36, 128), 512),
])
def test_flash_mha_window_fwd_bwd(one_chip, shape, window):
    """A windowed call is the same two kernel calls on the same operands as
    a full one, under a scope of its own (which names its events in a
    device trace), within the scoped-VMEM limit the full call states."""
    B, S, N, D = shape
    q = _sds(shape, jnp.bfloat16, one_chip)
    text = _compile(_sum_grad(functools.partial(
        pallas_flash.mha, window=window), 3), q, q, q)
    plan = pallas_flash.tile_plan(S, D, jnp.bfloat16, N=N, window=window)
    assert plan.window == window and plan.resident_bwd
    assert plan._replace(window=0) == pallas_flash.tile_plan(
        S, D, jnp.bfloat16, N=N)
    calls = _kernel_operand_shapes(text)
    assert len(calls) == 2
    sp = -(-S // plan.block) * plan.block
    assert all((B, sp, N * D) in operands for operands in calls), calls
    # both kernels are dispatched under the windowed scope, none under the
    # full call's (by pass they are counted in a model's program, below)
    names = [line.split('op_name="')[1].split('"')[0]
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(names) == 2 and all(
        "flash_attention_window" in n for n in names), names


@pytest.mark.parametrize("shape,kv,window", [
    # train-mellum2-ep4-8k: 32 query heads over the 4 KV heads held, a
    # window of 1024 on three layers of four
    ((4, 8192, 32, 128), 4, 1024), ((4, 8192, 32, 128), 4, None),
    # train-laguna-s-ep32-8k: 36 windowed | 24 full query heads over 4
    ((2, 8192, 36, 128), 4, 512), ((2, 8192, 24, 128), 4, None),
    # the hybrid cell's one layer; one KV head; one block to the head
    ((2, 8192, 8, 128), 2, None), ((2, 4096, 8, 128), 1, None),
    ((2, 2048, 4, 128), 2, 300),
    # train-zaya1-8b-ep2-8k: the latent's 8 query heads over 2 KV heads
    ((4, 8192, 8, 128), 2, None),
])
def test_flash_mha_grouped_query_fwd_bwd(one_chip, shape, kv, window):
    """Grouped-query heads are the same two kernel calls: q, o, do and dq at
    the query heads' width, k, v, dk and dv at the KV heads' and nowhere at
    the query heads' (no copy before the kernels, no sum after them), with
    the KV head's fp32 dk and dv sums counted in the limit the call
    states."""
    B, S, N, D = shape
    q = _sds(shape, jnp.bfloat16, one_chip)
    k = _sds((B, S, kv, D), jnp.bfloat16, one_chip)
    text = _compile(_sum_grad(functools.partial(
        pallas_flash.mha, window=window), 3), q, k, k)
    plan = pallas_flash.tile_plan(S, D, jnp.bfloat16, N=N, window=window,
                                  kv_heads=kv)
    assert plan.resident_bwd and plan.group == 1
    sp = -(-S // plan.block) * plan.block
    assert pallas_flash._bwd_resident_bytes(
        sp, D, 2, grouped=True) - pallas_flash._bwd_resident_bytes(
            sp, D, 2) == 2 * sp * D * 4
    fwd, bwd = sorted(_kernel_operand_shapes(text), key=len)
    wide, thin = (B, sp, N * D), (B, sp, kv * D)
    assert fwd == [wide, thin, thin], fwd                       # q, k, v
    assert bwd[:5] == [wide, thin, thin, wide, wide], bwd       # ... do, o
    # nothing is copied out to the query heads or summed back over a group
    assert "[%d,%d,%d,%d,%d]" % (B, S, kv, N // kv, D) not in text


def test_flash_mha_grouped_query_long_sequence_two_pass(one_chip):
    """S = 16k over 4 KV heads: the two-pass backward's three calls, the
    dk/dv pass walking a KV head's query heads."""
    q = _sds((1, 16384, 8, 128), jnp.bfloat16, one_chip)
    k = _sds((1, 16384, 4, 128), jnp.bfloat16, one_chip)
    text = _compile(_sum_grad(functools.partial(
        pallas_flash.mha, window=2048), 3), q, k, k)
    assert not pallas_flash.tile_plan(16384, 128, jnp.bfloat16, N=8,
                                      kv_heads=4).resident_bwd
    calls = _kernel_operand_shapes(text)
    assert len(calls) == 3
    assert all((1, 16384, 4 * 128) in operands for operands in calls)


def test_flash_mha_window_long_sequence_two_pass(one_chip):
    """S = 16k under a window: the two-pass backward's three calls."""
    q = _sds((1, 16384, 4, 128), jnp.bfloat16, one_chip)
    text = _compile(_sum_grad(functools.partial(
        pallas_flash.mha, window=1024), 3), q, q, q)
    assert len(_kernel_operand_shapes(text)) == 3


def test_recomputed_mellum_takes_the_kernel_for_every_windowed_layer(
        one_chip, on_the_chip):
    """``Mellum`` (three windowed layers and a full one, remat): every
    windowed layer's attention is a ``flash_attention_window`` kernel call
    forward and one backward, the full layer's a ``flash_attention`` pair;
    the remat wrap keeps the kernels' residuals (nothing recomputed); and
    the chip's compiler takes the dropless walk with softmax scoring and
    gated experts in its grouped form: a layer's two grouped matmuls
    forward, and backward those two again (a chunk's rows recomputed), the
    two transposed ones and the two outer products; the recomputed layer's
    forward walk is dead code (the walk keeps only its inputs)."""
    from deeperspeed_tpu.models.mellum import Mellum, MellumConfig

    model = Mellum(MellumConfig.tiny(
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        hidden_size=256, num_heads=2, num_kv_heads=1, head_dim=128,
        sliding_window=128, moe_intermediate_size=128, max_seq_len=256,
        ce_chunk_tokens=256, remat=True, dtype=jnp.bfloat16))
    loss = model.loss_fn()
    passes = _model_gradient_passes(
        model, lambda p, ids: loss(
            p["params"], {"input_ids": ids, "labels": ids})[0], one_chip)
    assert passes["flash_attention_window"] == dict(forward=3, recomputed=0,
                                                    backward=3)
    assert passes["flash_attention"] == dict(forward=1, recomputed=0,
                                             backward=1)
    assert passes["grouped_matmul"] == dict(forward=4 * 2, recomputed=0,
                                            backward=4 * 6)
    # the sorted slots' buffer of each pass is handed over unwritten
    assert passes["unwritten"] == dict(forward=4, recomputed=0, backward=4)


def test_recomputed_laguna_hands_its_kernels_k_and_v_at_the_kv_heads(
        one_chip, on_the_chip):
    """``Laguna`` (a full layer with 4 query heads, two windowed ones with 6,
    over 2 KV heads of 128, remat): a kernel call forward and one backward a
    layer by its kind, none recomputed, as before the kernel addressed KV
    heads by the query head's group; and in the compiled step no value is
    a copy of k or v at the query heads (``[B, S, 2, 3 | 2, 128]``)."""
    from deeperspeed_tpu.models.laguna import Laguna, LagunaConfig
    from deeperspeed_tpu.telemetry import count_kernel_passes

    model = Laguna(LagunaConfig.tiny(
        hidden_size=256, head_dim=128, sliding_window=128,
        intermediate_size=256, moe_intermediate_size=128,
        shared_expert_intermediate_size=128, max_seq_len=256,
        ce_chunk_tokens=256, remat=True, dtype=jnp.bfloat16))
    loss = model.loss_fn()
    ids = jnp.zeros((2, 256), jnp.int32)
    params = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(1), ids)))
    text = _compile(jax.grad(lambda p, ids: loss(
        p["params"], {"input_ids": ids, "labels": ids})[0]),
        params, _sds(ids.shape, ids.dtype, one_chip))
    passes = count_kernel_passes(text)
    assert passes["flash_attention_window"] == dict(forward=2, recomputed=0,
                                                    backward=2)
    assert passes["flash_attention"] == dict(forward=1, recomputed=0,
                                             backward=1)
    assert "[2,256,2,3,128]" not in text and "[2,256,2,2,128]" not in text
    calls = pallas_kernel_calls(text)
    for scope, wide in (("flash_attention_window", 6), ("flash_attention", 4)):
        assert all(operands[:3] == [(2, 256, wide * 128), (2, 256, 256),
                                    (2, 256, 256)]
                   for operands in calls[scope]), calls[scope]


@pytest.mark.parametrize("shape", [
    (1, 16384, 16, 128),      # train-evabyte-tp2-16k's call: 16 heads held
    (1, 4096, 2, 128),        # two windows
])
def test_eva_attention_fwd_bwd(one_chip, shape):
    """EVA attention at the cell's shape (eight windows of 2048 bytes, 1024
    summaries of 16-byte chunks, heads of 128): four kernel calls, the
    summaries' pair under the scope ``eva_pool`` from the projections' own
    ``[B, S, N*D]`` to ``[B, S / 16, N*D]`` and the attention's under
    ``eva_attention`` on both; the backward reads the lse as the forward
    wrote it, one float a row; no buffer of the program is a float32 copy of
    k or v, and none holds a score matrix of the sequence's length (the
    float32 scores ``[16, 16384, 2048 + 896]`` would be 3.1 GB, those
    inside the mask 1.5 GB)."""
    B, S, N, D = shape
    W, C = 2048, 16
    q = _sds(shape, jnp.bfloat16, one_chip)
    mu = _sds((N, D), jnp.float32, one_chip)

    def attend(q, k, v, mu, phi):
        with jax.named_scope("layer"):  # as in a model: the kernels' scopes
            # are then not the outermost, which jvp() would wrap
            kb, vb = eva.chunk_summaries(k, v, mu, phi, C, use_pallas=True)
            return eva.eva_attention(q, k, v, kb, vb, W, C, use_pallas=True)

    compiled = jax.jit(_sum_grad(attend, 5)).lower(q, q, q, mu, mu).compile()
    text = compiled.as_text()
    calls = pallas_kernel_calls(text)
    assert {name: len(found) for name, found in calls.items()} == {
        "eva_attention": 2, "eva_pool": 2}
    rows, pooled = (B, S, N * D), (B, S // C, N * D)
    for operands in calls["eva_attention"]:
        assert rows in operands and pooled in operands
    # the pooling's forward reads the direction matrices, k and v and writes
    # what the attention reads; its backward reads the directions, k, v and
    # the summaries' cotangents
    matrix = (N, pallas_eva_pool.PARTS * D, 128)
    assert sorted(calls["eva_pool"], key=len) == [
        [matrix, rows, rows], [matrix, (2, N * D), rows, rows, pooled, pooled]]
    entry = text[text.index("\nENTRY "):]
    assert f"= f32[{B},{S}," not in entry, "a float32 buffer of k's size"
    # the backward reads the lse as the forward wrote it: one float a row
    assert sum((B * N, 1, S) in operands
               for operands in calls["eva_attention"]) == 1
    # every temporary of forward and backward TOGETHER is under a third of
    # the bytes of the float32 scores inside the mask alone
    scores = 4 * B * N * eva.pairs_needed(S, W, C)
    assert compiled.memory_analysis().temp_size_in_bytes < scores / 3
    assert pallas_eva.compiles_for_tpu(S, W, C, D)
    assert pallas_eva_pool.compiles_for_tpu(S, C, D, jnp.bfloat16)


def test_recomputed_evabyte_keeps_the_eva_kernels_residuals(one_chip,
                                                            on_the_chip):
    """``EvaByte`` (two layers, remat, a float32 stream): each layer's
    attention is one ``eva_attention`` kernel call forward and one backward,
    the remat wrap keeps that kernel's output and lse (nothing recomputed);
    its summaries are one ``eva_pool`` call forward, one recomputed (the wrap
    does not keep them and the attention's backward reads them) and one
    backward."""
    from deeperspeed_tpu.models.evabyte import EvaByte, EvaByteConfig
    from deeperspeed_tpu.telemetry import count_kernel_passes

    model = EvaByte(EvaByteConfig.tiny(
        hidden_size=256, num_attention_heads=2, intermediate_size=256,
        window_size=2048, chunk_size=16, max_seq_len=4096,
        ce_chunk_tokens=2048, remat=True, dtype=jnp.bfloat16))
    loss = model.loss_fn()
    ids = jnp.zeros((1, 4096), jnp.int32)
    params = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(1), ids)))
    passes = count_kernel_passes(_compile(
        jax.grad(lambda p, ids: loss(
            p["params"], {"input_ids": ids, "labels": ids})[0]),
        params, _sds(ids.shape, ids.dtype, one_chip)))
    assert passes["eva_attention"] == dict(forward=2, recomputed=0,
                                           backward=2)
    assert passes["eva_pool"] == dict(forward=2, recomputed=2, backward=2)
    assert "flash_attention" not in passes


def test_dsa_fwd_bwd_at_the_keye_cells_shape(one_chip):
    """Learned sparse attention at train-keye-vl2-ep8-16k's shapes (one
    sequence of 16,384, 32 query heads over 4 KV heads of 128, an indexer of
    16 heads of 64, topk 2048), the selection, the attention forward and
    backward and the indexer's loss with its gradients: k and v reach the
    kernels at their KV heads, the selection as 34 MB of packed words, and
    the program holds no ``[heads, S, S]`` array of the main attention (its
    temporaries are a fraction of ONE head's float32 plane)."""
    B, S, N, KV, D, HI, DI = 1, 16384, 32, 4, 128, 16, 64
    bf = jnp.bfloat16

    def fn(qi, ki, w, q, k, v):
        def loss(qi, ki, w, q, k, v):
            sel = dsa.dsa_select(qi, ki, w, 2048, use_pallas=True)
            o, lse = dsa.dsa_attention(q, k, v, sel, use_pallas=True)
            return jnp.sum(o.astype(jnp.float32)) + dsa.dsa_indexer_loss(
                qi, ki, w, q, k, lse, sel, use_pallas=True)
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))(qi, ki, w, q, k, v)

    compiled = jax.jit(fn).lower(
        _sds((B, S, HI, DI), bf, one_chip), _sds((B, S, DI), bf, one_chip),
        _sds((B, S, HI), jnp.float32, one_chip),
        _sds((B, S, N, D), bf, one_chip), _sds((B, S, KV, D), bf, one_chip),
        _sds((B, S, KV, D), bf, one_chip)).compile()
    text = compiled.as_text()
    calls = pallas_kernel_calls(text)
    assert set(calls) >= {"dsa_select", "dsa_attention", "dsa_head_probs",
                          "dsa_loss_grads"}
    # the loss kernel reads a chunk of rows' probabilities and writes its
    # rows of the whole length's dq^I; its block of rows is its own
    loss_operands = [s for call in calls["dsa_loss_grads"] for s in call]
    assert (B, 512, S) in loss_operands and (B, HI, S, DI) in loss_operands
    assert pallas_dsa.loss_rows(pallas_dsa.sel_layout(S)) == 128
    operands = [shape for call in calls["dsa_attention"] for shape in call]
    assert (B, S, KV * D) in operands and (B, S, N * D) in operands
    assert (B, S, 512) in operands              # the packed selection
    assert (B, S, N * D) not in [s for call in calls["dsa_select"]
                                 for s in call]
    one_head_plane = S * S * 4
    assert compiled.memory_analysis().temp_size_in_bytes < one_head_plane
    assert f"f32[{N},{S},{S}]" not in text and f"bf16[{N},{S},{S}]" not in text
    assert f"f32[{B},{N},{S},{S}]" not in text


@pytest.mark.parametrize("kernel", ["forward", "dq", "dk/dv"])
@pytest.mark.parametrize("S,heads,kv_heads,walk", [
    (16384, 32, 4, "resident"),     # train-keye-vl2-ep8-16k's layer
    (32768, 8, 1, "span"),          # twice the length: k and v by spans
], ids=["keye-16k", "32k"])
def test_dsa_attention_kernel_at_its_plan(one_chip, S, heads, kv_heads, walk,
                                          kernel):
    """Each of the three ``dsa_attention`` kernels alone at the blocks
    ``pallas_dsa.attend_plan`` gives the shape: a block of rows of several
    query heads of a group (of columns in dk/dv) a grid program, the tiles
    of ``SelLayout.rows`` rows walked in the body, k and v of a KV head
    resident at 16k (a scoped-VMEM limit of 32 MiB and no more) and a span
    of them at 32k."""
    B, D, bf = 1, 128, jnp.bfloat16
    lay = pallas_dsa.sel_layout(S)
    plan = pallas_dsa.attend_plan(lay, D, heads // kv_heads, bf)
    assert lay.rows == 512 and plan == (
        (512, 16384, 4096, 4, walk) if S == 16384
        else (1024, 16384, 4096, 2, walk))
    q = _sds((B, S, heads * D), bf, one_chip)
    k = _sds((B, S, kv_heads * D), bf, one_chip)
    stat = _sds((B * heads, 1, S), jnp.float32, one_chip)
    words = _sds((B, S, lay.chunk), jnp.int32, one_chip)
    counts = _sds((B * (S // lay.rows) * lay.chunks,), jnp.int32, one_chip)
    if kernel == "forward":
        text = _compile(
            lambda *a: pallas_dsa.fwd_call(*a, heads, lay, plan),
            q, k, k, words, counts)
    else:
        part = slice(0, 1) if kernel == "dq" else slice(1, 3)
        text = _compile(
            lambda *a: pallas_dsa.bwd_call(*a, heads, lay, plan)[part],
            q, k, k, q, stat, stat, words, counts)
    calls = pallas_kernel_calls(text)
    assert list(calls) == ["dsa_attention"] and len(calls["dsa_attention"]) == 1
    # what the call states and what Mosaic laid out: at 32 MiB XLA still
    # keeps the selection's words in VMEM around the kernels (PERF.md, PR 54)
    stated, used = _scoped_vmem(text)
    assert used <= stated <= (32 if walk == "resident" else 64) << 20


@pytest.mark.parametrize("S,heads,kv_heads,dtype,side_by_side", [
    (16384, 32, 4, jnp.bfloat16, 4),        # train-keye-vl2-ep8-16k's layer
    (16384, 32, 4, jnp.float32, 4),
    (32768, 8, 1, jnp.bfloat16, 2),         # a tile of 512 x 1,024
], ids=["keye-16k", "float32", "32k"])
def test_dsa_head_probs_kernel_at_its_plan(one_chip, S, heads, kv_heads,
                                           dtype, side_by_side):
    """``dsa_head_probs`` alone at the sizes ``pallas_dsa.head_probs_plan``
    gives the shape: a grid program an output tile, every head's columns of
    the row block of q resident (a dynamic lane-block slice a set of heads
    in the body's loop), one call of one kernel behind its jit, under a
    scoped-VMEM limit of 32 MiB and no more (at 48 XLA stops keeping the
    selection's words in VMEM for the attention's kernels)."""
    B, D = 1, 128
    lay = pallas_dsa.sel_layout(S)
    plan = pallas_dsa.head_probs_plan(lay, heads // kv_heads)
    assert plan == (side_by_side,)
    text = _compile(
        lambda *a: pallas_dsa.head_probs_call(*a, lay.rows, heads, lay, plan),
        _sds((B, S, heads * D), dtype, one_chip),
        _sds((B, S, kv_heads * D), dtype, one_chip),
        _sds((B * heads, 1, S), jnp.float32, one_chip),
        _sds((B, S, lay.chunk), jnp.int32, one_chip),
        _sds((B * (S // lay.rows) * lay.chunks,), jnp.int32, one_chip),
        _sds((1,), jnp.int32, one_chip))
    calls = pallas_kernel_calls(text)
    assert list(calls) == ["dsa_head_probs"]
    assert len(calls["dsa_head_probs"]) == 1
    assert f"f32[{B},{lay.rows},{S}]" in text
    stated, used = _scoped_vmem(text)
    assert used <= stated <= 32 << 20


def test_recomputed_keye_keeps_what_is_made_once_a_step(one_chip):
    """``Keye`` (two layers, remat): a layer's selection is one
    ``dsa_select`` call, its attention one ``dsa_attention`` call forward
    and two backward (dq; dk and dv), its loss's ``dsa_head_probs`` and
    ``dsa_loss_grads`` a call each a chunk of rows; the remat wrap keeps the
    selection, the attention's output and log-sum-exp and the indexer's
    gradients, so nothing of them is recomputed; and no dense flash call is
    made."""
    from deeperspeed_tpu.models.keye import Keye, KeyeConfig
    from deeperspeed_tpu.telemetry import count_kernel_passes

    model = Keye(KeyeConfig.tiny(
        hidden_size=256, num_heads=4, num_kv_heads=2, head_dim=128,
        mrope_section=(16, 24, 24), indexer_num_heads=4, indexer_head_dim=64,
        topk=512, moe_intermediate_size=128, max_seq_len=2048,
        ce_chunk_tokens=2048, remat=True, dtype=jnp.bfloat16,
        use_pallas=True))
    loss = model.loss_fn()
    ids = jnp.zeros((1, 2048), jnp.int32)
    params = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(1), ids)))
    passes = count_kernel_passes(_compile(
        jax.grad(lambda p, ids: loss(
            p["params"], {"input_ids": ids, "labels": ids})[0]),
        params, _sds(ids.shape, ids.dtype, one_chip)))
    assert passes["dsa_select"] == dict(forward=2, recomputed=0, backward=0)
    assert passes["dsa_attention"] == dict(forward=2, recomputed=0,
                                           backward=4)
    chunks = 2048 // pallas_dsa.sel_layout(2048).rows
    for kernel in ("dsa_head_probs", "dsa_loss_grads"):
        assert passes[kernel] == dict(forward=2 * chunks, recomputed=0,
                                      backward=0), kernel
    assert "flash_attention" not in passes


def test_recomputed_zaya_keeps_the_flash_residuals_and_the_walks_plan(
        one_chip, on_the_chip):
    """``Zaya`` (three layers, remat, the head tied to the table): a layer's
    attention in the latent is a ``flash_attention`` call forward and one
    backward with k and v at the 2 KV heads, a layer's top-1 walk the
    grouped form's two matmuls forward and six backward; the recomputed
    layer keeps the kernel's residuals and the walk's plan and runs neither
    again."""
    from deeperspeed_tpu.models.zaya import Zaya, ZayaConfig

    model = Zaya(ZayaConfig.tiny(
        hidden_size=256, num_heads=4, num_kv_heads=2, head_dim=128,
        moe_intermediate_size=128, router_hidden_size=128, max_seq_len=256,
        ce_chunk_tokens=256, remat=True, dtype=jnp.bfloat16))
    loss = model.loss_fn()
    passes = _model_gradient_passes(
        model, lambda p, ids: loss(
            p["params"], {"input_ids": ids, "labels": ids})[0], one_chip)
    assert passes["flash_attention"] == dict(forward=3, recomputed=0,
                                             backward=3)
    assert passes["grouped_matmul"] == dict(forward=3 * 2, recomputed=0,
                                            backward=3 * 6)
    assert passes["unwritten"] == dict(forward=3, recomputed=0, backward=3)
    # the latent's mixing is the kernel pair (the row block follows a
    # sequence of 256 rows), run again in the recomputed layer: q, k, v are
    # not kept by name
    assert passes["cca_mix"] == dict(forward=3, recomputed=3, backward=3)


def test_cca_mix_pair_at_the_zaya_cells_shape(one_chip):
    """CCA's mixing at ``train-zaya1-8b-ep2-8k``'s shape (4 x 8192 rows, 8
    query and 2 KV heads of 128, bfloat16), forward + backward in all eight
    operands: two kernel calls under the scope ``cca_mix`` that read the
    projections' ``[B, S, n d]`` where it lies and write q, k, v (and their
    gradients) in the same layout; the backward's residuals are the streams
    and the parameters, and no buffer of the program is a float32 copy of a
    stream."""
    B, S, heads, kv_heads, d = 4, 8192, 8, 2, 128
    c, bf16, f32 = (heads + kv_heads) * d, jnp.bfloat16, jnp.float32
    wide, thin = (B, S, heads * d), (B, S, kv_heads * d)
    shapes = [_sds(shape, dtype, one_chip) for shape, dtype in (
        (wide, bf16), (thin, bf16), (thin, bf16), ((2, c), f32), ((c,), f32),
        ((2, heads + kv_heads, d, d), f32), ((c,), f32), ((kv_heads,), f32))]

    def mix(*operands):
        with jax.named_scope("layer"):
            return cca.cca_mix(
                *operands, heads=heads, kv_heads=kv_heads, rotary_dim=d // 2,
                rope_theta=5e6, eps=1e-5, use_pallas=True)

    def weighted(*operands):    # cotangents that are not a constant
        return sum(jnp.sum(out.astype(f32) * out.astype(f32))
                   for out in mix(*operands))

    forward = pallas_kernel_calls(_compile(mix, *shapes))
    assert list(forward) == ["cca_mix"] and len(forward["cca_mix"]) == 1
    assert forward["cca_mix"][0][:3] == [wide, thin, thin]
    compiled = jax.jit(jax.grad(weighted, argnums=tuple(range(8)))).lower(
        *shapes).compile()
    text = compiled.as_text()
    calls = pallas_kernel_calls(text)
    assert {name: len(found) for name, found in calls.items()} == {
        "cca_mix": 2}
    backward = max(calls["cca_mix"], key=len)
    # the streams and their halo views, then the three cotangents
    assert backward[:7] == [wide, thin, wide, thin, wide, thin, thin]
    entry = text[text.index("\nENTRY "):]
    assert f"= f32[{B},{S}," not in entry, "a float32 buffer of a stream's size"
    assert pallas_cca.compiles_for_tpu(S, d, d // 2)
    assert pallas_cca.mix_rows(S) == pallas_cca.ROWS


def test_flash_mla_fwd_bwd_at_the_moonlight_cells_shape(one_chip):
    """Latent attention at ``train-moonlight-16b-ep8-8k``'s shape (4 x 8192
    rows, 16 heads of 128 + 64 | 128, bfloat16), forward + backward in all
    five operands: one kernel call each under the scope
    ``flash_attention_mla`` whose operands are the projections' outputs as
    they stand.  The rotary key goes in ONCE, ``[B, S, 64]`` (never ``[B, S,
    16 x 64]``), its gradient comes out at that shape, and no operand is
    3,072 wide: no value padded to the score's 192."""
    B, S, N, dn, dr, dv = 4, 8192, 16, 128, 64, 128
    bf16 = jnp.bfloat16
    shapes = [_sds(shape, bf16, one_chip) for shape in (
        (B, S, N, dn), (B, S, N, dr), (B, S, N, dn), (B, S, dr),
        (B, S, N, dv))]
    assert pallas_flash_mla.supported((B, S, N, dn), dr, dv, bf16)
    nope, rope, key = (B, S, N * dn), (B, S, N * dr), (B, S, dr)

    def mla(*operands):
        with jax.named_scope("layer"):
            return pallas_flash_mla.mla(*operands)

    forward = pallas_kernel_calls(_compile(mla, *shapes))
    assert list(forward) == ["flash_attention_mla"]
    assert forward["flash_attention_mla"] == [[nope, rope, nope, key, nope]]
    text = _compile(_sum_grad(mla, 5), *shapes)
    calls = pallas_kernel_calls(text)["flash_attention_mla"]
    assert len(calls) == 2
    backward = max(calls, key=len)
    # the forward's five, then do, o and the lse
    assert backward[:7] == [nope, rope, nope, key, nope, nope, nope]
    assert backward[7] == (B * N, 1, S)
    flat = [shape for call in calls for shape in call]
    assert (B, S, N * (dn + dr)) not in flat, "a value or key padded to 192"
    assert sum(shape == key for shape in flat) == 2, "the ONE rotary key"
    # the key's gradient leaves the kernel at one head
    assert f"bf16[{B},{S},{dr}]" in text
    from deeperspeed_tpu.telemetry import count_kernel_passes

    assert count_kernel_passes(text)["flash_attention_mla"] == dict(
        forward=1, recomputed=0, backward=1)


def test_recomputed_moonlight_keeps_the_kernels_residuals_and_the_walks_plan(
        one_chip, on_the_chip):
    """``Moonlight`` (a dense and two sparse layers, remat, heads of 128 +
    64 | 128): a layer's attention is one ``flash_attention_mla`` call
    forward and one backward, a sparse layer's top-3 walk the grouped form's
    two matmuls forward and six backward; the recomputed layer keeps the
    kernel's residuals and the walk's plan and runs neither again; no plain
    ``flash_attention`` is in the step."""
    from deeperspeed_tpu.models.moonlight import Moonlight, MoonlightConfig

    model = Moonlight(MoonlightConfig.tiny(
        hidden_size=256, num_attention_heads=2, kv_lora_rank=128,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        intermediate_size=256, moe_intermediate_size=128, max_seq_len=256,
        ce_chunk_tokens=256, remat=True, dtype=jnp.bfloat16))
    loss = model.loss_fn()
    passes = _model_gradient_passes(
        model, lambda p, ids: loss(
            p["params"], {"input_ids": ids, "labels": ids})[0], one_chip)
    assert passes["flash_attention_mla"] == dict(forward=3, recomputed=0,
                                                 backward=3)
    assert passes["grouped_matmul"] == dict(forward=2 * 2, recomputed=0,
                                            backward=2 * 6)
    assert "flash_attention" not in passes


@pytest.mark.parametrize("tokens,latent,inner,held,gated,most", [
    (32768, 2304, 896, 16, True, 8),    # train-mellum2-ep4-8k's layer
    (16384, 2304, 896, 16, True, 8),    # the same at micro-batch 2
    (16384, 3072, 1024, 8, True, 8),    # train-laguna-s-ep32-8k's layer
    # train-zaya1-8b-ep2-8k's: experts as wide as the stream, a slot a token
    (32768, 2048, 2048, 8, True, 1),
])
def test_grouped_walk_fwd_bwd(one_chip, tokens, latent, inner, held, gated,
                              most):
    """The routed walk's grouped form at a cell's shapes, forward and
    backward: two grouped matmuls into a buffer handed over unwritten, then
    those again, two transposed and two outer products into float32
    accumulators, every block inside the limit the calls state."""
    from deeperspeed_tpu.moe import dropless

    bf16 = jnp.bfloat16
    activation = dropless.gated_silu if gated else dropless.relu2

    def fn(x, held_w, w_in, w_out, is_chosen):
        return dropless.routed_experts(
            x, held_w, is_chosen, w_in, w_out, activation,
            dropless.ROWS_PER_GROUPED_CHUNK, True, most)[0]

    shapes = (_sds((tokens, latent), bf16, one_chip),
              _sds((tokens, held), jnp.float32, one_chip),
              _sds((held, latent, inner * (2 if gated else 1)), bf16,
                   one_chip),
              _sds((held, inner, latent), bf16, one_chip),
              _sds((tokens, held), jnp.bool_, one_chip))
    assert len(_kernel_operand_shapes(_compile(fn, *shapes))) == 3
    from deeperspeed_tpu.telemetry import count_kernel_passes
    passes = count_kernel_passes(_compile(
        jax.grad(lambda *a: jnp.sum(fn(*a)), argnums=(0, 1, 2, 3)), *shapes))
    assert passes["grouped_matmul"] == dict(forward=0, recomputed=0,
                                            backward=6)
    assert passes["unwritten"] == dict(forward=0, recomputed=0, backward=1)


def test_flash_mha_non_causal(one_chip):
    q = _sds((2, 1000, 8, 64), jnp.bfloat16, one_chip)
    fn = functools.partial(pallas_flash.mha, causal=False)
    assert len(_kernel_operand_shapes(
        _compile(_sum_grad(fn, 3), q, q, q))) == 2


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("hidden", [1024, 4096, 6144])
def test_layer_norm_fwd_bwd(one_chip, hidden, dtype):
    x = _sds((16384, hidden), dtype, one_chip)
    g = _sds((hidden,), jnp.float32, one_chip)
    fn = functools.partial(normalize.layer_norm, use_pallas=True)
    _compile(fn, x, g, g)
    _compile(_sum_grad(fn, 3), x, g, g)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_rms_norm_fwd_bwd(one_chip, dtype):
    x = _sds((16384, 4096), dtype, one_chip)
    g = _sds((4096,), jnp.float32, one_chip)
    fn = functools.partial(normalize.rms_norm, use_pallas=True)
    _compile(fn, x, g)
    _compile(_sum_grad(fn, 2), x, g)


def _pool_shapes(one_chip, pool_dtype):
    """N=16, D=64, block 16: 8 rows of up to 32 blocks over a 256-block pool."""
    pool = _sds((256, 16, 16, 64), pool_dtype, one_chip)
    tables = _sds((8, 32), jnp.int32, one_chip)
    kw = {}
    if pool_dtype == jnp.int8:
        kw = dict(k_scale=_sds((256, 16, 16), jnp.float32, one_chip),
                  v_scale=_sds((256, 16, 16), jnp.float32, one_chip))
    return pool, tables, kw


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8])
def test_paged_decode_attention(one_chip, pool_dtype):
    pool, tables, kw = _pool_shapes(one_chip, pool_dtype)
    q = _sds((8, 16, 64), jnp.bfloat16, one_chip)
    lens = _sds((8,), jnp.int32, one_chip)
    fn = paged.paged_decode_attention.__wrapped__
    _compile(fn, q, pool, pool, tables, lens, **kw)


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8])
def test_paged_spec_decode_attention(one_chip, pool_dtype):
    pool, tables, kw = _pool_shapes(one_chip, pool_dtype)
    q = _sds((8, 4, 16, 64), jnp.bfloat16, one_chip)
    pos = _sds((8, 4), jnp.int32, one_chip)
    fn = paged.paged_spec_decode_attention.__wrapped__
    _compile(fn, q, pool, pool, tables, pos, **kw)


def test_sorted_topk(one_chip):
    x = _sds((8, 50304), jnp.float32, one_chip)
    _compile(functools.partial(topk.sorted_topk.__wrapped__, k=50), x)


@pytest.mark.parametrize("wire", [jnp.int8, jnp.float8_e4m3fn])
def test_fused_dequant_reduce(one_chip, wire):
    q = _sds((4, 1024, 1024), wire, one_chip)
    scale = _sds((4, 1024, 8, 1), jnp.float32, one_chip)
    _compile(functools.partial(qfused.fused_dequant_reduce, group_size=128),
             q, scale)


# ---------------------------------------------------- dp=4 on the 2x2 mesh
@pytest.fixture
def dp4(topo, monkeypatch):
    """The four described devices as the process-global dp=4 mesh."""
    mesh = topo_mod.MeshTopology(devices=topo.devices)
    assert mesh.dp == 4
    monkeypatch.setattr(topo_mod, "_GLOBAL_MESH", mesh)
    return mesh.mesh


def test_flash_dispatch_partitions_over_dp(dp4):
    """Through the repo's dispatcher every chip runs flash attention on its
    own quarter of the batch: [8, S, N, D] -> kernels on [8/4, S, N * D]."""
    q = _sds((8, 2048, 16, 64), jnp.bfloat16,
             NamedSharding(dp4, P("dp", None, None, None)))
    fn = functools.partial(attn_core.dot_product_attention, use_pallas=True)
    text = _compile(_sum_grad(fn, 3), q, q, q)
    calls = _kernel_operand_shapes(text)
    assert len(calls) >= 2          # forward + backward kernels
    for operands in calls:
        assert (2, 2048, 16 * 64) in operands, operands
        assert (8, 2048, 16 * 64) not in operands, operands
    assert "all-gather" not in text


def test_ssd_scan_dispatch_partitions_over_dp(dp4, on_the_chip):
    """Through ``ssd_scan`` every chip runs the scan's kernels on its own
    quarter of the batch, forward and backward; the decay rates and the
    skip are whole on every chip."""
    B, S, heads, p, groups, n = 8, 1024, 4, 64, 2, 128
    rows = NamedSharding(dp4, P("dp", None, None, None))
    whole = NamedSharding(dp4, P())
    args = (_sds((B, S, heads, p), jnp.bfloat16, rows),
            _sds((B, S, heads), jnp.float32,
                 NamedSharding(dp4, P("dp", None, None))),
            _sds((heads,), jnp.float32, whole),
            _sds((B, S, groups, n), jnp.bfloat16, rows),
            _sds((B, S, groups, n), jnp.bfloat16, rows),
            _sds((heads,), jnp.float32, whole))
    text = _compile(_sum_grad(ssm.ssd_scan, 6), *args)
    calls = _kernel_operand_shapes(text)
    assert len(calls) == 2, calls
    for operands in calls:
        assert (B // 4, S, heads * p) in operands, operands
        assert (B, S, heads * p) not in operands, operands
    assert "all-gather" not in text


def test_layer_norm_dispatch_partitions_over_dp(dp4):
    x = _sds((16384, 1024), jnp.bfloat16, NamedSharding(dp4, P("dp", None)))
    g = _sds((1024,), jnp.float32, NamedSharding(dp4, P()))
    fn = functools.partial(normalize.layer_norm, use_pallas=True)
    text = _compile(_sum_grad(fn, 3), x, g, g)
    calls = _kernel_operand_shapes(text)
    assert calls
    for operands in calls:
        assert (16384 // 4, 1024) in operands, operands
        assert (16384, 1024) not in operands, operands
    assert "all-gather" not in text
    # dgamma/dbeta: partial sums per chip, reduced across dp
    assert "all-reduce" in text

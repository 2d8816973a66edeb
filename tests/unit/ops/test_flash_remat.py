"""A recomputed block does not recompute the flash forward kernel: the
kernel's output and its one-float-a-row lse carry ``jax.ad_checkpoint``
names (``pallas_flash.SAVED_BY_REMAT``) and the dense models' remat wraps
save exactly those.  Counted here in the gradient's jaxpr, with the kernels
run by the Pallas interpreter; what the compiled TPU program holds is
``telemetry.count_kernel_passes`` (``test_tpu_compile.py`` asks it).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu.accelerator import get_accelerator
from deeperspeed_tpu.models import gpt_neox
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.models.ouro import Ouro, OuroConfig
from deeperspeed_tpu.ops.attention.pallas_flash import tile_plan

#: (heads, head dim) that take each layout of the kernel
PLANS = {"in_place_2": (2, 64), "in_place_1": (1, 128), "folded": (4, 16)}


@pytest.fixture
def flash_kernels(monkeypatch):
    """Attention goes to the flash kernel as it does on the chip (the
    interpreter runs it here)."""
    monkeypatch.setattr(type(get_accelerator()), "use_pallas_kernels",
                        lambda self: True)


def flash_passes(jaxpr, times=1, recomputing=False, found=None):
    """Flash kernel calls of a gradient's jaxpr by pass: a forward kernel
    inside a remat equation is the forward pass run again; a scan runs its
    body ``length`` times."""
    if found is None:
        found = dict(forward=0, recomputed=0, backward=0)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            kernel = eqn.params["jaxpr"].debug_info.func_src_info.split()[0]
            if kernel == "_fwd_kernel":
                found["recomputed" if recomputing else "forward"] += times
            elif kernel in ("_bwd_kernel", "_dq_kernel"):
                found["backward"] += times
            continue
        inner_times = times * eqn.params["length"] if name == "scan" else times
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    flash_passes(inner, inner_times,
                                 recomputing or name == "remat2", found)
    return found


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S", [256, 1000])
@pytest.mark.parametrize("plan", list(PLANS))
def test_recomputed_gpt_neox_block_keeps_the_kernels_residuals(
        plan, S, causal, flash_kernels, monkeypatch):
    """Two layers with ``remat=True``: two forward and two backward kernel
    calls and no third kind, and the loss and every gradient leaf equal to
    ``remat=False`` to the last bit (the same kernels on the same values)."""
    heads, head_dim = PLANS[plan]
    group = tile_plan(S, head_dim, jnp.float32, N=heads).group
    assert (f"in_place_{group}" if group else "folded") == plan
    if not causal:
        attend = gpt_neox.dot_product_attention
        monkeypatch.setattr(
            gpt_neox, "dot_product_attention",
            lambda *a, causal=True, **kw: attend(*a, causal=False, **kw))
    cfg = GPTNeoXConfig(vocab_size=256, hidden_size=heads * head_dim,
                        num_heads=heads, num_layers=2, max_seq_len=S,
                        fused_norms=False)
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, S), 0, cfg.vocab_size)
    params = GPTNeoX(cfg).init(jax.random.PRNGKey(1), ids)

    def loss_and_grads(remat):
        model = GPTNeoX(dataclasses.replace(cfg, remat=remat))

        def loss(p):
            return jnp.mean(jnp.square(model.apply(p, ids)))
        return jax.value_and_grad(loss)

    passes = flash_passes(jax.make_jaxpr(loss_and_grads(True))(params).jaxpr)
    assert passes == dict(forward=2, recomputed=0, backward=2)
    kept = jax.jit(loss_and_grads(True))(params)
    plain = jax.jit(loss_and_grads(False))(params)
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gpt_neox_without_remat_runs_each_kernel_once(flash_kernels):
    cfg = GPTNeoXConfig(vocab_size=256, hidden_size=128, num_heads=2,
                        num_layers=2, max_seq_len=256, fused_norms=False)
    model = GPTNeoX(cfg)
    ids = jnp.zeros((1, 256), jnp.int32)
    params = model.init(jax.random.PRNGKey(1), ids)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: jnp.mean(model.apply(p, ids))))(params).jaxpr
    assert flash_passes(jaxpr) == dict(forward=2, recomputed=0, backward=2)


@pytest.mark.parametrize("remat,recomputed", [(True, 6), (False, 0)])
def test_looped_model_still_recomputes_the_kernel(remat, recomputed,
                                                  flash_kernels):
    """``Ouro`` keeps its policy-less wrap (T passes over L layers would
    keep T * L outputs: ``models/ouro.py``): with remat the forward kernel
    runs again for each of the T * L = 3 * 2 layer applications."""
    cfg = OuroConfig.tiny(hidden_size=128, num_heads=2, num_kv_heads=2,
                          max_seq_len=256, ce_chunk_tokens=256,
                          total_ut_steps=3, remat=remat)
    model = Ouro(cfg)
    ids = jnp.zeros((1, 256), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(1), ids))["params"]
    loss = model.loss_fn()
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: loss(
        p, {"input_ids": ids, "labels": ids})[0]))(params).jaxpr
    assert flash_passes(jaxpr) == dict(forward=6, recomputed=recomputed,
                                       backward=6)

"""Timing helpers for the kernel-level tools.

JAX dispatch is asynchronous, so a timing loop ends in
``jax.block_until_ready`` on its last output: ``timed`` chains n calls and
then waits for the device to finish them.
"""

import json
import time

import jax


def emit(phase, seconds=0.0, **kw):
    print(json.dumps({"phase": phase, "ms": round(seconds * 1e3, 3), **kw}),
          flush=True)


def attn_flops(B, S, N, D, causal=True, mode="fwd"):
    """MXU FLOPs of blocked attention in matmul units.
    fwd = QK^T + PV (2); flash bwd = S-recompute + dP + dV + dQ + dK (5);
    bwd_stored = dP + dV + dQ + dK (4, dense path that keeps P);
    fwdbwd = flash fwd + flash bwd (7)."""
    per_mm = 2 * S * S * D * B * N / (2 if causal else 1)
    n_mm = {"fwd": 2, "bwd": 5, "bwd_stored": 4, "fwdbwd": 7}[mode]
    return n_mm * per_mm


def drain(out):
    """Wait until the device has finished computing ``out``."""
    jax.block_until_ready(out)


def timed(fn, *args, n=10, warmup=2):
    """Mean seconds per call of fn(*args), ended by ``block_until_ready``."""
    for _ in range(warmup):
        out = fn(*args)
    drain(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    drain(out)
    return (time.perf_counter() - t0) / n


def timed_inner(step, x, iters=50, warmup=True):
    """Per-iteration seconds of ``step`` (x -> same-shape x), with the loop
    INSIDE one jit: a single dispatch runs ``iters`` chained executions, so
    per-dispatch host overhead is amortized away.
    """
    import jax.lax as lax

    @jax.jit
    def loop(x0):
        return lax.fori_loop(0, iters, lambda i, c: step(c), x0)

    if warmup:
        drain(loop(x))
    t0 = time.perf_counter()
    out = loop(x)
    drain(out)
    return (time.perf_counter() - t0) / iters

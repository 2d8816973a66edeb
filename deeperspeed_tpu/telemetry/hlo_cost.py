"""HLO-derived step accounting: true FLOPs / bytes-accessed and MFU/MBU.

Instead of the hand-rolled per-module estimates in
``profiling/flops_profiler/profiler.py`` (now the fallback path), the
compiled step function itself is the ground truth:
``jit(fn).lower(args).compile().cost_analysis()`` reads XLA's cost model of
the *optimized* HLO -- fusion, remat, and sharding included.  Utilization is
then measured FLOPs/s (bytes/s) against a TPU peak-spec table keyed on
``device_kind``.

The AOT ``lower().compile()`` shares jax's executable cache with a prior
``fn(args)`` call for identical avals, so running the analysis *after* the
first step costs a retrace but not a recompile.
"""

import jax

from ..utils.logging import logger

# (peak dense FLOP/s per chip at bf16, HBM bytes/s per chip).  Public
# per-chip numbers; substring-matched against ``device.device_kind``.
# MXU peaks assume bf16 inputs / fp32 accumulate -- the training dtype this
# repo runs; fp32-only models overstate MFU by ~2x on v4+.
TPU_PEAK_SPECS = {
    "TPU v2": (45e12, 700e9),
    "TPU v3": (123e12, 900e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5litepod": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
    "TPU v6": (918e12, 1640e9),
    "TPU v7": (2307e12, 7380e9),
}

# CPU hosts (tests, smoke runs): a nominal desktop-class peak so MFU/MBU
# stay finite and comparable run-to-run; absolute values are not meaningful.
_CPU_PEAK = (1e11, 50e9)


def device_peaks(device=None):
    """``(peak_flops_per_s, peak_bytes_per_s, device_kind)`` for one chip.

    Longest substring match (``wire.match_device_spec``): generation keys
    ("TPU v5") are prefixes of variant kinds ("TPU v5litepod-16"), so
    first-match would price a v5e pod at v5p peaks."""
    from .wire import match_device_spec

    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "") or ""
    hit = match_device_spec(TPU_PEAK_SPECS, kind)
    if hit:
        return hit[1][0], hit[1][1], kind
    if getattr(device, "platform", "cpu") != "cpu":
        raise ValueError(
            f"no peak spec known for device kind {kind!r}; add it to "
            f"TPU_PEAK_SPECS with its source")
    return _CPU_PEAK[0], _CPU_PEAK[1], kind or "cpu"


def compiled_cost(compiled):
    """FLOPs + bytes-accessed of a ``jax.stages.Compiled`` (or anything with
    ``cost_analysis()``).  Returns ``{"flops", "bytes_accessed"}`` or None."""
    try:
        ca = compiled.cost_analysis()
    except Exception as e:
        logger.warning(f"cost_analysis unavailable: {e}")
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = ca.get("flops")
    nbytes = ca.get("bytes accessed", ca.get("bytes_accessed"))
    if flops is None and nbytes is None:
        return None
    return {"flops": float(flops or 0.0), "bytes_accessed": float(nbytes or 0.0)}


def pallas_kernel_calls(hlo_text):
    """``{scope: [operand shapes of each call]}`` for the Pallas kernels
    (``tpu_custom_call``) of a compiled program's ``as_text()``, keyed by the
    ``jax.named_scope`` each kernel is dispatched under (``flash_attention``,
    ``fused_norm``, ``paged_decode_attention``, ``sorted_topk``; ``"?"`` for
    a kernel without one).  Says whether a kernel is in the program -- i.e.
    did not give way to its XLA reference -- and on what size of operand."""
    import re

    from .trace import _KERNEL_CALL, _KERNEL_OP_NAME

    found = {}
    for line in hlo_text.splitlines():
        if _KERNEL_CALL not in line:
            continue
        scope = _KERNEL_OP_NAME.search(line)
        operands = line.split("operand_layout_constraints={", 1)[-1]
        operands = re.split(r"}, \w+=", operands, maxsplit=1)[0]
        shapes = [tuple(int(d) for d in dims.split(",") if d)
                  for dims in re.findall(r"\w+\[([\d,]*)\]", operands)]
        found.setdefault(scope.group(2) if scope else "?", []).append(shapes)
    return found


def step_cost(jitted_fn, *args, **kwargs):
    """HLO cost of one invocation of a jitted step function.

    Call after the step has executed once so ``lower().compile()`` hits the
    executable cache.  Returns ``{"flops", "bytes_accessed"}`` or None when
    the backend exposes no cost model (telemetry degrades, never raises).
    """
    try:
        compiled = jitted_fn.lower(*args, **kwargs).compile()
    except Exception as e:
        logger.warning(f"step cost lowering failed: {e}")
        return None
    return compiled_cost(compiled)


def utilization(cost, step_time_s, n_devices=None):
    """MFU / MBU of one step against the device peak-spec table.

    ``cost`` is a :func:`step_cost` dict for the whole (SPMD) program;
    ``n_devices`` defaults to the process-global device count.  Returns
    ``{"mfu", "mbu", "flops_per_s", "bytes_per_s", "device_kind", ...}``.
    """
    if cost is None or step_time_s <= 0:
        return None
    if n_devices is None:
        n_devices = len(jax.devices())
    peak_flops, peak_bytes, kind = device_peaks()
    flops_per_s = cost["flops"] / step_time_s
    bytes_per_s = cost["bytes_accessed"] / step_time_s
    denom_f = peak_flops * max(n_devices, 1)
    denom_b = peak_bytes * max(n_devices, 1)
    return {
        "flops": cost["flops"],
        "bytes_accessed": cost["bytes_accessed"],
        "flops_per_s": flops_per_s,
        "bytes_per_s": bytes_per_s,
        "mfu": flops_per_s / denom_f if denom_f else 0.0,
        "mbu": bytes_per_s / denom_b if denom_b else 0.0,
        "device_kind": kind,
        "n_devices": n_devices,
    }

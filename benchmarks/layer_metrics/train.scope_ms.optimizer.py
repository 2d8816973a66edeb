"""Device time of one training step after the backward pass: the scopes
``grad_accumulate`` (casts of the gradient, its accumulation), ``grad_norm_clip``
(norm and clip) and ``optimizer`` (the optimizer's pass over masters and
moments, and the cast of the masters to the compute type at the head of the
next forward).  Scope by scope: ``benchmarks/program_trace.py``."""

from benchmarks import program_trace


def compute(record, trace):
    found = program_trace.for_reader(record, trace)
    return found and found.scope_ms_per_step(
        "grad_accumulate", "grad_norm_clip", "optimizer")

"""Wall seconds of set-up spent tracing functions to jaxprs
(``/jax/core/compile/jaxpr_trace_duration``: every jitted function's and
every jitted kernel call's; a call traced inside another's trace fires inside
the outer one's interval), as the UNION of the intervals the program's
``compile_stats()`` keeps, from the measuring process's start to the window's
opening, so that nested traces count once.  A cut through
``setup.initialize_s``, ``setup.first_steps_s`` and
``setup.outside_program_s``, added to nothing:
``benchmarks/layer_metrics/_setup_timeline.py``."""

from benchmarks.layer_metrics import _setup_timeline


def compute(record, trace):
    return _setup_timeline.compile_s(record, "trace")

"""Wall seconds of set-up spent lowering jaxprs to MLIR
(``/jax/core/compile/jaxpr_to_mlir_module_duration``; a Pallas kernel's body
is lowered once a call site unless the call sits behind a ``jax.jit`` of its
own), as the UNION of the intervals the program's ``compile_stats()`` keeps,
from the measuring process's start to the window's opening.  A cut through
``setup.initialize_s``, ``setup.first_steps_s`` and
``setup.outside_program_s``, added to nothing:
``benchmarks/layer_metrics/_setup_timeline.py``."""

from benchmarks.layer_metrics import _setup_timeline


def compute(record, trace):
    return _setup_timeline.compile_s(record, "lower")

"""Wall seconds of set-up spent loading executables from the persistent
compile cache (``/jax/compilation_cache/cache_retrieval_time_sec``, about
10 ms a MB of executable), as the UNION of the intervals the program's
``compile_stats()`` keeps, from the measuring process's start to the window's
opening.  A cut through ``setup.initialize_s``, ``setup.first_steps_s`` and
``setup.outside_program_s``, added to nothing:
``benchmarks/layer_metrics/_setup_timeline.py``."""

from benchmarks.layer_metrics import _setup_timeline


def compute(record, trace):
    return _setup_timeline.compile_s(record, "cache_load")

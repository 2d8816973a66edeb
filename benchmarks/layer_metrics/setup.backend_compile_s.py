"""Wall seconds of set-up inside jax's backend-compile event
(``/jax/core/compile/backend_compile_duration``) LESS the cache loads inside
it (the event wraps the cache's lookup): the key's hashing, a true compile
and its write to the cache.  The UNION of the intervals the program's
``compile_stats()`` keeps, from the measuring process's start to the window's
opening.  Small in a warm run; what is not says a cache entry was missing.
A cut through ``setup.initialize_s``, ``setup.first_steps_s`` and
``setup.outside_program_s``, added to nothing:
``benchmarks/layer_metrics/_setup_timeline.py``."""

from benchmarks.layer_metrics import _setup_timeline


def compute(record, trace):
    return _setup_timeline.compile_s(record, "backend_compile")

"""Time the process spent tracing, lowering and compiling (or loading from
the persistent cache) JAX programs, in seconds: the sum of the program's
``jax_compile_phase_seconds`` over every phase and function, read from its
metrics registry at the end of the run (the window compiles nothing)."""

from benchmark import programtrace


def read(run):
    return programtrace.compile_seconds()

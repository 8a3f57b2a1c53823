"""Mean seconds per job that JAX spent tracing and lowering, on any
thread: the window's ``compile.trace`` and ``compile.lower`` records (the
program's listener on JAX's duration events; a jit traced inside another's
trace is counted once, in the outer one)."""

from benchmark import stages


def read(ctx):
    return stages.per_unit(ctx, "compile.trace", "compile.lower")

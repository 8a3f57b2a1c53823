"""Mean seconds per job of the program's ``compile_wait`` stage: the first
fit blocked on the background AOT compile (here: its cache loads)."""

from benchmark import stages


def read(ctx):
    return stages.per_unit(ctx, "compile_wait")

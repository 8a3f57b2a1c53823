"""Mean seconds per job of the program's ``fit.operands`` (operand and
statics assembly) and ``fit.materialize`` (the slab program's dispatch,
once a job) stages."""

from benchmark import stages


def read(ctx):
    return stages.per_unit(ctx, "fit.operands", "fit.materialize")

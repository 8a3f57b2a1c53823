"""Mean seconds per job of the benchmark's span around est.prepare
(fresh estimator, plan, pack, transfer)."""


def read(ctx):
    return ctx.span_mean("bench.prepare")

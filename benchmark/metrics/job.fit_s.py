"""Mean seconds per job of the benchmark's span around GameEstimator.fit
to block_until_ready (holds trace/lower and the cache loads)."""


def read(ctx):
    return ctx.span_mean("bench.fit")

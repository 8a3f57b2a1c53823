"""Bytes no implementation of the fit avoids (benchmark/costs.py: real
rows, storage dtype of the configuration, no solver iterations; a dense
[n, d] shard read whole, an ELL shard's stored nonzeros read with their
int32 indices, three float32 row vectors a coordinate) over window
seconds x peak HBM bytes/s. A lower bound, so under 100 %."""


def read(ctx):
    bytes_ = ctx.costs.fit_hbm_bytes(ctx.config, ctx.counts) * ctx.units
    return 100.0 * bytes_ / (
        ctx.window_s * (ctx.chips * ctx.peaks["hbm_bytes_per_s"]))

"""Whole fit's share of the chip's peak FLOP/s: model FLOPs of the window
(forward, gradient and Hessian products over real rows, one evaluation per
coordinate and CD iteration, each coordinate counted from what its shard
holds: 2nd(2 + d) over a dense [n, d] shard, 4 nnz + 2 sum(k^2) over an
ELL shard whose rows hold k stored nonzeros; benchmark/costs.py) over
window seconds x peak. Host clock; the fits end in block_until_ready."""


def read(ctx):
    flops = ctx.costs.fit_flops(ctx.config, ctx.counts) * ctx.units
    return 100.0 * flops / (
        ctx.window_s * (ctx.chips * ctx.peaks["flops_per_s"]))

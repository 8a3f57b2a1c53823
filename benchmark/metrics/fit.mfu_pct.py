"""Whole fit's share of the chip's peak FLOP/s: model FLOPs of the window
(forward, gradient and Hessian products over real rows, one evaluation per
coordinate and CD iteration; benchmark/costs.py) over window seconds x
peak. Host clock; the fits end in block_until_ready."""


def read(ctx):
    flops = ctx.costs.fit_flops(ctx.config) * ctx.units
    return 100.0 * flops / (
        ctx.window_s * (ctx.chips * ctx.peaks["flops_per_s"]))

"""Roofline share of the Pallas kernel ``newton_step_lanes``: for every
event of that name in the device trace, the least time the chip could take
for the call's own shapes (benchmark/costs.py newton_step_cost: the larger
of FLOPs over peak and bytes over peak; HBM binds at these shapes), summed,
over the summed device time of those events. The slab's shape [s, r,
lanes] is read from the event's HLO text. No such event in the trace: no
number."""

import re

_SLAB = re.compile(r"custom-call\(f32\[(\d+),(\d+),(\d+)\]")


def read(ctx):
    trace = ctx.trace
    if trace is None or not trace.devices:
        return None
    least = spent = 0.0
    for text, start, end in ctx.xplane.kernel_events(
            trace.first_device(), "newton_step_lanes"):
        shape = _SLAB.search(text)
        if shape is None:
            continue
        dim, rows, lanes = (int(v) for v in shape.groups())
        flops, bytes_ = ctx.costs.newton_step_cost(rows, dim, lanes)
        least += ctx.costs.least_seconds(flops, bytes_, ctx.peaks)[0]
        spent += end - start
    return 100.0 * least / spent if spent > 0 else None

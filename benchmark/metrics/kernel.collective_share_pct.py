"""Seconds in which a collective operation ran (all-reduce, all-gather,
collective-permute, reduce-scatter, all-to-all; their ``-start`` /
``-done`` halves and fusions named after them too) over the seconds in
which any operation ran, on the first device's plane of the traced
window, in percent. No device plane: no number. A one-chip trace holds no
collective and reads 0."""

COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "reduce-scatter", "all-to-all")


def read(ctx):
    trace = ctx.trace
    if trace is None or not trace.devices:
        return None
    events = trace.first_device()
    busy = ctx.xplane.busy_seconds(events)
    if busy <= 0:
        return None
    hits = [e for e in events
            if ctx.xplane.op_name(e[0]).startswith(COLLECTIVES)]
    return 100.0 * ctx.xplane.busy_seconds(hits) / busy

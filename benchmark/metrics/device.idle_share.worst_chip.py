"""The idle share of the device that worked least: 100 x (1 - the least
busy plane's busy seconds / the traced window). Beside
``device.idle_share.refit`` (the planes' mean) it says what uneven shards
cost: the devices wait for the one with the most entities. No device
plane: no number."""


def read(ctx):
    trace = ctx.trace
    if trace is None or not trace.devices or trace.window_s <= 0:
        return None
    least = min(ctx.xplane.busy_seconds(ev) for ev in trace.devices.values())
    return 100.0 * (1.0 - least / trace.window_s)

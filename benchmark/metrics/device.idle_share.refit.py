"""1 - union of the device-operation intervals over the traced window
(the first trace_units fits), in percent."""


def read(ctx):
    return ctx.idle_share_pct()

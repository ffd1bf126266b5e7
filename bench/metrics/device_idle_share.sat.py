"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals) / window, in the saturated closed loop
(%)."""


def read(ctx):
    if ctx.trace is None or ctx.trace.idle_share is None:
        return None
    return ctx.trace.idle_share * 100.0

"""Median host time of one ``ShardedIndexEngine.step()`` over the traced
steps of the saturated closed loop (ms)."""


def read(ctx):
    return ctx.median_step_s() * 1e3 if ctx.step_s else None

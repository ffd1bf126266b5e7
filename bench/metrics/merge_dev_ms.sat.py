"""Device time of the overlay merge per step: the programs of the jnp merge
(``merge_overlay_pack_jnp``) and of the Pallas merge kernel
(``kernels/overlay_merge``) in the trace (ms/step)."""

PROGRAMS = ("merge_overlay_pack", "overlay_merge")


def read(ctx):
    if ctx.trace is None or not ctx.step_s:
        return None
    s = ctx.trace.program_seconds(PROGRAMS)
    return None if s is None else s / ctx.steps * 1e3

"""Host time of the write path's overlay-pack refresh per step: the delta of
the engine's ``write_host_s`` counter (``_merged_overlay_pack``) over the
traced steps (ms/step). It leaves out ``IndexShard.apply_write``."""


def read(ctx):
    if not ctx.step_s or "write_host_s" not in ctx.stats_after:
        return None
    return ctx.counter_delta("write_host_s") / ctx.steps * 1e3

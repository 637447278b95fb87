"""Host time per ``ClusterServeEngine.step()`` call over the window: the
benchmark's own clock around each call into the engine."""


def read(ctx):
    steps = ctx.stats.get("steps")
    if not steps:
        return None
    return 1e3 * ctx.stats["step_s"] / steps

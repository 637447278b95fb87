"""Device time per job of the final solve on the gathered coreset: the
top-level runs of ``_kmeans_pp_init`` and ``_lloyd`` (Round 1 calls the
same functions inside its own program, so they are not counted here)."""

PROGRAMS = ("jit__kmeans_pp_init", "jit__lloyd")


def read(ctx):
    jobs = ctx.stats.get("jobs")
    if ctx.reduced is None or not jobs:
        return None
    s = ctx.reduced.program_s(PROGRAMS)
    return 1e3 * s / jobs if s > 0 else None

"""Device time per job of Round 1: the runs of the program
``round1_local_solves`` (every site's seeding, Lloyd or Weiszfeld passes
and sensitivities, vmapped over the sites)."""

PROGRAMS = ("jit_round1_local_solves",)


def read(ctx):
    jobs = ctx.stats.get("jobs")
    if ctx.reduced is None or not jobs:
        return None
    s = ctx.reduced.program_s(PROGRAMS)
    return 1e3 * s / jobs if s > 0 else None

"""Device time per job of Round 1's D^z seeding: operations under both
the ``round1`` scope (``round1_local_solves``) and the ``seed`` scope
(``_kmeans_pp_init``)."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms_per_job(ctx, ("round1", "seed"))

"""Device time per job of Round 1's D^z seeding draws: operations under
the ``round1`` scope (``round1_local_solves``), the ``seed`` scope
(``_kmeans_pp_init``) and the ``draw`` scope inside it (the categorical
draw of each step). ``round1_seed_ms`` less this is the distance sweep."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms_per_job(ctx, ("round1", "seed", "draw"))

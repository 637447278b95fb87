"""Device time per job of Round 2: operations under the ``round2`` scope
(``round2_local_samples``: every site's draws and portion assembly)."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms_per_job(ctx, ("round2",))

"""Device time per job of the final solve's seeding on the coreset
buffer: operations under the ``seed`` scope outside ``round1`` and
``round2``."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms_per_job(ctx, ("seed",), ("round1", "round2"))

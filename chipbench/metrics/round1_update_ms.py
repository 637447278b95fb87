"""Device time per job of Round 1's Lloyd or Weiszfeld passes:
operations under both the ``round1`` and the ``update`` scope
(``_lloyd``, ``_lloyd_converged``)."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms_per_job(ctx, ("round1", "update"))

"""Share of the query rows shipped to the device that were padding, from
the engine's ``EngineStats`` counters over the window:
n_padded / (n_padded + n_queries)."""


def read(ctx):
    padded, real = ctx.stats.get("n_padded"), ctx.stats.get("n_queries")
    if padded is None or not (padded + real):
        return None
    return 100.0 * padded / (padded + real)

"""Share of the coreset buffer's rows that can carry weight in the final
solve: the ``t`` samples and each site's ``k`` centers, over the ``rows``
argument of the ``final_solve`` host span."""
from chipbench import scopes


def read(ctx):
    rows = scopes.span_arg(ctx, "final_solve", "rows")
    cfg = ctx.config
    return (100.0 * (cfg["t"] + cfg["sites"] * cfg["k"]) / rows
            if rows else None)

"""Share of the rows Round 1 sweeps that hold a point: the configuration's
``n`` over the ``rows`` argument of the ``round1`` host span (every site
padded to the largest site's rows)."""
from chipbench import scopes


def read(ctx):
    rows = scopes.span_arg(ctx, "round1", "rows")
    return 100.0 * ctx.config["n"] / rows if rows else None

"""Device: 1 - union of the operations' intervals over the traced window,
averaged over the cell's chips."""
from bench.lib import idle_share_pct


def read(ctx):
    return idle_share_pct(ctx)

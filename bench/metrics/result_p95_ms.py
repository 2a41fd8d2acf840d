"""95th percentile, over every upload due in the window, of the time from
its due time (its last sample available) to all its outputs on the host.
An upload that failed or never finished reads as later than the run lasted.
"""
import math

from bench.lib import percentile


def read(ctx):
    if not ctx.records:
        return None
    never = ctx.window_s
    lat = [r.end - r.due if r.end is not None and r.error is None
           else math.inf for r in ctx.records]
    p95 = percentile(lat, 95)
    return 1e3 * (p95 if math.isfinite(p95) else never)

"""Admission queue: 95th percentile of the time from an upload's due time
to the start of its `process` call (harness clock)."""
from bench.lib import percentile


def read(ctx):
    waits = [r.start - r.due for r in ctx.records if r.start is not None]
    return 1e3 * percentile(waits, 95) if waits else None

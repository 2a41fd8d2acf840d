"""Stream loop: device idle time inside the `process` spans per launch of
the stage-graph kernel (device trace against harness spans)."""
from bench.lib import host_gap_ms


def read(ctx):
    return host_gap_ms(ctx)

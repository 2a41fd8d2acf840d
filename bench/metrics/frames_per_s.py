"""Frames of all recordings completed, over the seconds from the window's
start to the completion of the recording in progress when it closed."""
from bench.lib import completed


def read(ctx):
    done = completed(ctx)
    return sum(r.frames for r in done) / ctx.window_s if done else None

"""Whole window: the least time the chip could take for all the frames
completed (bench/work counts over bench/peaks.json), over the window's
seconds times the chips, in %. It still bounds a gain after a kernel is
replaced."""
from bench.lib import completed, least_time_s


def read(ctx):
    done = completed(ctx)
    if not done or not ctx.peaks:
        return None
    least, _ = least_time_s(ctx, sum(r.frames for r in done),
                            sum(r.upload.n_samples for r in done))
    return 100.0 * least / (ctx.window_s * ctx.chips)

"""Arithmetic the metric readers share."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of the
    values at or below it (no interpolation, so an infinite value stays
    one)."""
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def least_time_s(ctx, frames: int, samples: int) -> tuple[float, str]:
    """The least time one chip could take for the application's work on
    ``frames`` frames cut from ``samples`` samples, and which peak bounds
    it (``compute`` or ``memory``)."""
    ops, nbytes = ctx.work.counts(ctx.cfg, frames, samples)
    t_ops = ops / ctx.peaks["flops_per_s"]
    t_mem = nbytes / ctx.peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def completed(ctx) -> list:
    """Records of the uploads that finished with outputs."""
    return [r for r in ctx.records if r.end is not None and r.error is None]


def host_gap_ms(ctx):
    """Device idle time inside the harness's ``process`` spans per launch
    of the stage-graph kernel, in ms, averaged over the chips."""
    if ctx.trace is None:
        return None
    per_chip = []
    for c in ctx.trace.chips:
        launches = len(ctx.trace.kernel_events(c, ctx.kernel))
        if launches:
            per_chip.append(ctx.trace.idle_inside(c, "bench.process")
                            / launches)
    return 1e3 * sum(per_chip) / len(per_chip) if per_chip else None


def idle_share_pct(ctx):
    """1 - device busy time / traced window, in %, averaged over the
    chips."""
    if ctx.trace is None or not ctx.trace.chips:
        return None
    t = ctx.trace
    return 100.0 * sum(1.0 - t.busy_s(c) / t.window_s
                       for c in t.chips) / len(t.chips)


def kernel_roofline_pct(ctx, log=None):
    """The least time the chip could take for the frames completed in the
    traced window over the stage-graph kernel's time in the trace (summed
    over the chips), in %."""
    if ctx.trace is None:
        return None
    kernel_s = sum(ctx.trace.kernel_s(c, ctx.kernel) for c in ctx.trace.chips)
    done = completed(ctx)
    if kernel_s <= 0 or not done:
        return None
    least, bound = least_time_s(ctx, sum(r.frames for r in done),
                                sum(r.upload.n_samples for r in done))
    if log is not None:
        log(f"{ctx.kernel}: {bound}-bound, least {least:.6f} s of "
            f"{kernel_s:.6f} s kernel time")
    return 100.0 * least / kernel_s

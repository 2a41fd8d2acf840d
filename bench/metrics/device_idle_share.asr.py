"""Device: 1 - union of the operations' intervals over the traced window,
averaged over the chips that ran the stage-graph kernel (the cell serves
every upload on its first chip; the others only keep the host to it)."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    chips = [c for c in t.chips if t.kernel_events(c, ctx.kernel)]
    if not chips:
        return None
    return 100.0 * sum(1.0 - t.busy_s(c) / t.window_s
                       for c in chips) / len(chips)

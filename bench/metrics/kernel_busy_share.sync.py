"""Stage-graph kernel: its device time over the summed time of the
harness's `process` spans, in %: how little of an upload's service the
kernel is when each upload fills less than one dispatch."""


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace
    spans = sum(e - s for s, e in t.spans_named("bench.process"))
    kernel = sum(t.kernel_s(c, ctx.kernel) for c in t.chips) / len(t.chips)
    return 100.0 * kernel / spans if spans > 0 and kernel > 0 else None

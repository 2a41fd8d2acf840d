"""Reduction of a profiler trace to what the per-layer metrics read.

`read_xplane` turns the ``.xplane.pb`` that `jax.profiler` writes into plain
events: the operations each chip ran and the harness's own host spans
(`jax.profiler.TraceAnnotation` names that start with ``bench.``), on the
profiler's one clock. `Trace` reduces them: device busy time as the union of
operation intervals, kernel time by kernel name, device idle time inside
host spans, and idle gaps attributed to what the host was doing.
"""
from __future__ import annotations

import bisect
import collections
import re
from pathlib import Path

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# the device line that holds one event per operation the chip ran
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def read_xplane(trace_dir) -> dict:
    """{"devices": {chip: [[name, start_ns, end_ns], ...]},
    "spans": [[name, start_ns, end_ns], ...]} from the newest trace file
    under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    devices: dict = {}
    spans = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            ops = lines.get(OPS_LINE) or next(
                (ln for name, ln in lines.items() if "Ops" in name), None)
            if ops is not None:
                devices[int(m.group(1))] = [[e.name, e.start_ns, e.end_ns]
                                            for e in ops.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.end_ns]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": {str(k): v for k, v in sorted(devices.items())},
            "spans": spans}


def union(intervals) -> list:
    """Sorted, merged [start, end] intervals."""
    out: list = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _from(merged, start) -> int:
    """Index of the first merged interval that ends after ``start``."""
    return bisect.bisect_right(merged, start, key=lambda iv: iv[1])


def overlap(merged, start, end) -> float:
    """Length of [start, end] covered by merged intervals."""
    total = 0.0
    for i in range(_from(merged, start), len(merged)):
        s, e = merged[i]
        if s >= end:
            break
        total += min(e, end) - max(s, start)
    return total


def gaps(merged, start, end) -> list:
    """The parts of [start, end] not covered by merged intervals."""
    out, t = [], start
    for i in range(_from(merged, start), len(merged)):
        s, e = merged[i]
        if s >= end:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < end:
        out.append((t, end))
    return out


class Trace:
    """One traced window. Times are in seconds on the profiler's clock."""

    def __init__(self, events: dict):
        self.spans = [(n, s * 1e-9, e * 1e-9) for n, s, e in events["spans"]]
        windows = [(s, e) for n, s, e in self.spans if n == WINDOW_SPAN]
        if not windows:
            raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
        self.start, self.end = windows[0]
        self.ops = {int(c): [(n, s * 1e-9, e * 1e-9) for n, s, e in evs]
                    for c, evs in events["devices"].items()}
        self.busy = {c: union([s, e] for _, s, e in evs)
                     for c, evs in self.ops.items()}

    @property
    def chips(self) -> list:
        return sorted(self.ops)

    @property
    def window_s(self) -> float:
        return self.end - self.start

    def busy_s(self, chip: int) -> float:
        return overlap(self.busy[chip], self.start, self.end)

    def spans_named(self, name: str) -> list:
        return [(s, e) for n, s, e in self.spans if n == name]

    def kernel_events(self, chip: int, kernel: tuple) -> list:
        """(start, end) of each run of the kernel inside the window: an
        operation whose name contains one of the names in ``kernel``."""
        return [(s, e) for n, s, e in self.ops[chip]
                if any(k in n for k in kernel)
                and s >= self.start and e <= self.end]

    def kernel_s(self, chip: int, kernel: tuple) -> float:
        return sum(e - s for s, e in self.kernel_events(chip, kernel))

    def idle_inside(self, chip: int, span: str) -> float:
        """Seconds inside the host spans named ``span`` in which the chip
        ran nothing."""
        return sum((e - s) - overlap(self.busy[chip], s, e)
                   for s, e in self.spans_named(span))

    def device_ops(self, top: int = 10) -> list:
        """[name, seconds] of the operations that took most device time,
        summed over the chips."""
        tot: collections.Counter = collections.Counter()
        for evs in self.ops.values():
            for n, s, e in evs:
                if s >= self.start and e <= self.end:
                    tot[n] += e - s
        return [[n, t] for n, t in tot.most_common(top)]

    def idle_gaps(self, top: int = 10) -> list:
        """[host span, seconds]: the chips' idle time inside the window,
        split by the harness span the host was in (the spans inside the
        window do not overlap), summed over the chips; idle time under no
        span but the window is ``host``."""
        leaves = sorted(((n, s, e) for n, s, e in self.spans
                         if n != WINDOW_SPAN), key=lambda x: x[1])
        tot: collections.Counter = collections.Counter()
        for chip in self.chips:
            for gs, ge in gaps(self.busy[chip], self.start, self.end):
                covered = 0.0
                first = bisect.bisect_right(leaves, gs, key=lambda x: x[2])
                for i in range(first, len(leaves)):
                    n, s, e = leaves[i]
                    if s >= ge:
                        break
                    o = max(0.0, min(e, ge) - max(s, gs))
                    if o > 0:
                        tot[n] += o
                        covered += o
                tot["host"] += (ge - gs) - covered
        return [[n, t] for n, t in tot.most_common(top)]

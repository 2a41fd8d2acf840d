"""The program's own spans in a profiler trace, and the split of the stream
loop's host time that they give.

The serving path records `jax.profiler.TraceAnnotation` spans named
``serve.*`` (``src/repro/serve/trace.py``): per upload ``serve.process``
around ``serve.upload``, then per dispatch ``serve.prepare``,
``serve.launch`` (stats ``dispatch``, ``valid``, ``slots``) and
``serve.retire`` around ``serve.wait``, then ``serve.concat``; per
admission ``serve.admit`` (stats ``ticket``, ``cls``, ``stream``).
`read_program` returns them with their stats. `ProgramSpans` keeps those
inside the window of a `bench.trace.Trace` and reduces them. They nest,
unlike the harness spans that `Trace.idle_gaps` splits the idle time by,
so they are kept apart from `Trace.spans`.

A trace of a program without these spans gives an empty list, and every
number of `ProgramSpans.split` is then None.
"""
from __future__ import annotations

import bisect
from pathlib import Path

from bench.trace import overlap

PROGRAM_PREFIX = "serve."


def read_program(trace_dir) -> list:
    """[[name, start_ns, end_ns, {stat: value}], ...]: the host events of
    the newest trace file under ``trace_dir`` whose names start with
    ``serve.``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                out.extend([e.name, e.start_ns, e.end_ns, dict(e.stats)]
                           for e in line.events
                           if e.name.startswith(PROGRAM_PREFIX))
    return out


def _ms_per(total_s: float, n: int):
    return 1e3 * total_s / n if n else None


class ProgramSpans:
    """The program's spans inside one traced window, in seconds on the
    profiler's clock."""

    def __init__(self, trace, events: list):
        self.trace = trace
        self.spans = sorted(
            ((n, s * 1e-9, e * 1e-9, stats) for n, s, e, stats in events
             if trace.start <= s * 1e-9 and e * 1e-9 <= trace.end),
            key=lambda x: x[1])

    def named(self, name: str) -> list:
        """[(start, end, stats)] of the spans ``serve.<name>``, by start."""
        full = PROGRAM_PREFIX + name
        return [(s, e, st) for n, s, e, st in self.spans if n == full]

    def total_s(self, name: str) -> float:
        return sum(e - s for s, e, _ in self.named(name))

    def mean_ms(self, name: str):
        return _ms_per(self.total_s(name), len(self.named(name)))

    def self_s(self, name: str, child: str) -> float:
        """Time inside the ``name`` spans not inside a ``child`` span that
        lies within one of them."""
        parents = self.named(name)
        starts = [s for s, _, _ in parents]
        inside = 0.0
        for s, e, _ in self.named(child):
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and e <= parents[i][1]:
                inside += e - s
        return self.total_s(name) - inside

    def idle_inside(self, chip: int, name: str) -> float:
        """Seconds inside the ``name`` spans in which the chip ran
        nothing."""
        busy = self.trace.busy[chip]
        return sum((e - s) - overlap(busy, s, e)
                   for s, e, _ in self.named(name))

    def split(self, stream_cls: str = "stream") -> dict:
        """The stream loop's host time by part, in ms, None where the
        window holds no span to read:

        - ``serve_gap_ms``: device idle inside ``serve.process`` per
          ``serve.launch``, averaged over the chips;
        - ``prepare_ms``, ``launch_ms``, ``wait_ms``: mean span;
        - ``retire_ms``: mean ``serve.retire`` less its ``serve.wait``;
        - ``process_ms``: ``serve.process`` time per ``serve.launch``;
        - ``upload_ms``: ``serve.upload`` + ``serve.concat`` per
          ``serve.process``;
        - ``admit_ms``: mean ``serve.admit`` of the ``stream_cls`` class;
        - ``pad_share``: 100 × (1 − Σ``valid`` ÷ Σ``slots``) over the
          ``serve.launch`` spans, in %.
        """
        launches = self.named("launch")
        n = len(launches)
        gaps = [self.idle_inside(c, "process") / n
                for c in self.trace.chips] if n else []
        admits = [e - s for s, e, st in self.named("admit")
                  if st.get("cls") == stream_cls]
        slots = sum(st["slots"] for _, _, st in launches)
        valid = sum(st["valid"] for _, _, st in launches)
        return {
            "serve_gap_ms": 1e3 * sum(gaps) / len(gaps) if gaps else None,
            "prepare_ms": self.mean_ms("prepare"),
            "launch_ms": self.mean_ms("launch"),
            "retire_ms": _ms_per(self.self_s("retire", "wait"),
                                 len(self.named("retire"))),
            "wait_ms": self.mean_ms("wait"),
            "process_ms": _ms_per(self.total_s("process"), n),
            "upload_ms": _ms_per(self.total_s("upload")
                                 + self.total_s("concat"),
                                 len(self.named("process"))),
            "admit_ms": _ms_per(sum(admits), len(admits)),
            "pad_share": 100.0 * (1.0 - valid / slots) if slots else None,
        }

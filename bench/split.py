#!/usr/bin/env python3
"""Where the stream loop's host time goes, from the program's own spans:
traced windows of a cell, many seeds in one process.

    python3 bench/split.py --workload mbio.archive --seconds 10 --seeds 1,2,3

Each seed draws its own data and runs one window of the cell's mix through
the timed path under a profiler session, as ``bench/run.py --trace 1``
does. One JSON line per seed: ``frames_per_s`` and ``host_gap_ms`` as the
harness reads them, ``split``, the stream loop's parts from the program's
``serve.*`` spans (`bench.spans.ProgramSpans.split`), and ``kernel_ops``,
the device operations the cell's ``KERNEL`` names match.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402
from bench.lib import host_gap_ms  # noqa: E402
from bench.spans import ProgramSpans, read_program  # noqa: E402
from bench.trace import Trace, read_xplane  # noqa: E402


def traced(reg, cell, seed: int, seconds: float) -> dict:
    import jax

    prep = run.prepare(cell, seed, seconds, {})
    trace_dir = tempfile.mkdtemp(prefix="bench-split-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    records, t0, t1, _ = run.drive(prep, seconds)
    jax.profiler.stop_trace()
    ctx = run.Context(cell.cfg, records, t1 - t0, 0.0, len(cell.devices),
                      None, {}, cell.app_mod.KERNEL)
    ctx.trace = Trace(read_xplane(trace_dir))
    program = ProgramSpans(ctx.trace, read_program(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    kernel_ops = sorted({n for c in ctx.trace.chips
                         for n, _, _ in ctx.trace.ops[c]
                         if any(k in n for k in ctx.kernel)})
    return {"seed": seed,
            "frames_per_s": reg.module("metrics", "frames_per_s").read(ctx),
            "host_gap_ms": host_gap_ms(ctx),
            "split": program.split(),
            "kernel_ops": kernel_ops}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    reg = run.Registry.from_root(run.ROOT)
    try:
        devices = run.devices_for(reg.workload(args.workload)["chips"], True)
    except run.NoChip as e:
        run.log(str(e))
        return 2
    run.compile_cache()
    cell = run.load_cell(reg, args.workload, devices)
    for s in filter(None, args.seeds.split(",")):
        print(json.dumps(traced(reg, cell, int(s), args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

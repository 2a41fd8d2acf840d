#!/usr/bin/env python3
"""Rate sweep of an open-loop cell, to find the highest rate the system
sustains (the knee) once; the cell's mix then offers a fixed share of it.

    python3 bench/sweep.py --workload mbio.sync --seconds 10 \
        --rates 60,90,120,150 --seed 5

One process, set-up paid once. For each rate (ascending) the cell's mix is
run at that rate for ``--seconds``: one JSON line with the latency median
and 95th percentile, and how long after the window closed the last upload
finished (``drain_s``). Past the knee the queue grows all through the
window, so ``drain_s`` and the tail grow with it. The last line names the
knee: the highest rate below the first one at which an upload failed or
the last upload finished more than ``KEEPS_UP_S`` after the window
closed, and four fifths of it, the rate a cell below the knee offers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402
from bench.lib import percentile  # noqa: E402

KEEPS_UP_S = 0.25


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    reg = run.Registry.from_root(run.ROOT)
    try:
        devices = run.devices_for(reg.workload(args.workload)["chips"], True)
    except run.NoChip as e:
        run.log(str(e))
        return 2
    run.compile_cache()
    base = run.load_cell(reg, args.workload, devices)
    knee = None
    for rate in sorted(float(r) for r in args.rates.split(",")):
        cell = dataclasses.replace(base, mix={**base.mix, "rate_per_s": rate})
        prep = run.prepare(cell, args.seed, args.seconds, {})
        records, t0, t1, _ = run.drive(prep, args.seconds)
        lat = [1e3 * (r.end - r.due) for r in records
               if r.end is not None and r.error is None]
        drain_s = t1 - t0 - args.seconds
        failed = len(records) - len(lat)
        print(json.dumps({
            "rate_per_s": rate, "uploads": len(records),
            "failed": failed,
            "p50_ms": percentile(lat, 50), "p95_ms": percentile(lat, 95),
            "max_ms": max(lat, default=0.0),
            "drain_s": drain_s}), flush=True)
        del prep, records
        if failed or not lat or drain_s > KEEPS_UP_S:
            break
        knee = rate
    print(json.dumps({"knee_per_s": knee,
                      "four_fifths_per_s": None if knee is None
                      else 0.8 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

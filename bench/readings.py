#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, many seeds in one
process (set-up and compiles paid once):

    python3 bench/readings.py --workload mbio.archive --seconds 3 \
        --seeds 11,12,13 --control-seeds 21,22,23

Each seed draws its own data and numbers and runs a short window of the
cell's mix through the timed path, then the same check as `bench/run.py`.
Sound seeds run the program; control seeds put the plain reference,
computed in bfloat16 (`ref.<graph>.reference(lowp=True)`), in the
program's place. One JSON line per seed, then the largest sound reading and
the smallest control reading of each number.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


def lowp_process(cell, params):
    """A stand-in for `BiosignalStream.process`: the reference in
    bfloat16."""
    st = cell.cfg["stream"]

    def process(_stream, signal):
        return cell.ref_mod.reference(
            params, signal, window=st["window"], hop=st["hop"],
            fft_size=cell.cfg["app"]["fft_size"], lowp=True)
    return process


def reading(cell, seed: int, seconds: float, control: bool) -> dict:
    from repro.serve.stream import BiosignalStream

    prep = run.prepare(cell, seed, seconds, {})
    saved = BiosignalStream.process
    if control:
        BiosignalStream.process = lowp_process(cell, prep.params)
    try:
        records, _, _, _ = run.drive(prep, seconds)
    finally:
        BiosignalStream.process = saved
    failed = sum(1 for r in records if r.error is not None or r.end is None)
    numbers = run.check(cell, prep.params, prep.traffic, records, seed)
    return {"seed": seed, "kind": "control" if control else "sound",
            "attempted": len(records), "failed": failed, "numbers": numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    reg = run.Registry.from_root(run.ROOT)
    try:
        devices = run.devices_for(reg.workload(args.workload)["chips"], True)
    except run.NoChip as e:
        run.log(str(e))
        return 2
    run.compile_cache()
    cell = run.load_cell(reg, args.workload, devices)
    rows = []
    for seeds, control in ((args.seeds, False), (args.control_seeds, True)):
        for s in filter(None, seeds.split(",")):
            rows.append(reading(cell, int(s), args.seconds, control))
            print(json.dumps(rows[-1]), flush=True)
    for kind, pick in (("sound", max), ("control", min)):
        got = [r["numbers"] for r in rows if r["kind"] == kind]
        if got:
            print(json.dumps({kind: {k: pick(g[k] for g in got)
                                     for k in got[0]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

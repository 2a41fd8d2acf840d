"""Diff two autotune artifacts (BENCH_autotune.json) across commits, and
GATE the pinned-shape perf records.

CI's bench smoke writes the measured block-size winners next to the
BENCH_*.json perf records; this tool compares the current commit's winners
against the previous run's artifact and prints added / removed / changed
entries, so a perf regression that traces back to a different measured
block choice is visible in the job log.

``--gate`` promotes the diff from informational to a failure on the PINNED
shapes: entries `core.autotune.record_pinned` wrote from the bench run's
own paired reps (table5's stream/pipeline headline shapes). A pinned shape
fails when its runner-normalized metric regresses beyond a variance
threshold derived from the two runs' own rep spreads:

* only entries carrying a paired ``ratio`` in BOTH runs (fused-vs-baseline
  speedup, measured ALTERNATELY in one rep loop) are gated — absolute wall
  times are not comparable across heterogeneous CI runners, a same-run
  paired ratio is;
* ratio-less or mixed records are reported informationally, never failed
  (gating raw us across different runner hardware would flap).

A pinned record that the baseline has and the current run lacks fails the
gate, unless its name is in ``RETIRED``: a shape whose code was deleted
on purpose. Retired names are reported, never failed; every other missing
record still fails.

Raw winner drift (a different measured block choice) stays informational
even under ``--gate`` — on shared runners near-tied candidates flip on
machine noise; the gate fires only when the pinned perf actually moved.

A MISSING or UNREADABLE baseline is never a silent pass: the gate prints
an explicit "no baseline, gate SKIPPED" warning and exits with the
distinct code ``EXIT_NO_BASELINE`` (3) — so a broken artifact download
cannot masquerade as a green gate. CI (where the first run on a fresh
repo legitimately has no baseline) passes ``--missing-baseline-ok`` to
turn that path into a loudly-labelled success instead.

Usage:  python -m benchmarks.diff_autotune OLD.json NEW.json
            [--strict|--gate] [--missing-baseline-ok]
"""
from __future__ import annotations

import argparse
import json
import sys

# tolerance floor: rep spread on a quiet machine is a few %, but CI
# neighbours can inflate it — never gate tighter than this
RATIO_FLOOR = 0.10
SPREAD_MULT = 3.0
# distinct exit path for "the baseline artifact never arrived": neither
# the green 0 nor the regression 1
EXIT_NO_BASELINE = 3
# pinned records whose measured code was deleted: a baseline that still
# holds them reports them as retired instead of failing the gate
RETIRED = {
    "table5/stream_resident": "the device-resident ring loop it timed "
                              "was deleted",
}


def _read(path: str):
    """Parsed artifact, or None when missing/unreadable (the caller turns
    that into the explicit no-baseline path — never a silent pass)."""
    try:
        with open(path) as f:
            return json.load(f)
    except Exception as e:
        print(f"WARNING: cannot read {path}: {type(e).__name__}: {e}",
              file=sys.stderr)
        return None


def _winners(data: dict) -> dict:
    return {json.dumps(e["key"]): int(e["block_rows"])
            for e in data.get("autotune_winners", [])}


def diff(old: dict, new: dict) -> list[str]:
    lines = []
    for k in sorted(new.keys() - old.keys()):
        lines.append(f"+ {k} -> {new[k]}")
    for k in sorted(old.keys() - new.keys()):
        lines.append(f"- {k} (was {old[k]})")
    for k in sorted(old.keys() & new.keys()):
        if old[k] != new[k]:
            lines.append(f"~ {k}: {old[k]} -> {new[k]}")
    return lines


def gate_pinned(old: dict, new: dict) -> tuple[list[str], list[str]]:
    """Compare pinned perf records; returns (report, failures)."""
    report, failures = [], []
    for name in sorted(old.keys() & new.keys()):
        o, n = old[name], new[name]
        spread = max(o.get("spread", 0.0), n.get("spread", 0.0))
        if "ratio" in o and "ratio" in n:
            tol = max(RATIO_FLOOR, SPREAD_MULT * spread)
            drop = 1.0 - n["ratio"] / max(o["ratio"], 1e-9)
            line = (f"{name}: paired ratio {o['ratio']:.2f}x -> "
                    f"{n['ratio']:.2f}x (tol {tol:.0%}, rep spread "
                    f"{spread:.0%})")
            if drop > tol:
                failures.append(f"{line}  REGRESSED {drop:.0%}")
            else:
                report.append(f"{line}  ok")
        else:
            # no paired ratio on one side: raw us across (possibly
            # different) runner hardware is not gateable — report only
            report.append(f"{name}: {o['us']:.1f}us -> {n['us']:.1f}us "
                          f"(no paired ratio; informational)")
    for name in sorted(new.keys() - old.keys()):
        report.append(f"{name}: new pinned shape (no previous record)")
    for name in sorted(old.keys() - new.keys()):
        if name in RETIRED:
            report.append(f"{name}: retired ({RETIRED[name]})")
            continue
        failures.append(f"{name}: pinned record DISAPPEARED — the bench "
                        f"no longer measures this shape")
    return report, failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("old")
    ap.add_argument("new")
    ap.add_argument("--strict", action="store_true",
                    help="exit non-zero when any winner changed")
    ap.add_argument("--gate", action="store_true",
                    help="fail on pinned-shape perf regressions beyond the "
                         "paired-rep variance threshold (winner drift "
                         "alone stays informational)")
    ap.add_argument("--missing-baseline-ok", action="store_true",
                    help="exit 0 (instead of the distinct no-baseline code "
                         f"{EXIT_NO_BASELINE}) when the OLD artifact is "
                         "missing/unreadable — for the legitimate "
                         "first-run-on-a-fresh-repo case; the skip is "
                         "still printed loudly")
    args = ap.parse_args()
    old_data = _read(args.old)
    # the current run's artifact must always parse: a broken NEW file is
    # a bench bug, not a missing baseline
    new_data = _read(args.new)
    if new_data is None:
        print(f"diff_autotune: current artifact {args.new} unreadable")
        raise SystemExit(1)
    if old_data is None:
        print(f"WARNING: no baseline ({args.old} missing/unreadable), "
              f"gate SKIPPED - nothing was compared")
        raise SystemExit(0 if args.missing_baseline_ok else EXIT_NO_BASELINE)
    old, new = _winners(old_data), _winners(new_data)
    lines = diff(old, new)
    if not lines:
        print(f"autotune winners unchanged ({len(new)} entries)")
    else:
        print(f"autotune winners changed ({len(old)} -> {len(new)} entries):")
        for line in lines:
            print(" ", line)
    if args.gate:
        report, failures = gate_pinned(old_data.get("pinned", {}),
                                       new_data.get("pinned", {}))
        for line in report:
            print("  pinned:", line)
        for line in failures:
            print("  pinned:", line)
        if failures:
            print(f"pinned-shape gate FAILED ({len(failures)} regression(s))")
            raise SystemExit(1)
        print(f"pinned-shape gate ok ({len(report)} shape(s))")
    if lines and args.strict:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

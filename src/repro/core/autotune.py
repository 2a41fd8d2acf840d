"""Measured block-size autotuning for the Pallas kernels.

`VWRSpec.max_block_bytes` picks the row-block `rb` with a static formula
(largest block whose n_vwrs live copies fit the VMEM budget). That is the
paper's *design-time* reasoning about the 4096-bit VWR width; at *run* time
the right refill width depends on the actual kernel and shape. This module
replaces the formula with measurement: time a handful of candidate `rb`
values on the real arrays, keep the fastest, and cache the winner per
(kernel, shape) key so the search cost is paid once per process.

Shared by the fft / fir / fused-pipeline kernels (their `ops` wrappers grow
an ``autotune=True`` knob) and the streaming window runtime.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable

import jax

from repro.core.vwr import SUBLANES

# (kernel-name, shape...) -> winning block_rows
_CACHE: dict[tuple, int] = {}
# pinned-shape perf records: name -> {"us", "spread", ...} — the paired
# rep measurements CI's regression gate compares across commits
_PINNED: dict[str, dict] = {}


def clear_cache() -> None:
    _CACHE.clear()
    _PINNED.clear()


def cache_snapshot() -> dict:
    return dict(_CACHE)


def record_pinned(name: str, times_us: list, *,
                  baseline_us: list | None = None) -> dict:
    """Record a pinned benchmark shape's paired-rep timings for the
    cross-commit gate (`benchmarks/diff_autotune.py --gate`).

    ``times_us`` are the per-rep wall times of the pinned configuration;
    ``baseline_us`` (optional) a PAIRED sibling timed alternately in the
    same rep loop. The gate compares the runner-normalized ratio
    baseline/us when a baseline exists (cross-runner absolute times are
    not comparable; a same-run paired ratio is), with the tolerance taken
    from the run's own rep spread. Spread is (median - min)/min — robust
    to the occasional 5-10x GC/neighbour outlier rep that would otherwise
    blow the gate tolerance wide open.
    """
    def _spread(ts):
        ts = sorted(ts)
        return (ts[len(ts) // 2] - ts[0]) / max(ts[0], 1e-9)

    best = min(times_us)
    rec = {"us": best, "spread": _spread(times_us), "reps": len(times_us)}
    if baseline_us is not None:
        rec["ratio"] = min(baseline_us) / max(best, 1e-9)
        rec["spread"] = max(rec["spread"], _spread(baseline_us))
    _PINNED[name] = rec
    return rec


def _freeze(x):
    """JSON round-trip: lists (de)serialize to tuples, recursively — cache
    keys are nested tuples like (name, rows, window, hop, outputs, dtype)."""
    return tuple(_freeze(v) for v in x) if isinstance(x, (list, tuple)) else x


def save_cache(path: str) -> int:
    """Persist the winners as a JSON artifact (next to the BENCH_*.json
    perf records) so later processes warm-start instead of re-measuring
    and CI can diff winners across commits. Pinned-shape perf records
    (`record_pinned`) ride along for the regression gate. Returns the
    winner entry count."""
    entries = [{"key": list(k), "block_rows": v}
               for k, v in sorted(_CACHE.items(), key=lambda kv: str(kv[0]))]
    with open(path, "w") as f:
        json.dump({"autotune_winners": entries, "pinned": dict(_PINNED)},
                  f, indent=1, default=list)
    return len(entries)


def load_cache(path: str) -> int:
    """Warm-start the in-process cache from a `save_cache` artifact.
    Missing file is not an error (first run of a fresh checkout). Pinned
    perf records are deliberately NOT loaded — they must be re-measured
    every run, or the cross-commit gate would compare an artifact against
    a copy of itself. Returns the number of loaded winner entries."""
    if not os.path.exists(path):
        return 0
    with open(path) as f:
        data = json.load(f)
    entries = data.get("autotune_winners", [])
    for e in entries:
        _CACHE[_freeze(e["key"])] = int(e["block_rows"])
    return len(entries)


def candidate_block_rows(rows: int, *, max_candidates: int = 4) -> list[int]:
    """Candidate row-blocks for an R-row operand: divisors of R (so the grid
    tiles exactly), preferring sublane multiples, largest first. The
    whole-batch block (rows itself) is always the first candidate — it is
    the largest divisor, sublane-aligned whenever any divisor is."""
    divs = [d for d in range(1, rows + 1) if rows % d == 0]
    aligned = [d for d in divs if d % SUBLANES == 0]
    pool = sorted(aligned or divs, reverse=True)
    return pool[:max_candidates]


def _measure(fn: Callable[[], object], reps: int) -> float:
    jax.block_until_ready(fn())                 # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def autotune_block_rows(key: tuple, candidates: list[int],
                        build: Callable[[int], Callable[[], object]],
                        *, reps: int = 3) -> int:
    """Pick the fastest `block_rows` among `candidates`.

    ``build(rb)`` returns a zero-arg callable running the kernel with that
    block size; each candidate is compiled once and timed best-of-`reps`.
    The winner is cached under ``key`` for the life of the process.
    """
    if key in _CACHE:
        return _CACHE[key]
    if len(candidates) == 1:
        _CACHE[key] = candidates[0]
        return candidates[0]
    timed = [(_measure(build(rb), reps), rb) for rb in candidates]
    best = min(timed)[1]
    _CACHE[key] = best
    return best


def tuned_block_rows(name: str, rows: int, extras: tuple,
                     run: Callable[[int], object]) -> int:
    """One-call wiring for the kernel `ops` wrappers: build the per-shape
    cache key, enumerate candidates, measure, cache. ``run(rb)`` executes
    the kernel with that block size."""
    key = _freeze((name, rows) + tuple(extras))
    return autotune_block_rows(key, candidate_block_rows(rows),
                               lambda rb: lambda: run(rb))


def candidate_stream_block_frames(n_frames: int, window: int, hop: int,
                                  *, max_candidates: int = 4) -> list[int]:
    """Candidate frame-blocks for the raw-signal streaming kernel. The
    grid pads the frame count, so candidates need not divide it — but the
    body chunk (block_frames*hop samples) must cover the window-hop
    overlap spill, which floors every candidate."""
    floor = 1 if window <= hop else -(-(window - hop) // hop)
    pool = {c for c in (1, 2, 4, 8, 16, SUBLANES * 4)
            if floor <= c <= max(n_frames, floor)}
    pool |= {floor, min(max(n_frames, floor), max(8, floor))}
    return sorted(pool, reverse=True)[:max_candidates]


def tuned_stream_block_frames(name: str, n_frames: int, window: int,
                              hop: int, outputs: tuple, dtype: str,
                              run: Callable[[int], object],
                              n_columns: int = 1,
                              shares: tuple | None = None) -> int:
    """`tuned_block_rows` for the raw-signal streaming kernel: the cache
    key carries the full (window, hop, outputs) shape — the same window
    batch tuned for classification-only traffic (no `filtered` write) may
    legitimately pick a different block than the all-outputs variant —
    plus the column count when sharded (`n_columns > 1`): each column
    stages only ~n_frames/D frames, so the right block is per-(shape, D).
    A non-uniform deal additionally carries its quantized share signature
    (``shares``, the `column_shares` frame counts): a winner measured on
    a (9, 19, 18, 18) deal must not leak onto the (16,)*4 equal deal.
    Candidates are enumerated over the WIDEST per-column share — the
    column that bounds the dispatch wall."""
    sig = () if shares is None else ("w",) + tuple(shares)
    key = _freeze((name, n_frames, window, hop, outputs, dtype)
                  + ((n_columns,) if n_columns > 1 else ()) + sig)
    per_col = max(shares) if shares is not None else -(-n_frames // n_columns)
    return autotune_block_rows(
        key, candidate_stream_block_frames(max(per_col, 1), window, hop),
        lambda rb: lambda: run(rb))


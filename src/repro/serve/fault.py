"""Fault-tolerant column serving: heartbeats, dead-column drain, and
deterministic requeue.

The flexibility claim behind column replication only holds if columns are
INTERCHANGEABLE — and interchangeable must include "one of them died
mid-stream". This module connects the pure decision logic in
`runtime/fault.py` (heartbeat timeout, straggler eviction, capped-backoff
retry) to the live streaming runtime (`serve/stream.py`,
`serve/engine.py:ColumnScheduler`):

* the telemetry retire feed doubles as the HEARTBEAT source — every
  retire beats the column's `runtime.fault.HeartbeatMonitor` (no
  separate liveness channel);
* per-column dispatch wall times feed `runtime.fault.StragglerDetector`,
  so a column that is persistently slow gets evicted BEFORE it fails;
* a dead column's streams DRAIN onto survivors
  (`serve/engine.py:ColumnScheduler.mark_dead`) and its *unretired*
  hop-aligned frame ranges REQUEUE across them
  (`kernels/pipeline/shard.py:requeue_ranges`), with the degraded deal
  recomputed via `serve/engine.py:ColumnScheduler.deal_weights` — dead
  columns zeroed, riding `column_shares`' zero-weight path;
* transient dispatch failures are retried in place with capped
  exponential backoff (`runtime.fault.Supervisor.call`), never escalated
  to a death.

THE INVARIANT (the chaos property `tests/test_chaos.py` sweeps): for any
injected fault schedule — column deaths at arbitrary dispatch steps,
transient faults, stragglers, hangs — the recovered output is
**bit-identical** to the fault-free run, just redistributed across
surviving columns. That holds because every unit of
requeued work is a HOP-ALIGNED frame range (frame i depends only on
samples ``[i*hop, i*hop + window)``; the chunk FIR's frame-local
transient patch makes each frame independent of where the signal is
cut — the same two facts that make the multi-column deal numerically
invisible, see `kernels/pipeline/shard.py`).

`FaultInjector` is the chaos harness: a deterministic fault schedule
keyed by (column, per-column dispatch sequence number), injectable
into `serve/stream.py:BiosignalStream._dispatch_chunk` and the per-upload
loop (`serve/stream.py:BiosignalStream._process_upload`). The bench gate
(`run.py --check-fault`, `docs/BENCHMARKS.md`) pins the recovery cost:
killing one of D=4 columns mid-run must keep the modelled dispatch wall
within 1.5x of the fault-free run, outputs bit-identical.

The injector is SHARED ACROSS BOTH TRAFFIC CLASSES the repo serves: the
"column" key is just the supervised unit's index, so the fault-tolerant
LM engine (`serve/engine_fault.py:FaultTolerantEngine`) injects the same
schedules with an engine SLOT standing in as the column (a slot's
admission prefill is its seq 0, decode steps follow). One chaos
vocabulary — kill / transient / hang_from / slow, one `VirtualClock` —
drives both the frame-requeue property (`tests/test_chaos.py`) and the
request-replay property (`tests/test_engine_fault.py`).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque

import jax
import jax.numpy as jnp

from repro.core.biosignal import BiosignalApp, make_app
from repro.kernels.pipeline.kernel import empty_outputs
from repro.kernels.pipeline.shard import column_shares, requeue_ranges
from repro.runtime.fault import (ColumnDeadError, StragglerDetector,
                                 Supervisor, TransientDispatchError)
from repro.serve.engine import ColumnScheduler
from repro.serve.stream import (BiosignalStream, StreamConfig,
                                StreamTelemetry, frame_count)

__all__ = ["VirtualClock", "ColumnHungError", "FaultInjector",
           "FaultTolerantColumnRunner"]


class VirtualClock:
    """A deterministic monotonic clock tests/benches advance by hand —
    the injectable time source `FaultInjector`, `StreamTelemetry`, and
    `ColumnScheduler` share so heartbeat timeouts and EWMA math replay
    exactly."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += float(dt)
        return self.t


# ColumnHungError moved under the serve/errors.py taxonomy (ServeError
# root); re-imported here so its historical home keeps working
from repro.serve.errors import ColumnHungError  # noqa: E402,F401


@dataclasses.dataclass
class FaultInjector:
    """Deterministic fault schedule for the chaos harness.

    Faults are keyed by ``(column, seq)`` where ``seq`` is the
    per-column DISPATCH sequence number (0-based, incremented on every
    `on_dispatch` call — retried attempts count, so "two transient
    failures then success" is entries at seq s and s+1).

    * ``kill[column] = seq`` — the dispatch raises
      `runtime.fault.ColumnDeadError` (fatal; the serving layer drains
      and requeues).
    * ``transient`` — set of ``(column, seq)`` dispatches that raise
      `runtime.fault.TransientDispatchError` (retryable; the stream's
      `runtime.fault.Supervisor.call` backoff absorbs them).
    * ``hang_from[column] = seq`` — from that dispatch on, the column is
      wedged (`ColumnHungError`): no result, no retire, no heartbeat.
      Requires heartbeat supervision (or a real wall-clock) to resolve.
    * ``slow[column] = extra_s`` — every dispatch on the column takes
      ``extra_s`` extra virtual seconds (straggler simulation).

    ``dispatch_s`` is the virtual cost of a healthy dispatch; when
    ``clock`` (a `VirtualClock`) is set, every `on_dispatch` advances it
    by ``dispatch_s + slow.get(column, 0)`` so heartbeat timeouts and
    straggler medians replay deterministically. `reset` rewinds the
    sequence counters (NOT the clock) so one schedule can be replayed
    across bench reps.
    """
    kill: dict = dataclasses.field(default_factory=dict)
    transient: set = dataclasses.field(default_factory=set)
    hang_from: dict = dataclasses.field(default_factory=dict)
    slow: dict = dataclasses.field(default_factory=dict)
    dispatch_s: float = 0.0
    clock: VirtualClock | None = None
    _seq: dict = dataclasses.field(default_factory=dict)

    def reset(self) -> None:
        self._seq.clear()

    def on_dispatch(self, column: int) -> None:
        seq = self._seq.get(column, 0)
        self._seq[column] = seq + 1
        if self.clock is not None:
            self.clock.advance(self.dispatch_s +
                               float(self.slow.get(column, 0.0)))
        if column in self.hang_from and seq >= self.hang_from[column]:
            raise ColumnHungError(column)
        if self.kill.get(column) == seq:
            raise ColumnDeadError(column)
        if (column, seq) in self.transient:
            raise TransientDispatchError(
                f"injected transient fault on column {column} seq {seq}")


class FaultTolerantColumnRunner:
    """Drives ONE signal across D columns with fault-tolerant requeue —
    the serving front-end of the detection → drain → requeue → re-deal
    closed loop.

    The signal's frames are dealt into hop-aligned per-column ranges
    (`column_shares` exact-sum equal deal, or ``weights``), each range
    dispatched through the column's pinned `serve.stream.BiosignalStream`,
    one range of ``cfg.batch_windows`` frames at a time. After every
    dispatch round `ColumnScheduler.supervise` runs: a column is
    declared dead on `runtime.fault.ColumnDeadError`, heartbeat timeout
    (the retire feed went quiet), or straggler eviction; its streams
    drain and its UNRETIRED ranges requeue across survivors via
    `requeue_ranges` under the degraded `ColumnScheduler.deal_weights`
    (dead columns zeroed; equal weights while telemetry is cold). The
    last column dying raises
    `runtime.fault.InsufficientHealthyWorkers`.

    `process` returns the full framed output dict, bit-identical to the
    fault-free single-column reference for ANY injected fault schedule
    (the chaos property). ``column_busy`` holds per-column busy seconds
    (sum of dispatch walls) — ``max(column_busy)`` is the modelled
    dispatch wall on a real D-device machine, the quantity the
    ``--check-fault`` bench gate bounds.
    """

    def __init__(self, app: BiosignalApp | None = None,
                 cfg: StreamConfig | None = None, *, n_columns: int,
                 injector: FaultInjector | None = None,
                 weights=None, deal_band: float = 0.0,
                 heartbeat_timeout: float | None = None,
                 straggler: StragglerDetector | None = None,
                 retry: Supervisor | None = None, devices=None, clock=None,
                 max_idle_passes: int = 10_000):
        assert n_columns >= 1, n_columns
        self.app = app or make_app()
        self.cfg = cfg or StreamConfig()
        assert self.cfg.n_columns == 1, \
            "the runner deals ranges itself; streams stay column-pinned"
        self.injector = injector
        self.weights = weights
        self.deal_band = deal_band
        self.max_idle_passes = max_idle_passes
        self.clock = clock if clock is not None else (
            injector.clock if injector is not None and
            injector.clock is not None else time.perf_counter)
        self.telemetry = StreamTelemetry(clock=self.clock)
        if devices is None:
            # distinct devices while they last; columns share beyond that
            local = jax.devices()
            devices = [local[d % len(local)] for d in range(n_columns)]
        self.scheduler = ColumnScheduler(
            devices, telemetry=self.telemetry,
            heartbeat_timeout=heartbeat_timeout, straggler=straggler,
            clock=self.clock)
        # one pinned stream per column: an idle scheduler admits
        # round-robin, so stream "col d" lands on column d exactly
        self.streams = {}
        for d in range(n_columns):
            sid = f"col{d}"
            device = self.scheduler.admit(sid)
            self.streams[d] = BiosignalStream(
                self.app, self.cfg, device=device, telemetry=self.telemetry,
                stream_id=sid, column=d, injector=injector, retry=retry)
        self.column_busy = [0.0] * n_columns
        self.dispatches = 0
        self.requeues = 0

    @property
    def n_columns(self) -> int:
        return len(self.streams)

    def live_columns(self) -> list[int]:
        return self.scheduler.healthy_columns()

    # ------------------------------------------------------------ deal

    def _initial_queues(self, n_frames: int) -> list[deque]:
        """Deal frames into per-column queues of hop-aligned ranges: a
        column's contiguous share splits into ``batch_windows``-frame
        dispatch ranges."""
        w = self.weights if self.weights is not None \
            else (1.0,) * self.n_columns
        shares = column_shares(n_frames, self.n_columns, w)
        queues = [deque() for _ in range(self.n_columns)]
        start = 0
        bw = self.cfg.batch_windows
        for d, share in enumerate(shares):
            for s in range(start, start + share, bw):
                queues[d].append((s, min(bw, start + share - s)))
            start += share
        return queues

    def _degraded_weights(self) -> list[float]:
        """The re-deal weight vector for requeued work: measured column
        rates with dead columns zeroed (`ColumnScheduler.deal_weights`),
        or the equal deal over survivors while telemetry is cold."""
        measured = self.scheduler.deal_weights(band=self.deal_band)
        if measured is not None:
            return list(measured)
        return [0.0 if c in self.scheduler.dead else 1.0
                for c in range(self.n_columns)]

    def _requeue_from(self, column: int, queues: list[deque]) -> None:
        """Drain a dead column's queue and deal its unretired ranges
        across the survivors (hop-aligned splits, degraded weights)."""
        unretired = list(queues[column])
        queues[column].clear()
        if not unretired:
            return
        parts = requeue_ranges(unretired, self.n_columns,
                               self._degraded_weights())
        for d, runs in enumerate(parts):
            queues[d].extend(runs)
        self.requeues += 1

    # -------------------------------------------------------- dispatch

    def _chunk(self, sig, start: int, count: int):
        cfg = self.cfg
        s0 = start * cfg.hop
        return sig[s0: s0 + (count - 1) * cfg.hop + cfg.window]

    def _dispatch(self, column: int, sig, start: int, count: int) -> dict:
        out = self.streams[column].process(self._chunk(sig, start, count))
        self.dispatches += 1
        return out

    # ---------------------------------------------------------- serve

    def process(self, signal) -> dict:
        """All framed outputs for ``signal`` under the injected fault
        schedule — bit-identical to the fault-free run. Raises
        `runtime.fault.InsufficientHealthyWorkers` if every column dies,
        and RuntimeError if the fleet stops progressing without a
        supervisable cause (a hung column with no heartbeat timeout)."""
        cfg = self.cfg
        sig = jnp.asarray(signal)
        assert sig.ndim == 1, sig.shape
        n = frame_count(sig.shape[0], cfg.window, cfg.hop)
        if n == 0:
            w = self.app.svm_w.shape
            return empty_outputs(cfg.window, w[0], w[1], sig.dtype,
                                 cfg.outputs)
        queues = self._initial_queues(n)
        results: dict[int, tuple[int, dict]] = {}
        idle = 0
        while True:
            pending = [d for d in self.live_columns() if queues[d]]
            if not pending:
                break
            progressed = False
            for d in pending:
                if d in self.scheduler.dead:    # died earlier this round
                    continue
                start, count = queues[d][0]
                t0 = self.clock()
                try:
                    out = self._dispatch(d, sig, start, count)
                except ColumnHungError:
                    continue        # wedged: no retire — only the
                    #                 heartbeat timeout can resolve this
                except ColumnDeadError:
                    self.scheduler.mark_dead(d)
                    self._requeue_from(d, queues)
                    continue
                dt = self.clock() - t0
                queues[d].popleft()
                results[start] = (count, out)
                self.column_busy[d] += dt
                self.scheduler.record_batch_time(d, dt)
                progressed = True
            newly = self.scheduler.supervise()
            for d in newly:
                self._requeue_from(d, queues)
            if progressed or newly:
                idle = 0
            else:
                idle += 1
                if idle > self.max_idle_passes:
                    raise RuntimeError(
                        "fleet stopped progressing (hung column without "
                        "heartbeat supervision?)")
        # assemble: the requeued ranges must tile [0, n) exactly once
        items = sorted(results.items())
        pos = 0
        for start, (count, _) in items:
            assert start == pos, (start, pos)
            pos += count
        assert pos == n, (pos, n)
        outs = [out for _, (_, out) in items]
        return {k: jnp.concatenate([o[k] for o in outs]) for k in outs[0]}

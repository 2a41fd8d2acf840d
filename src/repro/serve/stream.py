"""Streaming window runtime: continuous biosignal traffic through the fused
pipeline kernel.

The paper's deployment model (§4.4.2) is a sensor feeding windows to the
accelerator forever; ours is the serving analogue. The default feed is
ZERO-COPY: the runtime hands the kernel contiguous RAW signal chunks and the
kernel builds the overlapping (window, hop) frames in VMEM itself
(`kernels/pipeline.pipeline_stream_pallas`) — no host gather, no duplicated
overlap bytes in HBM, no materialized zero-padding frames for the tail
batch. The pre-framed path (`framing="host"`) is kept as the fallback and
cross-check reference. Dispatch is pipelined: while batch k's outputs are
being consumed on the host, up to `depth` later batches are already in
flight (JAX async dispatch is the host-side ping-pong buffer, mirroring the
SPM's double-buffered line fills; depth=2 measured WITHIN NOISE of the
depth=1 double buffer on the CPU interpret path — ±4% across trials, see
table5/stream_depth* rows — so the default stays 1 and the knob is there
for real accelerators with wider dispatch gaps). An ``outputs``
selection drops unrequested HBM writes — classification-only traffic never
writes filtered windows — and the kernel row-block can be autotuned from
measured candidates (`core/autotune.py`) instead of the static VWRSpec
formula.

MULTI-COLUMN: ``n_columns > 1`` is the VWR2A column-replication analogue
for this path (archsim deals passes round-robin across columns; we deal
hop-aligned raw chunks across devices). Each dispatch covers
``batch_windows`` frames PER COLUMN, `shard_map`ped over the `data` axis of
a local mesh when the process has >= n_columns devices (on a laptop/CI box:
run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``), and
falls back to bit-identical serial column execution otherwise. Independent
streams can instead be pinned to distinct columns via ``device=`` — that is
what `serve.engine.ColumnScheduler` hands out.

TELEMETRY: `StreamTelemetry` measures per-stream and per-column throughput
(an EWMA of windows/s, updated on every retire — the moment `_collect`,
or the per-upload loop, blocks until the dispatched outputs are ready).
The measurements are what make the runtime LOAD-AWARE:
`serve.engine.ColumnScheduler` places new streams on the column with the
least measured load (not just the fewest streams), its `rebalance` step
re-pins streams when the
max/min column-load ratio blows past a threshold, and `deal_weights`
turns measured per-column rates into the non-uniform `column_shares`
deal (`StreamConfig.column_weights`) — a column sharing its device with
another tenant retires slower, so it is dealt proportionally fewer
frames.

PER-UPLOAD LOOP: `BiosignalStream.process` on a raw-chunk (framing
"kernel"), single-column stream runs the whole upload as ONE jitted
program (`_upload_loop`): a `lax.scan` over the upload's dispatches, each
step a hop-aligned slice of the device-resident signal through the same
stream kernel a per-batch dispatch launches. The host pays one upload,
one launch and one wait per upload instead of a Python round trip per
batch. `stream()`, `framing="host"` and ``n_columns > 1`` keep the
host-driven per-batch loop (`_batches`), the REFERENCE path.
`docs/ARCHITECTURE.md` shows the two control loops side by side.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.biosignal import BiosignalApp, make_app
from repro.kernels import interpret_mode
from repro.kernels.pipeline.graph import (canonical_graph_outputs,
                                          frame_width, get_graph_factory,
                                          graph_empty_outputs,
                                          graph_stream_call,
                                          graph_stream_pallas,
                                          ring_chunk_samples)
from repro.kernels.pipeline.kernel import biosignal_graph, empty_outputs
from repro.kernels.pipeline.ops import (OUTPUTS, app_pipeline,
                                        app_pipeline_stream,
                                        canonical_outputs, default_app,
                                        graph_pipeline,
                                        graph_pipeline_stream,
                                        stream_frame_count)
from repro.serve.trace import span


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Shape + policy of one stream's dispatches (`BiosignalStream`'s
    per-upload and per-batch loops read the same fields).

    Invariants the runtimes check: ``window`` meets the graph's own
    frame rule (`kernels.pipeline.graph.frame_width`: ``window >=
    app.fft_size`` for graphs that window a frame's first fft_size
    samples, ``window == frame_length`` for a frame zero-padded to the
    FFT), ``0 < hop <= window`` (frames advance by whole hops; every
    chunk/deal boundary in the kernel and the multi-column split is
    HOP-ALIGNED, which is what makes raw-chunk feeds bit-identical to
    host framing), and
    ``column_weights`` — when set — has exactly ``n_columns`` entries and
    requires ``framing="kernel"``. See `docs/ARCHITECTURE.md` (paper →
    code map) for how these knobs correspond to VWR2A's column/VWR
    geometry.
    """
    window: int = 2048          # samples per frame (the processing window)
    hop: int = 512              # frame stride; < window => overlapping frames
    batch_windows: int = 8      # frames per fused-kernel dispatch PER COLUMN
    autotune: bool = False      # measure the kernel row-block (cached)
    block_rows: int | None = None   # pin the row-block explicitly
    outputs: tuple = OUTPUTS    # which app outputs to compute/write
    framing: str = "kernel"     # "kernel": raw chunks, frames built in VMEM
    #                             "host": gather-framed fallback/reference
    n_columns: int = 1          # column replicas a dispatch is dealt across
    depth: int = 1              # max in-flight batches (1 = classic double
    #                             buffer, the measured CPU winner; 2+ for
    #                             accelerators with wider dispatch gaps)
    column_weights: tuple | None = None   # non-uniform deal weights (one
    #                             per column, e.g. measured rates from
    #                             StreamTelemetry / deal_weights); None =
    #                             the equal deal
    graph: str = "biosignal"    # which registered stage graph runs
    #                             (graph.py:get_graph_factory name; the
    #                             ASR front-end is graph="asr"). The
    #                             default `outputs` then means ALL of
    #                             that graph's outputs. Non-biosignal
    #                             graphs are single-column for now.


# single source of the framing arithmetic (shared with the kernel, whose
# trim logic depends on the same count)
frame_count = stream_frame_count


def frame_signal(signal, window: int, hop: int):
    """(S,) continuous signal -> (n_frames, window) overlapping frames.

    Host-side gather: every sample is duplicated ~window/hop times. Kept
    for the `framing="host"` fallback and as the reference the raw-chunk
    kernel path is tested against."""
    sig = jnp.asarray(signal)
    assert sig.ndim == 1, sig.shape
    n = frame_count(sig.shape[0], window, hop)
    if n == 0:
        return jnp.zeros((0, window), sig.dtype)
    idx = np.arange(n)[:, None] * hop + np.arange(window)[None, :]
    return sig[jnp.asarray(idx)]


def column_mesh(n_columns: int):
    """A `data`-axis mesh over the first n_columns local devices, or None
    when the process doesn't have that many (the sharded entry then runs
    its bit-identical serial-column fallback)."""
    if n_columns <= 1 or len(jax.devices()) < n_columns:
        return None
    from repro.launch.mesh import make_local_mesh

    return make_local_mesh(data=n_columns)


@functools.partial(
    jax.jit,
    static_argnames=("graph", "window", "hop", "batch_windows", "interpret",
                     "block_frames", "outputs"))
def _upload_loop(sig, operands, *, graph, window: int, hop: int,
                 batch_windows: int, interpret: bool,
                 block_frames: int | None, outputs: tuple):
    """A whole upload's dispatches as ONE compiled program: the signal is
    padded once with the raw zeros the per-batch tail chunk gets, then a
    `lax.scan` over the ``n_batches`` dispatch indices slices chunk k at
    ``k * batch_windows * hop`` and runs it through `graph_stream_call`
    — one stream-kernel launch per dispatch, the very call (and chunk)
    the per-batch path makes, so the outputs are bit-identical. Returns
    the frame-major output dict trimmed to the signal's frames (>= 1)."""
    n = frame_count(sig.shape[0], window, hop)
    n_batches = -(-n // batch_windows)
    stride = batch_windows * hop
    width = ring_chunk_samples(window, hop, batch_windows)
    total = (n_batches - 1) * stride + width
    sig = sig[:min(sig.shape[0], total)]
    if total > sig.shape[0]:
        sig = jnp.concatenate(
            [sig, jnp.zeros((total - sig.shape[0],), sig.dtype)])

    def dispatch(carry, k):
        chunk = lax.dynamic_slice(sig, (k * stride,), (width,))
        return carry, graph_stream_call(
            chunk, operands, graph=graph, window=window, hop=hop,
            interpret=interpret, block_frames=block_frames, outputs=outputs)

    _, outs = lax.scan(dispatch, None, jnp.arange(n_batches))
    return {k: v.reshape((n_batches * batch_windows,) + v.shape[2:])[:n]
            for k, v in outs.items()}


# the `_upload_loop` programs this process has run, by what jit keys them
# on: a `serve.launch` span's ``compiled`` stat is 1 for a key's first run
_UPLOAD_PROGRAMS: set = set()


class StagedTables:
    """Device copies of stage-graph operand tables, shared by the streams
    of one serving layer: the graph factories build host tables
    (`kernels/pipeline/graph.py:register_graph_factory`), and this keeps
    ONE copy per (graph, app, device) for as long as the serving layer
    lives, so admitting a stream copies nothing once its app's tables are
    on its device, and a stream pinned to a device reads tables there.

    Keyed by the app's identity (apps with ndarray fields aren't
    hashable); an entry holds its app, so the identity stays its own. At
    most ``CAPACITY`` entries are kept, the least recently used dropped
    first (streams opened without an app each build their own)."""
    CAPACITY = 16

    def __init__(self):
        self._entries: dict = {}      # key -> (app, graph, operands)

    def get(self, graph_name: str, app, device=None) -> tuple:
        """(StageGraph, operand tables on ``device``) of ``app`` under the
        registered graph ``graph_name`` (``device`` None: the default
        placement)."""
        key = (graph_name, id(app), device)
        hit = self._entries.pop(key, None)
        if hit is None:
            graph, tables = get_graph_factory(graph_name)(app)
            hit = (app, graph, tuple(jax.device_put(list(tables), device)))
            if len(self._entries) >= self.CAPACITY:
                del self._entries[next(iter(self._entries))]
        self._entries[key] = hit            # most recently used last
        return hit[1], hit[2]


@dataclasses.dataclass(frozen=True)
class ColumnStats:
    """One column's measured-throughput snapshot (see `StreamTelemetry`)."""
    column: int
    streams: int        # live streams attached to the column
    windows: int        # total windows retired on the column
    rate: float         # EWMA of the column's retire throughput, windows/s
    load: float         # sum of the column's live streams' EWMA rates —
    #                     the demand signal ColumnScheduler balances on


class StreamTelemetry:
    """Per-stream and per-column throughput telemetry.

    Every batch retire (`BiosignalStream._collect`, the block-until-ready
    point) reports ``(stream_id, n_windows)``; the telemetry turns the
    inter-retire gap into an instantaneous windows/s sample and folds it
    into an EWMA (``alpha`` = weight of the newest sample) per stream and
    per column. The first retire of a stream/column only seeds the
    timestamp — a rate needs a gap — so a telemetry with no *gap* yet is
    COLD (`warm` is False) and schedulers fall back to counting streams.

    ``clock`` is injectable (defaults to `time.perf_counter`) so tests
    and benchmarks can replay measured timings deterministically.

    Retires arrive from both loops: the host-driven path reports one per
    batch (`BiosignalStream._collect`), the per-upload loop one per upload
    (`BiosignalStream._process_upload`, all its frames), so the totals
    match. ``add_retire_listener`` lets a consumer observe every retire
    as it lands — that is how
    `serve.engine.ColumnScheduler`'s retire-count rebalance trigger
    replaces a host-side poller.
    """

    def __init__(self, alpha: float = 0.3, clock=time.perf_counter):
        assert 0.0 < alpha <= 1.0, alpha
        self.alpha = alpha
        self._clock = clock
        self._stream_col: dict = {}       # stream_id -> column
        self._stream_rate: dict = {}      # stream_id -> EWMA windows/s
        self._stream_last: dict = {}      # stream_id -> last retire t
        self._stream_windows: dict = {}   # stream_id -> total windows
        self._col_rate: dict[int, float] = {}
        self._col_last: dict[int, float] = {}
        self._col_windows: dict[int, int] = {}
        self._listeners: list = []        # fns called (stream_id, n) per
        #                                   retire, AFTER the EWMA update

    def add_retire_listener(self, fn) -> None:
        """Register ``fn(stream_id, n_windows)`` to run on every recorded
        retire (after the EWMA fold, so the listener sees warm rates).
        The hook is how retire-count triggers subscribe —
        `ColumnScheduler(rebalance_every=...)` registers itself here."""
        self._listeners.append(fn)

    def attach(self, stream_id, column: int = 0) -> None:
        """Register a stream on a column (idempotent re-attach moves it —
        that is how a rebalance re-pin shows up here)."""
        self._stream_col[stream_id] = int(column)
        self._stream_rate.setdefault(stream_id, 0.0)
        self._stream_windows.setdefault(stream_id, 0)

    def detach(self, stream_id) -> None:
        for d in (self._stream_col, self._stream_rate, self._stream_last,
                  self._stream_windows):
            d.pop(stream_id, None)

    def column_of(self, stream_id) -> int:
        return self._stream_col[stream_id]

    @staticmethod
    def _ewma(old: float | None, inst: float, alpha: float) -> float:
        return inst if old is None or old == 0.0 else \
            alpha * inst + (1.0 - alpha) * old

    def record_retire(self, stream_id, n_windows: int) -> None:
        """Fold one retired batch (``n_windows`` valid frames) into the
        stream's and its column's EWMAs, then notify retire listeners.
        For the per-upload loop a "batch" is the whole upload."""
        if stream_id not in self._stream_col:
            self.attach(stream_id)
        t = self._clock()
        col = self._stream_col[stream_id]
        self._stream_windows[stream_id] += int(n_windows)
        self._col_windows[col] = self._col_windows.get(col, 0) + int(n_windows)
        last = self._stream_last.get(stream_id)
        if last is not None and t > last:
            inst = n_windows / (t - last)
            self._stream_rate[stream_id] = self._ewma(
                self._stream_rate.get(stream_id), inst, self.alpha)
        self._stream_last[stream_id] = t
        last_c = self._col_last.get(col)
        if last_c is not None and t > last_c:
            inst = n_windows / (t - last_c)
            self._col_rate[col] = self._ewma(
                self._col_rate.get(col), inst, self.alpha)
        self._col_last[col] = t
        for fn in self._listeners:
            fn(stream_id, int(n_windows))

    @property
    def warm(self) -> bool:
        """True once ANY stream has a measured rate (>= 2 retires)."""
        return any(r > 0.0 for r in self._stream_rate.values())

    def stream_rate(self, stream_id) -> float:
        return self._stream_rate.get(stream_id, 0.0)

    def column_rate(self, column: int) -> float:
        return self._col_rate.get(column, 0.0)

    def column_load(self, column: int) -> float:
        """Sum of the column's live streams' EWMA rates (demand)."""
        return sum(self._stream_rate.get(s, 0.0)
                   for s, c in self._stream_col.items() if c == column)

    def column_stats(self, n_columns: int | None = None) -> list[ColumnStats]:
        """Snapshot over columns 0..n-1 (default: every column seen)."""
        cols = range(n_columns) if n_columns is not None else sorted(
            set(self._col_windows) | set(self._stream_col.values()) or {0})
        return [ColumnStats(
            column=c,
            streams=sum(1 for v in self._stream_col.values() if v == c),
            windows=self._col_windows.get(c, 0),
            rate=self.column_rate(c),
            load=self.column_load(c)) for c in cols]


class BiosignalStream:
    """Drives a continuous signal through the fused pipeline kernel in
    window batches: `stream` yields them one by one (up to `cfg.depth` in
    flight); `process` on a raw-chunk, single-column stream runs all of
    an upload's batches in one on-device loop.

    >>> stream = BiosignalStream(make_app(), StreamConfig(hop=256))
    >>> out = stream.process(signal)          # dict over all frames

    ``device`` pins every dispatch of THIS stream to one device (column) —
    how the serving layer places independent streams on distinct columns —
    and is mutually exclusive with ``cfg.n_columns > 1`` (which spreads
    each dispatch of one stream across all columns).

    ``telemetry`` (a `StreamTelemetry`) makes the stream report every
    retire (a batch in `stream`, a whole upload in the per-upload loop)
    under ``stream_id`` on ``column`` — the measurements the
    load-aware scheduler places and rebalances on. `repin` moves the
    stream to another device mid-flight (a `ColumnScheduler.rebalance`
    move); in-flight batches finish on the old device, later dispatches
    go to the new one.

    Args: ``app`` — the `core.biosignal.BiosignalApp` whose taps/weights
    the kernel stages (default `make_app()`); ``cfg`` — the
    `StreamConfig` dispatch shape (see its invariants). Guarantees:
    `process` equals running the fused kernel on
    `frame_signal(signal, window, hop)` in one call — bit-identical
    across framing modes, column counts and batch sizes; the zero-frame
    degenerate path returns the same keys/dtypes as the hot path. The
    control-loop structure (what runs on host vs device) is diagrammed in
    `docs/ARCHITECTURE.md`, with the ``serve.*`` profiler spans this loop
    records (Tracing). `docs/BENCHMARKS.md` holds CPU interpret-mode
    gates, which time no chip; chip numbers are in `PERF.md`.
    """

    def __init__(self, app: BiosignalApp | None = None,
                 cfg: StreamConfig | None = None, *, device=None,
                 telemetry: StreamTelemetry | None = None,
                 stream_id=None, column: int = 0,
                 injector=None, retry=None,
                 tables: StagedTables | None = None):
        cfg = cfg or StreamConfig()
        self._tables = tables if tables is not None else StagedTables()
        if cfg.graph == "biosignal":
            self.app = app or make_app()
            self._graph = None          # biosignal keeps its sharded path
            # its tables are staged on the first upload (`_staged`), after
            # the signal's copy is under way; the rule needs the graph only
            rule = biosignal_graph(len(self.app.fir_taps),
                                   *self.app.svm_w.shape, self.app.fft_size)
            cfg = dataclasses.replace(
                cfg, outputs=canonical_outputs(cfg.outputs))
        else:
            self.app = app if app is not None else default_app(cfg.graph)
            self._graph, _ = self._tables.get(cfg.graph, self.app, device)
            rule = self._graph
            # the config default (the biosignal 4-tuple) means "all of
            # THIS graph's outputs" for a non-biosignal graph
            sel = None if cfg.outputs is OUTPUTS else cfg.outputs
            cfg = dataclasses.replace(
                cfg, outputs=canonical_graph_outputs(self._graph, sel))
            assert cfg.n_columns == 1 and cfg.column_weights is None, \
                "non-biosignal graphs are single-column (no sharded entry)"
        self.cfg = cfg
        frame_width(rule, cfg.window)           # the graph's own frame rule
        assert 0 < self.cfg.hop <= self.cfg.window
        assert self.cfg.batch_windows > 0
        assert self.cfg.framing in ("kernel", "host"), self.cfg.framing
        assert self.cfg.n_columns >= 1
        assert self.cfg.depth >= 1
        assert device is None or self.cfg.n_columns == 1, \
            "pin a stream to one column OR shard it across columns, not both"
        if self.cfg.column_weights is not None:
            assert len(self.cfg.column_weights) == self.cfg.n_columns, \
                (self.cfg.column_weights, self.cfg.n_columns)
            assert self.cfg.framing == "kernel", \
                "the load-aware deal is a raw-chunk (framing='kernel') path"
        self.device = device
        self.mesh = column_mesh(self.cfg.n_columns)
        self.telemetry = telemetry
        self.stream_id = stream_id if stream_id is not None else id(self)
        self.column = column
        # fault hooks: ``injector`` (a `serve.fault.FaultInjector`) is
        # consulted before every raw-chunk dispatch and may raise
        # TransientDispatchError (retried below) or ColumnDeadError
        # (propagates — the serving layer drains + requeues). ``retry``
        # is the `runtime.fault.Supervisor` whose capped-exponential
        # `call` wraps the dispatch; default: 3 retries, no sleep.
        self.injector = injector
        self._retry = retry
        if injector is not None and retry is None:
            from repro.runtime.fault import (Supervisor,
                                             TransientDispatchError)

            self._retry = Supervisor(max_retries=3,
                                     retry_on=(TransientDispatchError,))
        if telemetry is not None:
            telemetry.attach(self.stream_id, column)

    def repin(self, device, column: int | None = None) -> None:
        """Move the stream's future dispatches to another device (the
        rebalance hand-off). Only meaningful for pinned (n_columns == 1)
        streams, like ``device=`` itself. Pass ``column`` when repinning
        MANUALLY so the telemetry re-attributes later retires to the new
        column (`ColumnScheduler.rebalance` already re-attaches through
        its own move bookkeeping, so its moves can omit it)."""
        assert self.cfg.n_columns == 1, \
            "repin applies to column-pinned streams"
        self.device = device
        if column is not None:
            self.column = column
            if self.telemetry is not None:
                self.telemetry.attach(self.stream_id, column)

    @property
    def dispatch_windows(self) -> int:
        """Frames per dispatch across all columns."""
        return self.cfg.batch_windows * self.cfg.n_columns

    @property
    def chunk_samples(self) -> int:
        """Raw samples per kernel-framed dispatch: one batch's span."""
        return ring_chunk_samples(self.cfg.window, self.cfg.hop,
                                  self.dispatch_windows)

    def _place(self, x):
        return x if self.device is None else jax.device_put(x, self.device)

    def _dispatch_chunk(self, chunk):
        """Raw-chunk dispatch: the kernel does the framing in VMEM. With a
        fault ``injector`` attached, the injector fires first (simulated
        transient faults are retried through the supervisor's capped
        backoff; a column death propagates to the serving layer)."""
        cfg = self.cfg

        def dispatch():
            if self.injector is not None:
                self.injector.on_dispatch(self.column)
            if self._graph is not None:
                return graph_pipeline_stream(
                    cfg.graph, self.app, chunk,
                    window=cfg.window, hop=cfg.hop,
                    block_frames=cfg.block_rows, autotune=cfg.autotune,
                    outputs=cfg.outputs)
            return app_pipeline_stream(self.app, chunk,
                                       window=cfg.window, hop=cfg.hop,
                                       block_frames=cfg.block_rows,
                                       autotune=cfg.autotune,
                                       outputs=cfg.outputs,
                                       n_columns=cfg.n_columns,
                                       mesh=self.mesh,
                                       column_weights=cfg.column_weights)
        if self._retry is not None:
            return self._retry.call(dispatch)
        return dispatch()

    def _dispatch_frames(self, frames):
        """Pre-framed dispatch (fallback/reference path)."""
        if self._graph is not None:
            return graph_pipeline(self.cfg.graph, self.app, frames,
                                  block_rows=self.cfg.block_rows,
                                  autotune=self.cfg.autotune,
                                  outputs=self.cfg.outputs)
        return app_pipeline(self.app, frames,
                            block_rows=self.cfg.block_rows,
                            autotune=self.cfg.autotune,
                            outputs=self.cfg.outputs,
                            n_columns=self.cfg.n_columns, mesh=self.mesh)

    def _batches(self, signal) -> Iterator[tuple]:
        """(in-flight output dict, n valid frames, dispatch index k) per
        window batch. Spans: ``serve.upload`` (the signal's host-to-device
        copy), then per dispatch ``serve.prepare`` (slice, tail pad,
        device placement) and ``serve.launch`` (enqueueing the fused
        kernel, fault injector and retry included; ``valid`` real frames
        of ``slots``)."""
        cfg = self.cfg
        with span("upload", stream=self.stream_id):
            sig = jnp.asarray(signal)
        n = frame_count(sig.shape[0], cfg.window, cfg.hop)
        bw = self.dispatch_windows
        if cfg.framing == "host":
            frames = frame_signal(sig, cfg.window, cfg.hop)
            for k, start in enumerate(range(0, n, bw)):
                with span("prepare", dispatch=k):
                    batch = frames[start: start + bw]
                    valid = batch.shape[0]
                    if valid < bw:  # pad the tail batch to the fixed shape
                        batch = jnp.concatenate(
                            [batch, jnp.zeros((bw - valid, cfg.window),
                                              batch.dtype)], axis=0)
                    batch = self._place(batch)
                with span("launch", dispatch=k, valid=valid, slots=bw,
                          batches=1):
                    out = self._dispatch_frames(batch)
                yield out, valid, k
            return
        # raw-chunk feed: batch k's frames live in one contiguous slice of
        # the signal — no gather, and the tail batch (frames % (bw*D) != 0)
        # pads with at most chunk_samples raw zeros instead of bw-valid
        # whole zero frames; the sharded entry trims the pad columns
        width = self.chunk_samples
        for k, start in enumerate(range(0, n, bw)):
            valid = min(bw, n - start)
            with span("prepare", dispatch=k):
                s0 = start * cfg.hop
                chunk = sig[s0: s0 + width]
                if chunk.shape[0] < width:
                    chunk = jnp.concatenate(
                        [chunk, jnp.zeros((width - chunk.shape[0],),
                                          sig.dtype)])
                chunk = self._place(chunk)
            with span("launch", dispatch=k, valid=valid, slots=bw,
                      batches=1):
                out = self._dispatch_chunk(chunk)
            yield out, valid, k

    def stream(self, signal) -> Iterator[dict]:
        """Yields one output dict per window batch (trimmed to the real
        frames). Up to `cfg.depth` later batches are dispatched before
        batch k is yielded, so the consumer always overlaps with
        `depth` in-flight batches (depth=1 is the classic double buffer:
        consume k while k+1 runs)."""
        inflight: deque[tuple[dict, int, int]] = deque()
        for nxt in self._batches(signal):       # async: in flight now
            inflight.append(nxt)
            if len(inflight) > self.cfg.depth:
                yield self._collect(*inflight.popleft())
        while inflight:
            yield self._collect(*inflight.popleft())

    def _collect(self, out: dict, valid: int, k: int) -> dict:
        """Retire dispatch ``k`` (span ``serve.retire``): wait for its
        outputs (``serve.wait``), report the retire, trim the pad
        frames."""
        with span("retire", dispatch=k, valid=valid):
            with span("wait", dispatch=k):
                out = jax.block_until_ready(out)    # the batch retires HERE
            if self.telemetry is not None:
                self.telemetry.record_retire(self.stream_id, valid)
            return {key: v[:valid] for key, v in out.items()}

    def _empty(self, dtype) -> dict:
        """Zero-frame result: same keys/shapes/dtypes as the kernel path."""
        if self._graph is not None:
            return graph_empty_outputs(self._graph, self.cfg.window, dtype,
                                       self.cfg.outputs)
        w = self.app.svm_w.shape
        return empty_outputs(self.cfg.window, w[0], w[1], dtype,
                             self.cfg.outputs)

    @property
    def _staged(self) -> tuple:
        """(StageGraph, operand tables on the stream's device) the
        per-upload loop runs, from the serving layer's `StagedTables`
        (copied there by the first stream of this app and device)."""
        return self._tables.get(self.cfg.graph, self.app, self.device)

    def _loop_block_frames(self, dtype) -> int | None:
        """The frame-block a per-batch dispatch would use: the pinned
        ``block_rows``, or under ``autotune`` the tuner's winner for one
        dispatch's chunk (same cache key as the per-batch entry, measured
        on a zero chunk when not cached yet)."""
        cfg = self.cfg
        if not cfg.autotune or cfg.block_rows is not None or \
                cfg.batch_windows == 1:
            return cfg.block_rows
        from repro.core.autotune import tuned_stream_block_frames

        graph, operands = self._staged
        chunk = jnp.zeros((self.chunk_samples,), dtype)
        return tuned_stream_block_frames(
            f"{graph.name}_pipeline_stream", cfg.batch_windows, cfg.window,
            cfg.hop, cfg.outputs, str(dtype),
            lambda rb: graph_stream_pallas(
                chunk, operands, graph=graph, window=cfg.window, hop=cfg.hop,
                interpret=interpret_mode(), block_frames=rb,
                outputs=cfg.outputs))

    def _process_upload(self, signal) -> dict:
        """`process` on a raw-chunk, single-column stream: every dispatch
        of the upload in ONE on-device loop (`_upload_loop`). The fault
        injector fires once per dispatch, in order and each through the
        retry, before the program runs; the telemetry sees one retire of
        all ``n`` frames. Spans: ``serve.prepare`` (the signal's copy to
        the stream's device, ``samples`` long), one ``serve.launch``
        (``valid`` frames of ``slots``, over ``batches`` dispatches, of the
        registered ``graph``; ``compiled`` 1 on the process's first
        upload of this length and loop shape, the one that compiles its
        loop program), ``serve.retire`` around
        ``serve.wait``."""
        cfg = self.cfg
        with span("prepare", dispatch=0, samples=len(signal)):
            sig = jax.device_put(signal, self.device)
        assert sig.ndim == 1, sig.shape
        n = frame_count(sig.shape[0], cfg.window, cfg.hop)
        if n == 0:
            return self._empty(sig.dtype)
        batches = -(-n // cfg.batch_windows)
        graph, operands = self._staged
        with span("launch", dispatch=0, valid=n,
                  slots=batches * cfg.batch_windows, batches=batches,
                  graph=graph.name) as launch:
            if self.injector is not None:
                for _ in range(batches):
                    self._retry.call(self.injector.on_dispatch, self.column)
            static = dict(graph=graph, window=cfg.window, hop=cfg.hop,
                          batch_windows=cfg.batch_windows,
                          interpret=interpret_mode(),
                          block_frames=self._loop_block_frames(sig.dtype),
                          outputs=cfg.outputs)
            key = (sig.shape, sig.dtype, *static.values())
            launch.set_metadata(compiled=int(key not in _UPLOAD_PROGRAMS))
            _UPLOAD_PROGRAMS.add(key)
            out = _upload_loop(sig, operands, **static)
        with span("retire", dispatch=0, valid=n):
            with span("wait", dispatch=0):
                out = jax.block_until_ready(out)
            if self.telemetry is not None:
                self.telemetry.record_retire(self.stream_id, n)
        return out

    def process(self, signal) -> dict:
        """One-call convenience: all framed outputs, equal to running the
        app on `frame_signal(signal, window, hop)` at once. A raw-chunk,
        single-column stream runs the upload as one on-device loop
        (`_process_upload`); the others concatenate `stream`'s batches.
        Spans: ``serve.process`` around the call, and on the per-batch
        path ``serve.concat`` around assembling the result."""
        with span("process", stream=self.stream_id, samples=len(signal)):
            if self.cfg.framing == "kernel" and self.cfg.n_columns == 1:
                return self._process_upload(signal)
            chunks = list(self.stream(signal))
            if not chunks:
                return self._empty(jnp.asarray(signal).dtype)
            with span("concat", stream=self.stream_id):
                return {k: jnp.concatenate([c[k] for c in chunks], axis=0)
                        for k in chunks[0]}

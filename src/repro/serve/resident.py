"""Device-resident streaming loop: the steady state runs on-device.

The per-batch runtime (`serve.stream.BiosignalStream`) is host-driven:
every `batch_windows`-frame dispatch is a Python-loop round trip — slice a
chunk, dispatch a `pallas_call`, block for the retire, update telemetry.
ROADMAP named that host-dispatch gap the biggest remaining latency lever
(it is why depth-2 pipelining measures within noise: the gap being hidden
is host overhead, not device work). This module inverts the control flow,
the STRELA direction (streaming *elastic* execution: data flows, control
stays out of the way) and the faithful analogue of VWR2A keeping its
control processor off the hot loop:

* the raw signal stays DEVICE-RESIDENT and a `lax.scan` iterates ring
  sweeps inside ONE jitted computation (`_resident_loop`): each sweep
  slices `ring_depth` dispatch-sized chunks out of the donated signal
  buffer and runs them through the fused ring kernel
  (`kernels/pipeline/kernel.py:pipeline_ring_pallas` — one `pallas_call`
  whose (slot, block) grid reuses the in-kernel framing index_maps), so
  dispatch, frame-block advance, and retire all happen on-device;
* telemetry counters (windows retired, the per-column EWMA inputs) are
  accumulated in device arrays carried through the scan and DRAINED to
  `serve.stream.StreamTelemetry` at a low, configurable frequency
  (`ResidentConfig.drain_interval` sweeps per drain) — one small host
  transfer per drain instead of one blocking readback per batch;
* the signal and counter buffers are DONATED to the loop
  (`jax.jit(donate_argnums=...)`), so XLA reuses the ring memory for
  outputs across sweeps instead of allocating per batch.

Bit-equivalence: for every (n_frames, ring_depth) — dividing or not —
`ResidentStream.process` returns exactly what the host-driven
`BiosignalStream.process` returns, to the last bit, and the drained
counters match the host path's per-batch retire accounting exactly
(`tests/test_resident.py` property-tests both, including the zero-frame
and tail-pad cases). The host-driven path stays as the reference.

See `docs/ARCHITECTURE.md` (serving-runtime control loop) for the
host-driven vs device-resident dataflow side by side, and
`docs/BENCHMARKS.md` for the `run.py --check-resident` gate that pins
resident >= per-batch dispatch throughput.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels import interpret_mode
from repro.core.biosignal import BiosignalApp, make_app
from repro.kernels.pipeline.graph import (canonical_graph_outputs,
                                          frame_width,
                                          graph_empty_outputs,
                                          graph_ring_pallas,
                                          ring_chunk_samples)
from repro.kernels.pipeline.ops import (OUTPUTS, canonical_outputs,
                                        default_app, stream_frame_count)
from repro.serve.stream import StagedTables, StreamConfig, StreamTelemetry

DEFAULT_RING_DEPTH = 4


@dataclasses.dataclass(frozen=True)
class ResidentConfig:
    """Knobs of the device-resident loop (the per-stream window/hop/batch
    shape stays in `serve.stream.StreamConfig`).

    ``ring_depth`` — dispatch-sized chunks (ring slots) per on-device
    sweep; one sweep = one `pipeline_ring_pallas` call covering
    `ring_depth * batch_windows` frames. `None` picks
    `DEFAULT_RING_DEPTH`, or a measured winner when ``autotune`` is set
    (`core.autotune.tuned_ring_depth`; the cache key carries the
    (window, hop, batch_windows, outputs, drain_interval) shape).
    ``drain_interval`` — ring sweeps between telemetry counter drains:
    the retire counters accumulate on-device and reach
    `StreamTelemetry.record_retire` only every `drain_interval` sweeps
    (plus once at end-of-signal), so the host touches the device
    `drain_interval * ring_depth` batches less often than the per-batch
    path. ``autotune`` — measure ring-depth candidates instead of the
    static default.
    """
    ring_depth: int | None = None
    drain_interval: int = 1
    autotune: bool = False



@functools.partial(
    jax.jit, donate_argnums=(0, 1),
    static_argnames=("graph", "window", "hop", "batch_windows",
                     "ring_depth", "n_sweeps", "interpret", "block_frames",
                     "outputs"))
def _resident_loop(sig, counter, operands, n_frames, *, graph, window: int,
                   hop: int, batch_windows: int, ring_depth: int,
                   n_sweeps: int, interpret: bool,
                   block_frames: int | None, outputs: tuple):
    """ONE compiled computation for the whole steady state: `lax.scan`
    over ring sweeps of the donated signal buffer.

    Each sweep stacks its `ring_depth` chunk views (hop-aligned dynamic
    slices of the resident signal — no host gather, no duplicated bytes
    beyond the `window-hop` slot halos) and dispatches the fused ring
    kernel on them; the retired-window counter advances in the scan carry
    (tail-pad aware: pad frames past `n_frames` never count). Returns the
    per-frame output dict, the final counter, and the per-sweep counter
    snapshots the host drains at `drain_interval` granularity.

    ``graph`` is the STATIC `kernels.pipeline.graph.StageGraph` to run
    (the loop is graph-generic: biosignal and ASR resident streams share
    this one jit) and ``operands`` its staged table arrays. ``sig`` and
    ``counter`` are donated: the loop owns the ring memory.
    """
    span = ring_chunk_samples(window, hop, batch_windows)
    stride = batch_windows * hop
    sweep_frames = ring_depth * batch_windows

    def sweep(carry, s):
        base = s * (ring_depth * stride)
        ring = jnp.stack([
            lax.dynamic_slice(sig, (base + r * stride,), (span,))
            for r in range(ring_depth)])
        out = graph_ring_pallas(ring, operands, graph=graph, window=window,
                                hop=hop, interpret=interpret,
                                block_frames=block_frames,
                                outputs=outputs)
        # frames retired this sweep = valid frames newly covered (the tail
        # sweep's pad frames are excluded by the same min() the host
        # path's per-batch `valid` uses)
        done = jnp.minimum((s + 1) * sweep_frames, n_frames)
        retired = done - jnp.minimum(s * sweep_frames, n_frames)
        counter2 = carry + retired.astype(carry.dtype)
        return counter2, (out, counter2)

    counter, (outs, snaps) = lax.scan(sweep, counter, jnp.arange(n_sweeps))
    # (n_sweeps, ring_depth, bw, ...) -> flat frame-major rows
    flat = {k: v.reshape((n_sweeps * sweep_frames,) + v.shape[3:])
            for k, v in outs.items()}
    return flat, counter, snaps


class ResidentStream:
    """Drives a signal through the fused pipeline with the steady-state
    loop ON-DEVICE — the resident sibling of `serve.stream.BiosignalStream`
    (same `StreamConfig` shape contract, same output dict, bit-identical
    results; construct it directly or via
    `BiosignalStream.process_resident`).

    >>> rs = ResidentStream(make_app(), StreamConfig(hop=256),
    ...                     ResidentConfig(ring_depth=8))
    >>> out = rs.process(signal)       # == BiosignalStream.process(signal)

    Constraints: the resident loop is a raw-chunk path
    (`cfg.framing == "kernel"`) on ONE column (`cfg.n_columns == 1` —
    multi-column serving pins independent resident streams to distinct
    columns via `serve.engine.ColumnScheduler`, exactly like the
    per-batch path). ``telemetry``/``stream_id``/``column`` wire the
    drained counters into `StreamTelemetry.record_retire`: every drain
    reports the windows retired since the previous drain, so the
    scheduler's EWMA inputs are the drained deltas instead of per-batch
    host timestamps — `ColumnScheduler`'s retire-count rebalance trigger
    fires off these drains. ``last_drains`` keeps the most recent
    process() call's cumulative drained counts for introspection/tests.
    """

    def __init__(self, app: BiosignalApp | None = None,
                 cfg: StreamConfig | None = None,
                 rcfg: ResidentConfig | None = None, *, device=None,
                 telemetry: StreamTelemetry | None = None,
                 stream_id=None, column: int = 0,
                 injector=None, retry=None,
                 tables: StagedTables | None = None):
        cfg = cfg or StreamConfig()
        if cfg.graph == "biosignal":
            self.app = app or make_app()
        else:
            self.app = app if app is not None else default_app(cfg.graph)
        # the loop is graph-generic: resolve (graph, operands on the
        # stream's device) once here
        self._graph, self._operands = (
            tables if tables is not None else StagedTables()).get(
                cfg.graph, self.app, device)
        if cfg.graph == "biosignal":
            cfg = dataclasses.replace(
                cfg, outputs=canonical_outputs(cfg.outputs))
        else:
            sel = None if cfg.outputs is OUTPUTS else cfg.outputs
            cfg = dataclasses.replace(
                cfg, outputs=canonical_graph_outputs(self._graph, sel))
        self.cfg = cfg
        self.rcfg = rcfg or ResidentConfig()
        assert self.cfg.framing == "kernel", \
            "the resident loop is a raw-chunk (framing='kernel') path"
        assert self.cfg.n_columns == 1 and self.cfg.column_weights is None, \
            "resident streams are column-pinned; use ColumnScheduler for D"
        frame_width(self._graph, self.cfg.window)   # the graph's own rule
        assert 0 < self.cfg.hop <= self.cfg.window
        assert self.cfg.batch_windows > 0
        assert self.rcfg.ring_depth is None or self.rcfg.ring_depth >= 1
        assert self.rcfg.drain_interval >= 1
        self.device = device
        self.telemetry = telemetry
        self.stream_id = stream_id if stream_id is not None else id(self)
        self.column = column
        self.last_drains: list[int] = []
        # fault hooks, mirroring `serve.stream.BiosignalStream`: the
        # injector fires once per loop dispatch (`on_dispatch`, transient
        # faults retried via the supervisor's capped backoff) and once
        # per counter drain (`on_drain` — a ColumnDeadError there is the
        # "death mid-resident-sweep" chaos scenario: earlier drains
        # already fed the telemetry, the outputs are lost with the
        # column, and the serving layer requeues the whole share)
        self.injector = injector
        self._retry = retry
        if injector is not None and retry is None:
            from repro.runtime.fault import (Supervisor,
                                             TransientDispatchError)

            self._retry = Supervisor(max_retries=3,
                                     retry_on=(TransientDispatchError,))
        if telemetry is not None:
            telemetry.attach(self.stream_id, column)

    @property
    def chunk_samples(self) -> int:
        """Raw samples per ring slot (one dispatch's span — identical to
        `BiosignalStream.chunk_samples` for the same config)."""
        return ring_chunk_samples(self.cfg.window, self.cfg.hop,
                                  self.cfg.batch_windows)

    def _ring_depth(self, n_batches: int) -> int:
        if self.rcfg.ring_depth is not None:
            return self.rcfg.ring_depth
        if self.rcfg.autotune and n_batches > 1:
            from repro.core.autotune import tuned_ring_depth

            cfg = self.cfg
            # the biosignal graph keeps its historical cache name; other
            # graphs tune under their own key so winners never leak
            name = "resident_ring" if cfg.graph == "biosignal" \
                else f"{cfg.graph}_resident_ring"
            return tuned_ring_depth(
                name, cfg.window, cfg.hop, cfg.batch_windows,
                cfg.outputs, "float32", self.rcfg.drain_interval, n_batches,
                lambda rd: self._run(
                    jnp.zeros((self.chunk_samples +
                               (n_batches * cfg.batch_windows - 1) * cfg.hop,
                               ), jnp.float32), rd))
        return DEFAULT_RING_DEPTH

    def _run(self, sig, ring_depth: int):
        """Pad + dispatch the compiled resident loop; returns
        (outputs, final counter, per-sweep counter snapshots)."""
        cfg = self.cfg
        n = stream_frame_count(sig.shape[0], cfg.window, cfg.hop)
        stride = cfg.batch_windows * cfg.hop
        n_batches = -(-n // cfg.batch_windows)
        n_sweeps = -(-n_batches // ring_depth)
        total = (n_sweeps * ring_depth - 1) * stride + self.chunk_samples
        sig = sig[:min(sig.shape[0], total)]
        if total > sig.shape[0]:
            sig = jnp.concatenate(
                [sig, jnp.zeros((total - sig.shape[0],), sig.dtype)])
        counter = jnp.zeros((), jnp.int32)
        if self.device is not None:
            sig = jax.device_put(sig, self.device)
            counter = jax.device_put(counter, self.device)

        def dispatch():
            # the injector fires BEFORE the loop consumes its donated
            # buffers, so a retried transient attempt reuses them intact
            if self.injector is not None:
                self.injector.on_dispatch(self.column)
            with warnings.catch_warnings():
                # CPU (and interpret-mode) backends cannot honour buffer
                # donation; the donation is FOR the accelerator target,
                # and the fallback is correct — silence only that advisory
                warnings.filterwarnings(
                    "ignore",
                    message="Some donated buffers were not usable")
                return _resident_loop(
                    sig, counter, self._operands,
                    jnp.asarray(n, jnp.int32), graph=self._graph,
                    window=cfg.window, hop=cfg.hop,
                    batch_windows=cfg.batch_windows,
                    ring_depth=ring_depth, n_sweeps=n_sweeps,
                    interpret=interpret_mode(), block_frames=cfg.block_rows,
                    outputs=cfg.outputs)
        if self._retry is not None:
            return self._retry.call(dispatch)
        return dispatch()

    def _drain(self, snaps) -> None:
        """Retire the device counters into the telemetry: cumulative
        per-sweep snapshots -> one `record_retire` per drain point (every
        `drain_interval` sweeps, plus the final partial window). The
        drained DELTAS sum to exactly the host path's per-batch retire
        total — the accounting property `tests/test_resident.py` pins."""
        snaps = np.asarray(snaps)
        k = self.rcfg.drain_interval
        points = list(range(k - 1, snaps.shape[0], k))
        # the end-of-signal drain always happens, even when the loop ran
        # fewer sweeps than one drain interval
        if not points or points[-1] != snaps.shape[0] - 1:
            points.append(snaps.shape[0] - 1)
        self.last_drains = [int(snaps[p]) for p in points]
        prev = 0
        for cum in self.last_drains:
            # the injector's per-drain hook fires mid-drain: a
            # ColumnDeadError here leaves the EARLIER drains already
            # recorded (heartbeats kept arriving until the death) but
            # aborts before this one — the chaos tests' death
            # mid-resident-sweep scenario
            if self.injector is not None:
                self.injector.on_drain(self.column)
            if self.telemetry is not None:
                self.telemetry.record_retire(self.stream_id, cum - prev)
            prev = cum

    def process(self, signal) -> dict:
        """All framed outputs for `signal`, bit-identical to the
        host-driven `BiosignalStream.process` — but the whole steady state
        is ONE device dispatch (scan over ring sweeps) instead of one
        round trip per `batch_windows` frames."""
        cfg = self.cfg
        sig = jnp.asarray(signal)
        assert sig.ndim == 1, sig.shape
        n = stream_frame_count(sig.shape[0], cfg.window, cfg.hop)
        if n == 0:
            # same degenerate contract as the host path: no frames, no
            # retires, the kernel's canonical empty dict
            self.last_drains = []
            return graph_empty_outputs(self._graph, cfg.window, sig.dtype,
                                       cfg.outputs)
        n_batches = -(-n // cfg.batch_windows)
        outs, _, snaps = self._run(sig, self._ring_depth(n_batches))
        self._drain(snaps)
        return {k: v[:n] for k, v in outs.items()}

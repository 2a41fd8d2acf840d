"""Public API for the fused stage-graph pipeline kernels.

Two entry points share the in-VMEM stage chain:

* ``biosignal_pipeline`` — pre-framed (R, S) window batches (the PR-2
  path, now with an ``outputs`` selection);
* ``biosignal_pipeline_stream`` — the RAW 1-D signal: overlapping
  (window, hop) frames are built inside the kernel from a once-staged
  signal chunk, so HBM traffic is ~n_samples instead of n_frames*window
  and the host never gathers frames.

The ``graph_pipeline*`` trio is the GENERIC face of the same machinery:
any registered stage graph (`graph.py:register_graph_factory` —
``"biosignal"``, ``"asr"``, or one you author per
`docs/STAGE_GRAPHS.md`) resolved by name, same framed/stream/ring
entries, autotune keys carrying the graph name so winners never leak
across graphs.
"""
from __future__ import annotations

import functools

from repro.kernels import interpret_mode
from repro.kernels.pipeline.graph import (default_app, get_graph_factory,
                                          graph_pallas, graph_ring_pallas,
                                          graph_stream_pallas)
from repro.kernels.pipeline.kernel import (OUTPUTS, canonical_outputs,
                                           pipeline_pallas,
                                           pipeline_ring_pallas,
                                           pipeline_stream_pallas,
                                           ring_chunk_samples,
                                           stream_frame_count)
from repro.kernels.pipeline.shard import (column_shares, pipeline_sharded,
                                          pipeline_stream_sharded)

__all__ = ["OUTPUTS", "canonical_outputs", "biosignal_pipeline",
           "biosignal_pipeline_stream", "biosignal_pipeline_ring",
           "app_pipeline", "app_pipeline_stream", "app_pipeline_ring",
           "graph_pipeline", "graph_pipeline_stream", "graph_pipeline_ring",
           "ring_chunk_samples", "default_app"]



def biosignal_pipeline(signal, taps, w, b, *, fft_size: int = 512,
                       block_rows: int | None = None,
                       autotune: bool = False, outputs=None,
                       n_columns: int = 1, mesh=None):
    """Run the full MBioTracker pipeline on (R, S) windows in ONE fused
    Pallas call. Returns the staged app's output dict restricted to
    ``outputs`` (default: all four keys).

    ``block_rows`` pins the per-grid-step row-block; ``autotune=True``
    instead picks it from measured candidates (cached per shape) — the
    measured replacement for the static VWRSpec budget formula.
    ``n_columns > 1`` deals row-blocks across column replicas
    (`shard_map` over ``mesh``'s `data` axis when available, serial
    columns otherwise); the autotune cache key carries the column count
    so winners are per-(shape, D).
    """
    outputs = canonical_outputs(outputs)
    interpret = interpret_mode()
    run_cols = functools.partial(pipeline_sharded, n_columns=n_columns,
                                 mesh=mesh) if n_columns > 1 else \
        pipeline_pallas
    if autotune and block_rows is None:
        from repro.core.autotune import tuned_block_rows

        R, S = signal.shape
        extras = (S, fft_size, outputs, str(signal.dtype)) + (
            (n_columns,) if n_columns > 1 else ())
        block_rows = tuned_block_rows(
            "biosignal_pipeline", -(-R // n_columns), extras,
            lambda rb: run_cols(signal, taps, w, b, fft_size=fft_size,
                                interpret=interpret, block_rows=rb,
                                outputs=outputs))
    return run_cols(signal, taps, w, b, fft_size=fft_size,
                    interpret=interpret, block_rows=block_rows,
                    outputs=outputs)


def biosignal_pipeline_stream(signal, taps, w, b, *, window: int, hop: int,
                              fft_size: int = 512,
                              block_frames: int | None = None,
                              autotune: bool = False, outputs=None,
                              n_columns: int = 1, mesh=None,
                              column_weights=None):
    """Run the pipeline over a RAW 1-D signal with in-kernel (window, hop)
    framing — the single-residency streaming path. Output equals
    ``biosignal_pipeline`` on host-framed windows, to the last bit.

    ``block_frames`` pins the frames-per-grid-step; ``autotune=True``
    measures candidates, cached under the (window, hop, outputs, D) shape
    key. ``n_columns > 1`` deals hop-aligned signal chunks (+ window-hop
    halo) across column replicas via `shard_map` over ``mesh``'s `data`
    axis (serial columns when no mesh fits) — outputs stay equal to the
    single-device call. ``column_weights`` makes that deal load-aware
    (non-uniform `column_shares`, e.g. measured per-column rates from
    `serve.stream.StreamTelemetry`); the autotune key then carries the
    quantized share signature so winners don't leak across deal shapes.
    """
    outputs = canonical_outputs(outputs)
    interpret = interpret_mode()
    assert column_weights is None or len(column_weights) == n_columns, \
        (column_weights, n_columns)
    if n_columns == 1:
        # a single weight is the degenerate identity deal: normalize it
        # away so it neither reaches the kernel nor splits the autotune
        # key of the identical computation
        column_weights = None
    run_cols = functools.partial(pipeline_stream_sharded,
                                 n_columns=n_columns, mesh=mesh,
                                 weights=column_weights) \
        if n_columns > 1 else pipeline_stream_pallas
    if autotune and block_frames is None:
        from repro.core.autotune import tuned_stream_block_frames

        n = stream_frame_count(signal.shape[0], window, hop)
        if n > 1:
            shares = column_shares(n, n_columns, column_weights) \
                if column_weights is not None else None
            block_frames = tuned_stream_block_frames(
                "biosignal_pipeline_stream", n, window, hop, outputs,
                str(signal.dtype),
                lambda rb: run_cols(
                    signal, taps, w, b, window=window, hop=hop,
                    fft_size=fft_size, interpret=interpret, block_frames=rb,
                    outputs=outputs), n_columns=n_columns, shares=shares)
    return run_cols(signal, taps, w, b, window=window, hop=hop,
                    fft_size=fft_size, interpret=interpret,
                    block_frames=block_frames, outputs=outputs)


def biosignal_pipeline_ring(ring, taps, w, b, *, window: int, hop: int,
                            fft_size: int = 512,
                            block_frames: int | None = None,
                            outputs=None):
    """Run the pipeline over a `(ring_depth, span)` RING of raw chunks in
    one fused `pallas_call` — the kernel entry the device-resident loop
    (`serve/resident.py`) dispatches per sweep. Each ring slot frames
    in-kernel exactly like `biosignal_pipeline_stream` on that slot's
    chunk; slot r of the result is bit-identical to the single-chunk call
    on `ring[r]`. See `docs/ARCHITECTURE.md` (serving control loop)."""
    outputs = canonical_outputs(outputs)
    return pipeline_ring_pallas(ring, taps, w, b, window=window, hop=hop,
                                fft_size=fft_size, interpret=interpret_mode(),
                                block_frames=block_frames, outputs=outputs)


def graph_pipeline(name: str, app, frames, *,
                   block_rows: int | None = None, autotune: bool = False,
                   outputs=None):
    """Run a REGISTERED stage graph on pre-framed (R, S) windows in ONE
    fused Pallas call. ``name`` resolves via
    `graph.py:get_graph_factory`; ``app`` binds the graph's operand
    tables (``None`` uses the graph's registered default app). Returns
    the graph's output dict restricted to ``outputs``."""
    factory = get_graph_factory(name)
    graph, operands = factory(app if app is not None
                              else default_app(name))
    interpret = interpret_mode()
    if autotune and block_rows is None:
        from repro.core.autotune import tuned_block_rows

        R, S = frames.shape
        block_rows = tuned_block_rows(
            f"{graph.name}_pipeline", R,
            (S, graph.params, outputs, str(frames.dtype)),
            lambda rb: graph_pallas(frames, operands, graph=graph,
                                    interpret=interpret, block_rows=rb,
                                    outputs=outputs))
    return graph_pallas(frames, operands, graph=graph, interpret=interpret,
                        block_rows=block_rows, outputs=outputs)


def graph_pipeline_stream(name: str, app, signal, *, window: int, hop: int,
                          block_frames: int | None = None,
                          autotune: bool = False, outputs=None):
    """Run a registered stage graph over a RAW 1-D signal with in-kernel
    (window, hop) framing — `graph.py:graph_stream_pallas` under an
    autotuned frame-block. The cache key is
    ``f"{name}_pipeline_stream"``, so the biosignal graph keeps its
    historical ``"biosignal_pipeline_stream"`` winners and other graphs
    tune independently."""
    factory = get_graph_factory(name)
    graph, operands = factory(app if app is not None
                              else default_app(name))
    interpret = interpret_mode()
    if autotune and block_frames is None:
        from repro.core.autotune import tuned_stream_block_frames

        n = stream_frame_count(signal.shape[0], window, hop)
        if n > 1:
            block_frames = tuned_stream_block_frames(
                f"{graph.name}_pipeline_stream", n, window, hop, outputs,
                str(signal.dtype),
                lambda rb: graph_stream_pallas(
                    signal, operands, graph=graph, window=window, hop=hop,
                    interpret=interpret, block_frames=rb, outputs=outputs))
    return graph_stream_pallas(signal, operands, graph=graph, window=window,
                               hop=hop, interpret=interpret,
                               block_frames=block_frames, outputs=outputs)


def graph_pipeline_ring(name: str, app, ring, *, window: int, hop: int,
                        block_frames: int | None = None, outputs=None):
    """Run a registered stage graph over a `(ring_depth, span)` ring of
    raw chunks in one fused call — the graph-generic
    `biosignal_pipeline_ring`, dispatched by the device-resident loop
    (`serve/resident.py`) for any graph."""
    factory = get_graph_factory(name)
    graph, operands = factory(app if app is not None
                              else default_app(name))
    return graph_ring_pallas(ring, operands, graph=graph, window=window,
                             hop=hop, interpret=interpret_mode(),
                             block_frames=block_frames, outputs=outputs)


def app_pipeline(app, signal, *, block_rows: int | None = None,
                 autotune: bool = False, outputs=None, n_columns: int = 1,
                 mesh=None):
    """Fused execution of a `core.biosignal.BiosignalApp` instance on
    pre-framed windows."""
    return biosignal_pipeline(signal, app.fir_taps, app.svm_w, app.svm_b,
                              fft_size=app.fft_size, block_rows=block_rows,
                              autotune=autotune, outputs=outputs,
                              n_columns=n_columns, mesh=mesh)


def app_pipeline_stream(app, signal, *, window: int, hop: int,
                        block_frames: int | None = None,
                        autotune: bool = False, outputs=None,
                        n_columns: int = 1, mesh=None,
                        column_weights=None):
    """Fused raw-signal streaming execution of a `BiosignalApp`."""
    return biosignal_pipeline_stream(signal, app.fir_taps, app.svm_w,
                                     app.svm_b, window=window, hop=hop,
                                     fft_size=app.fft_size,
                                     block_frames=block_frames,
                                     autotune=autotune, outputs=outputs,
                                     n_columns=n_columns, mesh=mesh,
                                     column_weights=column_weights)


def app_pipeline_ring(app, ring, *, window: int, hop: int,
                      block_frames: int | None = None, outputs=None):
    """Fused ring-of-chunks execution of a `BiosignalApp` (one
    `pallas_call` per ring sweep — the device-resident loop's dispatch)."""
    return biosignal_pipeline_ring(ring, app.fir_taps, app.svm_w, app.svm_b,
                                   window=window, hop=hop,
                                   fft_size=app.fft_size,
                                   block_frames=block_frames,
                                   outputs=outputs)

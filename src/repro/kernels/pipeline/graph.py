"""Graph compiler: registered stages -> ONE fused `pallas_call` body.

This module is the machinery half of the stage-graph layer
(`stages.py` is the registry half; `docs/STAGE_GRAPHS.md` the authoring
guide). A `StageGraph` names a chain of registered stages, binds their
VMEM table operands, and declares the per-frame outputs; the compiler
assembles them into the SAME three fused entries the hardcoded
biosignal kernel used to own:

* `graph_pallas` — pre-framed (R, S) window batches;
* `graph_stream_pallas` — RAW 1-D signal, overlapping (window, hop)
  frames built in-kernel from a once-staged chunk (the §4.2
  single-residency overlap reuse);
* `graph_ring_pallas` — a (ring_depth, span) ring of raw chunks in one
  call, the dispatch of the device-resident loop (`serve/resident.py`).

Invariants (pinned by `tests/test_stage_graph.py` / `tests/test_asr.py`):

* **Bit-identity with the pre-refactor kernel.** The compiled body
  composes the same helpers in the same order as the frozen legacy
  bodies (`kernel.py:pipeline_kernel` /
  `kernel.py:pipeline_stream_kernel`): stage once -> FIR (`_fir_stage`)
  -> registered map stages -> one HBM write. For the biosignal graph
  the outputs are bitwise equal to the pre-refactor fused kernel across
  every (window, hop, outputs, ring_depth).
* **FIR-first / hop-alignment.** Every graph's first stage is a causal
  k-tap FIR (`stages.Stage` kind ``"fir"``). The stream/ring framing —
  the signal as rows of ``hop`` samples, a body row block plus the next
  block as its tail, FIR once over the laid-out chunk, the frame-local
  zero-history head patch of the first ``n_taps - 1`` columns — is keyed
  off that stage's tap count and is what makes raw hop-aligned chunk
  feeds bit-identical to host framing for ANY graph.
* **Mosaic-legal.** Every body compiles for the TPU v5e
  (`tests/test_tpu_compile.py`): row blocks are (8, 128)-tiled, slices
  start on whole rows, and the stages use rotates and selects where a
  gather, strided slice or scatter would not lower.
* **Generic elision.** A registered stage runs only when a *requested*
  output transitively depends on it (`stages_to_run`); unrequested
  outputs are never written to HBM (their out specs don't exist). This
  strictly generalizes the old ``outputs != ("filtered",)`` special
  case.

The biosignal graph is registered by `kernel.py` (name ``"biosignal"``),
the ASR front-end by `asr.py` (name ``"asr"``); `get_graph_factory`
resolves either by name for the serving layer
(`serve/stream.py:StreamConfig.graph`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.vwr import SUBLANES, VWRSpec, resolve_block_rows, round_up
from repro.kernels.pipeline.stages import (OperandMismatchError,
                                           StageGraphError,
                                           UnknownGraphError, get_stage,
                                           register_stage)

__all__ = ["OutputSpec", "StageGraph", "build_graph", "stages_to_run",
           "canonical_graph_outputs", "graph_empty_outputs",
           "register_graph_factory", "get_graph_factory", "default_app",
           "registered_graphs", "graph_pallas", "graph_stream_pallas",
           "graph_ring_pallas", "stream_frame_count",
           "min_stream_block_frames", "resolve_stream_block_frames",
           "ring_chunk_samples"]


# ---------------------------------------------------------------------------
# Framing arithmetic (single source; `kernel.py` re-exports these names)
# ---------------------------------------------------------------------------

def stream_frame_count(n_samples: int, window: int, hop: int) -> int:
    return 0 if n_samples < window else 1 + (n_samples - window) // hop


def min_stream_block_frames(window: int, hop: int) -> int:
    """Smallest legal frame-block: the tail chunk supplies the
    (window - hop) overlap spill, so the body chunk (block_frames * hop
    samples) must be at least that long."""
    return 1 if window <= hop else -(-(window - hop) // hop)


def resolve_stream_block_frames(n_frames: int, window: int, hop: int,
                                override: int | None = None) -> int:
    """Frames staged per grid step. Unlike the framed kernel the block
    need not divide (or even stay below) the frame count — the signal is
    zero-padded and the garbage tail frames are trimmed after the call.
    Never below `min_stream_block_frames`: the tail chunk holds only
    block_frames*hop samples, which must cover the window-hop spill. The
    default is one sublane tile of frames, however few the frames: the
    kernel's row blocks must tile (8, 128) on the TPU."""
    rb = override or SUBLANES
    return max(1, rb, min_stream_block_frames(window, hop))


def ring_chunk_samples(window: int, hop: int, batch_windows: int) -> int:
    """Samples per ring slot: one `batch_windows`-frame dispatch's span —
    the same arithmetic as `serve.stream.BiosignalStream.chunk_samples`."""
    return (batch_windows - 1) * hop + window


def _fir_stage(x, taps_ref, k: int):
    """Causal k-tap FIR on the staged block — unrolled shifted FMAs, the
    in-VMEM mirror of `core.fir.fir_direct`. The mandatory first stage of
    every graph; the stream framing's head patch reuses it per frame."""
    rb, S = x.shape
    xp = jnp.pad(x, ((0, 0), (k - 1, 0)))
    y = jnp.zeros_like(x)
    for i in range(k):                   # unrolled taps == circular shifts
        y = y + taps_ref[0, i] * xp[:, k - 1 - i: k - 1 - i + S]
    return y


@register_stage("fir", kind="fir", operands=("fir_taps",),
                produces=("filtered",))
def _fir_body(state, tables, params):
    """The mandatory first stage, shared by every graph (the biosignal
    lowpass and the ASR pre-emphasis are both instances). The compiled
    bodies never call this: the framing machinery inlines `_fir_stage`
    itself, because the stream/ring schedule (FIR once over the chunk,
    then the frame-local head patch) cannot be expressed as a per-frame
    map. Kept as the semantic reference of what it inlines."""
    return {"filtered": _fir_stage(state["raw"], tables["fir_taps"],
                                   int(params["n_taps"]))}


# ---------------------------------------------------------------------------
# Graph definition
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class OutputSpec:
    """Shape/dtype contract of one per-frame graph output.

    ``shape`` is the TRAILING shape per frame: a tuple of ints or
    symbolic keys — ``"window"`` (the runtime frame length) or the name
    of a graph param (e.g. ``"n_mels"``). The empty tuple means a scalar
    per frame (stored as an (R, 1) HBM column, squeezed on read — the
    generalization of the biosignal ``class`` output). ``dtype`` is
    ``"float32"`` | ``"int32"`` | ``"input"`` (the signal's own dtype —
    the big elidable ``filtered`` write uses it)."""
    shape: tuple
    dtype: str = "float32"

    def __post_init__(self):
        if self.dtype not in ("float32", "int32", "input"):
            raise StageGraphError(f"OutputSpec dtype {self.dtype!r}")

    def resolve(self, window: int, params: dict) -> tuple:
        out = []
        for d in self.shape:
            if isinstance(d, str):
                d = window if d == "window" else params[d]
            out.append(int(d))
        return tuple(out)

    def np_dtype(self, input_dtype):
        return {"float32": jnp.float32, "int32": jnp.int32,
                "input": input_dtype}[self.dtype]


@dataclasses.dataclass(frozen=True)
class StageGraph:
    """A fused application: registered stages + operand binding + outputs.

    Hashable (stages hash by their frozen fields) so the whole graph is
    a STATIC argument of the jitted entries below — one compiled kernel
    per (graph, shape) like the legacy per-app entries. ``params`` must
    carry ``n_taps`` (the FIR-first framing contract) and ``fft_size``
    (the rFFT segment length, also the minimum legal window). Build via
    `build_graph`, which validates stage/operand wiring with the typed
    `stages.py` errors."""
    name: str
    stages: tuple                    # Stage objects, dataflow order
    outputs: tuple                   # ((name, OutputSpec), ...)
    operands: tuple                  # staged table names, binding order
    params: tuple                    # ((key, value), ...) static scalars

    def param(self, key: str):
        return dict(self.params)[key]

    @property
    def n_taps(self) -> int:
        return int(self.param("n_taps"))

    @property
    def fft_size(self) -> int:
        return int(self.param("fft_size"))

    @property
    def output_names(self) -> tuple:
        return tuple(n for n, _ in self.outputs)

    @property
    def output_specs(self) -> dict:
        return dict(self.outputs)


def build_graph(name: str, stage_names, outputs, operands,
                params) -> StageGraph:
    """Resolve + validate a `StageGraph` (the only constructor the
    authoring guide blesses — see `docs/STAGE_GRAPHS.md`).

    Checks, each with a typed error from `stages.py`:
    unknown stage name (`UnknownStageError`); first stage not a FIR, a
    later FIR, an output no stage produces, duplicate state keys, or a
    missing required param (`StageGraphError`); a stage operand the
    graph doesn't bind, an operand no stage reads, or a stage requiring
    state nothing earlier produced (`OperandMismatchError`)."""
    stages = tuple(get_stage(s) if isinstance(s, str) else s
                   for s in stage_names)
    outputs = tuple((n, spec) for n, spec in outputs)
    operands = tuple(operands)
    params = tuple(params)
    if not stages:
        raise StageGraphError(f"graph {name!r}: needs at least one stage")
    if stages[0].kind != "fir":
        raise StageGraphError(
            f"graph {name!r}: first stage must be kind='fir' (the framing "
            f"machinery keys its head patch off it), got "
            f"{stages[0].name!r}")
    if any(s.kind == "fir" for s in stages[1:]):
        raise StageGraphError(
            f"graph {name!r}: only the first stage may be kind='fir'")
    pdict = dict(params)
    for need in ("n_taps", "fft_size"):
        if need not in pdict:
            raise StageGraphError(f"graph {name!r}: missing param {need!r}")
    bound = set(operands)
    read: set = set()
    produced: set = set()
    for s in stages:
        missing = [o for o in s.operands if o not in bound]
        if missing:
            raise OperandMismatchError(
                f"graph {name!r}: stage {s.name!r} reads operands "
                f"{missing} the graph does not bind (bound: "
                f"{list(operands)})")
        read |= set(s.operands)
        unmet = [r for r in s.requires if r not in produced]
        if unmet:
            raise OperandMismatchError(
                f"graph {name!r}: stage {s.name!r} requires state {unmet} "
                f"no earlier stage produces")
        dup = [p for p in s.produces if p in produced]
        if dup:
            raise StageGraphError(
                f"graph {name!r}: stage {s.name!r} re-produces {dup}")
        produced |= set(s.produces)
    unread = [o for o in operands if o not in read]
    if unread:
        raise OperandMismatchError(
            f"graph {name!r}: bound operands {unread} are read by no stage")
    for n, _spec in outputs:
        if n not in produced:
            raise StageGraphError(
                f"graph {name!r}: output {n!r} is produced by no stage")
    return StageGraph(name=name, stages=stages, outputs=outputs,
                      operands=operands, params=params)


def stages_to_run(graph: StageGraph, outputs: tuple) -> tuple:
    """The MAP stages a compiled body must execute for this output
    selection: a reverse dataflow walk — a stage runs iff a requested
    output transitively depends on its products. (The FIR stage is the
    framing machinery itself and always runs.) This is the generic form
    of the legacy kernel's ``outputs != ("filtered",)`` elision."""
    needed = set(outputs)
    run = []
    for s in reversed(graph.stages[1:]):
        if needed & set(s.produces):
            run.append(s)
            needed |= set(s.requires)
    return tuple(reversed(run))


def canonical_graph_outputs(graph: StageGraph, outputs) -> tuple:
    """Validate + canonically order an output selection against the
    graph's declared outputs (`None` = all of them) — the per-graph
    generalization of `kernel.py:canonical_outputs`."""
    names = graph.output_names
    if outputs is None:
        return names
    sel = tuple(outputs)
    bad = [o for o in sel if o not in names]
    if bad:
        raise StageGraphError(
            f"graph {graph.name!r}: unknown outputs {bad}; choose from "
            f"{names}")
    if not sel:
        raise StageGraphError("outputs selection must not be empty")
    return tuple(o for o in names if o in sel)


def graph_empty_outputs(graph: StageGraph, window: int, dtype,
                        outputs=None) -> dict:
    """The zero-frame result for a graph, with the SAME keys/shapes/
    dtypes as a non-empty call — the degenerate-path single source
    (generalizes `kernel.py:empty_outputs`)."""
    outputs = canonical_graph_outputs(graph, outputs)
    params = dict(graph.params)
    specs = graph.output_specs
    return {o: jnp.zeros((0,) + specs[o].resolve(window, params),
                         specs[o].np_dtype(dtype)) for o in outputs}


# ---------------------------------------------------------------------------
# Graph factory registry (name -> factory building (graph, operands))
# ---------------------------------------------------------------------------

# name -> (factory(app) -> (StageGraph, operand arrays), default_app())
_GRAPHS: dict[str, tuple[Callable, Callable | None]] = {}


def register_graph_factory(name: str, factory: Callable, *,
                           default_app: Callable | None = None) -> None:
    """Register a named graph: ``factory(app) -> (graph, operands)``
    binds an application's weights/tables to the graph's operand list;
    ``default_app()`` (optional) builds the app the serving layer uses
    when a `StreamOpen`/`AsrTranscribe` carries none."""
    if name in _GRAPHS:
        raise StageGraphError(f"graph {name!r} is already registered")
    _GRAPHS[name] = (factory, default_app)


def get_graph_factory(name: str) -> Callable:
    """Resolve a graph name to its factory — the serving layer's graph
    handle (`serve/stream.py:StreamConfig.graph`). Lazily imports the
    in-repo graph modules so registration order never matters; raises
    the typed `UnknownGraphError` on a miss."""
    if name not in _GRAPHS:
        import repro.kernels.pipeline.asr     # noqa: F401 (registers "asr")
        import repro.kernels.pipeline.kernel  # noqa: F401 ("biosignal")
    try:
        return _GRAPHS[name][0]
    except KeyError:
        raise UnknownGraphError(
            f"unknown graph {name!r}; registered: "
            f"{sorted(_GRAPHS)}") from None


def default_app(name: str):
    """The registered default application instance for a graph name."""
    get_graph_factory(name)                  # force registration + typo check
    builder = _GRAPHS[name][1]
    if builder is None:
        raise StageGraphError(f"graph {name!r} registered no default app")
    return builder()


def registered_graphs() -> tuple:
    return tuple(sorted(_GRAPHS))


# ---------------------------------------------------------------------------
# Compiled bodies
# ---------------------------------------------------------------------------

def _write_graph_outputs(graph: StageGraph, refs: dict, state: dict) -> None:
    """The ONE HBM write per grid step — only requested refs exist.
    Scalar-per-frame outputs (shape ()) are stored as an (rb, 1) column;
    values are cast to the ref dtype only when they differ (a no-op for
    the all-f32 path, the `filtered` input-dtype cast otherwise)."""
    specs = graph.output_specs
    for o, ref in refs.items():
        v = state[o]
        if specs[o].shape == ():
            v = v[:, None]
        ref[...] = v if v.dtype == ref.dtype else v.astype(ref.dtype)


def _run_graph(graph: StageGraph, filt, tables: dict, outputs: tuple):
    """Execute the elided map-stage chain on a VMEM-resident FIR output
    block; returns the full state dict (the inter-stage tensors never
    leave the block — the paper's single-residency chaining)."""
    params = dict(graph.params)
    state = {graph.stages[0].produces[0]: filt}
    for stage in stages_to_run(graph, outputs):
        state.update(stage.body(state, tables, params))
    return state


def _graph_body(graph: StageGraph, x, tables: dict, out_refs: dict,
                outputs: tuple) -> None:
    """One staged (rb, window) frame block -> FIR-first stage chain -> one
    HBM write. Shared by every grid: framed, stream and ring."""
    filt = _fir_stage(x, tables[graph.stages[0].operands[0]], graph.n_taps)
    _write_graph_outputs(graph, out_refs,
                         _run_graph(graph, filt, tables, outputs))


def graph_kernel(*refs, graph: StageGraph, outputs: tuple):
    """Pre-framed graph body: one (rb, S) block staged once, the FIR-first
    stage chain, one HBM write (the generic `kernel.py:pipeline_kernel`)."""
    n_ops = len(graph.operands)
    tables = dict(zip(graph.operands, refs[1: 1 + n_ops]))
    out_refs = dict(zip(outputs, refs[1 + n_ops:]))
    _graph_body(graph, refs[0][...].astype(jnp.float32), tables, out_refs,
                outputs)


def graph_stream_kernel(*refs, graph: StageGraph, window: int, hop: int,
                        block_frames: int, outputs: tuple, n_tails: int):
    """Raw-signal graph body with IN-KERNEL framing. The signal arrives as
    rows of ``hop`` samples: the body block holds this step's
    `block_frames` rows, and (when window > hop) the tail block is the
    next step's rows, which supply the window - hop overlap spill. The
    rows are laid end to end into one (1, span) chunk (whole rows only,
    so no slice starts off the (8, 128) tile), the graph's FIR runs once
    over the chunk, frames are cut by static hop slices, and the first
    ``n_taps - 1`` columns are patched with frame-local zero history, so
    the result is bit-identical to running the graph on host-framed
    windows. Shared verbatim by the (slot, block) ring grid."""
    n_taps, rb = graph.n_taps, block_frames
    rows = refs[0][...]
    if n_tails:
        rows = jnp.concatenate([rows, refs[1][...]], axis=0)
    i = 1 + n_tails
    tables = dict(zip(graph.operands, refs[i: i + len(graph.operands)]))
    out_refs = dict(zip(outputs, refs[i + len(graph.operands):]))
    taps_ref = tables[graph.stages[0].operands[0]]
    span = (rb - 1) * hop + window
    chunk = jnp.concatenate(
        [rows[r: r + 1] for r in range(-(-span // hop))], axis=1
    )[:, :span].astype(jnp.float32)
    # FIR once over the chunk (overlap shared in VMEM)
    filt_chunk = _fir_stage(chunk, taps_ref, n_taps)
    filt = jnp.concatenate([filt_chunk[:, r * hop: r * hop + window]
                            for r in range(rb)], axis=0)
    # frame-local FIR transient: the framed reference zero-pads each
    # frame's history, the chunk FIR used real preceding samples — patch
    # the first n_taps-1 columns (the only ones that can differ)
    head = jnp.concatenate([chunk[:, r * hop: r * hop + n_taps - 1]
                            for r in range(rb)], axis=0)
    filt = jnp.concatenate([_fir_stage(head, taps_ref, n_taps),
                            filt[:, n_taps - 1:]], axis=1)
    _write_graph_outputs(graph, out_refs,
                         _run_graph(graph, filt, tables, outputs))


# ---------------------------------------------------------------------------
# Entries (unjitted cores + jitted wrappers)
# ---------------------------------------------------------------------------

def _operand_specs(operands) -> list:
    """Broadcast VMEM BlockSpecs for the staged tables: the same index_map
    takes ANY grid rank, so one operand list serves the 1-D framed/stream
    grids and the 2-D ring grid."""
    return [pl.BlockSpec(tuple(op.shape), lambda *_: (0, 0),
                         memory_space=pltpu.VMEM) for op in operands]


def _graph_out_shapes_specs(graph: StageGraph, R: int, rb: int, window: int,
                            dtype, outputs: tuple, index_map=None):
    """Output ShapeDtypeStructs + BlockSpecs for an R-row result written
    in rb-row blocks, resolved from the graph's `OutputSpec`s (the
    generic `kernel.py:_out_shapes_specs`)."""
    params = dict(graph.params)
    specs = graph.output_specs
    imap = index_map if index_map is not None else lambda i: (i, 0)
    out_shape, out_specs = [], []
    for o in outputs:
        trail = specs[o].resolve(window, params) or (1,)
        dt = specs[o].np_dtype(dtype)
        out_shape.append(jax.ShapeDtypeStruct((R,) + trail, dt))
        out_specs.append(pl.BlockSpec((rb,) + trail, imap,
                                      memory_space=pltpu.VMEM))
    return tuple(out_shape), tuple(out_specs)


def _graph_as_output_dict(graph: StageGraph, outs: tuple, outputs: tuple,
                          n: int) -> dict:
    specs = graph.output_specs
    return {o: v[:n, 0] if specs[o].shape == () else v[:n]
            for o, v in zip(outputs, outs)}


def graph_frames_call(frames, operands, *, graph: StageGraph,
                      interpret: bool = True,
                      block_rows: int | None = None, outputs=None):
    """Unjitted framed core (jit wrapper: `graph_pallas`; `kernel.py`'s
    legacy-signature `pipeline_pallas` routes here with the biosignal
    graph)."""
    outputs = canonical_graph_outputs(graph, outputs)
    R, S = frames.shape
    assert S >= graph.fft_size, (S, graph.fft_size)
    # raw + filtered + two FFT planes ~= 4 live VWR blocks
    rb = resolve_block_rows(R, S * 4, spec=VWRSpec(n_vwrs=4),
                            override=block_rows)
    out_shape, out_specs = _graph_out_shapes_specs(graph, R, rb, S,
                                                   frames.dtype, outputs)
    outs = pl.pallas_call(
        functools.partial(graph_kernel, graph=graph, outputs=outputs),
        out_shape=out_shape,
        in_specs=[pl.BlockSpec((rb, S), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)]
        + _operand_specs(operands),
        out_specs=out_specs,
        grid=(R // rb,),
        interpret=interpret,
    )(frames, *operands)
    return _graph_as_output_dict(graph, outs, outputs, R)


def _stream_layout(n: int, window: int, hop: int,
                   block_frames: int | None):
    """Grid arithmetic shared by the stream and ring entries: frames per
    step ``rb`` (a sublane multiple, so (rb, hop) row blocks tile), the
    step count, whether a tail block is needed (window > hop), and the
    hop-sized rows the padded signal must hold — one block more than the
    steps when a tail is read, since the last step's tail is the block
    after it."""
    rb = round_up(resolve_stream_block_frames(n, window, hop, block_frames),
                  SUBLANES)
    n_blocks = -(-n // rb)
    n_tails = int(window > hop)
    return rb, n_blocks, n_tails, (n_blocks + n_tails) * rb


def _row_specs(rb: int, hop: int, n_tails: int, ring: bool) -> list:
    """Body (+ tail) BlockSpecs over the (rows, hop) signal, or the
    (slot, rows, hop) ring: the tail is the SAME array one block ahead."""
    if ring:
        return [pl.BlockSpec((None, rb, hop), lambda r, j, t=t: (r, j + t, 0),
                             memory_space=pltpu.VMEM)
                for t in range(1 + n_tails)]
    return [pl.BlockSpec((rb, hop), lambda j, t=t: (j + t, 0),
                         memory_space=pltpu.VMEM)
            for t in range(1 + n_tails)]


def graph_stream_call(signal, operands, *, graph: StageGraph, window: int,
                      hop: int, interpret: bool = True,
                      block_frames: int | None = None, outputs=None):
    """Unjitted raw-signal streaming core (jit wrapper:
    `graph_stream_pallas`). Exactly ONE `pallas_call` per call; the signal
    is zero-padded (or cut) to whole blocks of hop-sized rows and the
    garbage tail frames are trimmed after the call."""
    outputs = canonical_graph_outputs(graph, outputs)
    (S,) = signal.shape
    assert window >= graph.fft_size, (window, graph.fft_size)
    assert 0 < hop <= window, (hop, window)
    n = stream_frame_count(S, window, hop)
    if n == 0:
        return graph_empty_outputs(graph, window, signal.dtype, outputs)
    rb, n_blocks, n_tails, rows = _stream_layout(n, window, hop, block_frames)
    total = rows * hop
    sig = signal[:min(S, total)]
    if total > sig.shape[0]:
        sig = jnp.concatenate(
            [sig, jnp.zeros((total - sig.shape[0],), sig.dtype)])
    out_shape, out_specs = _graph_out_shapes_specs(
        graph, n_blocks * rb, rb, window, signal.dtype, outputs)
    outs = pl.pallas_call(
        functools.partial(graph_stream_kernel, graph=graph, window=window,
                          hop=hop, block_frames=rb, outputs=outputs,
                          n_tails=n_tails),
        out_shape=out_shape,
        in_specs=_row_specs(rb, hop, n_tails, ring=False)
        + _operand_specs(operands),
        out_specs=out_specs,
        grid=(n_blocks,),
        interpret=interpret,
    )(*((sig.reshape(rows, hop),) * (1 + n_tails)), *operands)
    return _graph_as_output_dict(graph, outs, outputs, n)


def graph_ring_call(ring, operands, *, graph: StageGraph, window: int,
                    hop: int, interpret: bool = True,
                    block_frames: int | None = None, outputs=None):
    """Unjitted ring core (jit wrapper: `graph_ring_pallas`): a
    (ring_depth, span) ring of raw chunks through ONE `pallas_call` on a
    (slot, block) grid, the stream body/tail row blocks reused verbatim
    per slot. Slot r of the result is bit-identical to
    `graph_stream_call(ring[r], ...)` — the device-resident loop's
    dispatch contract."""
    outputs = canonical_graph_outputs(graph, outputs)
    D, span = ring.shape
    assert window >= graph.fft_size, (window, graph.fft_size)
    assert 0 < hop <= window, (hop, window)
    n = stream_frame_count(span, window, hop)      # frames per ring slot
    assert n > 0, f"ring span {span} shorter than one {window}-window"
    rb, n_blocks, n_tails, rows = _stream_layout(n, window, hop, block_frames)
    total = rows * hop
    # pad every slot row to the block tiling (the pad frames are trimmed
    # per slot)
    if total > span:
        ring = jnp.concatenate(
            [ring, jnp.zeros((D, total - span), ring.dtype)], axis=1)
    else:
        ring = ring[:, :total]
    out_shape, out_specs = _graph_out_shapes_specs(
        graph, D * n_blocks * rb, rb, window, ring.dtype, outputs,
        index_map=lambda r, j: (r * n_blocks + j, 0))
    outs = pl.pallas_call(
        functools.partial(graph_stream_kernel, graph=graph, window=window,
                          hop=hop, block_frames=rb, outputs=outputs,
                          n_tails=n_tails),
        out_shape=out_shape,
        in_specs=_row_specs(rb, hop, n_tails, ring=True)
        + _operand_specs(operands),
        out_specs=out_specs,
        grid=(D, n_blocks),
        interpret=interpret,
    )(*((ring.reshape(D, rows, hop),) * (1 + n_tails)), *operands)
    res = _graph_as_output_dict(graph, outs, outputs, D * n_blocks * rb)
    # per-slot trim: every slot framed n_blocks*rb rows, keep its n real
    # frames and restore the (ring_depth, n, ...) slot structure
    return {key: v.reshape((D, n_blocks * rb) + v.shape[1:])[:, :n]
            for key, v in res.items()}


graph_pallas = functools.partial(jax.jit, static_argnames=(
    "graph", "interpret", "block_rows", "outputs"))(graph_frames_call)
graph_stream_pallas = functools.partial(jax.jit, static_argnames=(
    "graph", "window", "hop", "interpret", "block_frames",
    "outputs"))(graph_stream_call)
graph_ring_pallas = functools.partial(jax.jit, static_argnames=(
    "graph", "window", "hop", "interpret", "block_frames",
    "outputs"))(graph_ring_call)

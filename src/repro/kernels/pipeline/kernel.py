"""Pallas TPU kernel: the FULL MBioTracker pipeline fused into one kernel.

The paper's headline number is *application-level* (§4.4.2 / Table 5):
chaining kernels while the data stays resident in the SPM/VWRs is where the
energy goes away — the FIR output is consumed by the delineation, whose
window is consumed by the feature extraction, whose features feed the SVM,
and main memory is touched exactly twice (signal in, features out). Our
staged `BiosignalApp` runs those stages as separate jnp/pallas calls, so
every stage round-trips HBM. This kernel transplants the paper's staging to
the whole application, extending what `kernels/fft/kernel.py` does for one
kernel:

    one grid step = one (rb x S) window block staged into VMEM, then
      1. 11-tap FIR          — k unrolled shifted FMAs (paper §4.4.1),
      2. delineation         — the mask-algebra predicates of
                               `core.biosignal.delineate` (the paper's
                               predicated RC code), on the VMEM-resident
                               filtered block,
      3. time features       — masked interval statistics,
      4. 512-pt packed rFFT  — the radix-2 stages of the FFT kernel with a
                               staged twiddle table + untangle epilogue,
                               reduced to 6 log-band powers,
      5. linear SVM          — margin + argmax class,
    and ONE HBM write of (filtered, features, margin, class).

Inter-stage tensors never leave the block: the working set is budgeted
against `VWRSpec(n_vwrs=4)` (raw + filtered + FFT planes + table/epilogue
scratch). Numerics follow `core.biosignal` op-for-op so the fused outputs
match the staged app to f32 tolerance. The interval median is an exact
counting selection — a bisection of compare + lane-sum steps (no `sort` /
`take_along_axis` / gather / branch anywhere in the kernel), and every
stage compiles for the TPU v5e (`tests/test_tpu_compile.py`).

`pipeline_stream_pallas` is the RAW-SIGNAL entry: the grid iterates
frame-blocks over a 1-D signal and the overlapping (window, hop) frames
are built in-kernel from a once-staged chunk — the streaming
single-residency analogue of the paper's §4.2 overlap reuse. Both entries
take an `outputs` selection that elides unrequested computation and HBM
writes.

**As of the stage-graph refactor** the two public entries
(`pipeline_pallas`, `pipeline_stream_pallas`) keep their exact
signatures but route through the generic graph compiler
(`graph.py:graph_frames_call`, `graph.py:graph_stream_call`): the
biosignal app is the first registered `StageGraph` (stages ``fir ->
delineate -> biosignal_features -> svm``, registered below), and the
compiled body is **bit-identical** to the frozen legacy bodies this
module retains (`pipeline_kernel`, `pipeline_stream_kernel`) because it
composes the same helpers in the same op order —
`tests/test_stage_graph.py` pins that equality across (window, hop,
outputs). The ASR front-end (`asr.py`) is the second graph over the same
machinery; see `docs/STAGE_GRAPHS.md` for authoring more.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.biosignal import (band_power_features, delineate,
                                  interval_time_features, make_app)
from repro.core.fft import untangle_rfft
from repro.core.shuffle import deinterleave
from repro.kernels.fft.kernel import radix2_stages, twiddle_table
from repro.kernels.pipeline.graph import (OutputSpec, _fir_stage,
                                          build_graph, graph_frames_call,
                                          graph_stream_call,
                                          register_graph_factory)
# the framing arithmetic lives in graph.py now; re-exported here because
# this module is its historical import location
from repro.kernels.pipeline.graph import min_stream_block_frames  # noqa: F401
from repro.kernels.pipeline.graph import resolve_stream_block_frames  # noqa: F401,E501
from repro.kernels.pipeline.graph import stream_frame_count  # noqa: F401
from repro.kernels.pipeline.stages import register_stage

# f32 matmuls in the kernel bodies: Mosaic's default contraction may drop
# to bf16 passes, the interpreter's never does
HIGHEST = jax.lax.Precision.HIGHEST


def untangle_table(fft_size: int) -> np.ndarray:
    """(2, m) packed untangle factors e^{-2*pi*i*k/N} for the real-FFT
    epilogue — staged into VMEM alongside the twiddles (the paper keeps
    both in the SPM)."""
    m = fft_size // 2
    ang = -2.0 * np.pi * np.arange(m) / fft_size
    return np.stack([np.cos(ang), np.sin(ang)]).astype(np.float32)


def _packed_rfft(seg, wr_ref, wi_ref, u_ref, *, fft_size: int):
    """Packed real FFT of a VMEM-resident (rb, fft_size) block: N real ->
    N/2+1 complex via radix-2 stages on the packed half-length signal +
    the untangle epilogue. The butterfly stages are the FFT kernel's
    (`radix2_stages`), reading the staged (stages, m) twiddle table and the
    (2, m) untangle table. Returns ``(Xr, Xi)``, each (rb, fft/2+1).
    Shared by the biosignal band-power stage (mean-subtracted input) and
    the ASR power-spectrum stage (raw windowed input) — the in-kernel
    mirror of `core.fft.rfft_packed`."""
    zr, zi = deinterleave(seg)                     # pack: z = even + i*odd
    Zr, Zi = radix2_stages(zr, zi, wr_ref, wi_ref,
                             int(np.log2(fft_size // 2)))
    return untangle_rfft(Zr, Zi, u_ref[0, :], u_ref[1, :])


def _rfft_band_powers(seg, wr_ref, wi_ref, u_ref, *, fft_size: int):
    """Mean-subtracted `_packed_rfft` power reduced to the 6 log-band
    powers of `core.biosignal.extract_features`."""
    seg = seg - jnp.mean(seg, axis=-1, keepdims=True)
    Xr, Xi = _packed_rfft(seg, wr_ref, wi_ref, u_ref, fft_size=fft_size)
    power = jnp.square(Xr) + jnp.square(Xi)        # (rb, fft/2+1)
    return band_power_features(power, fft_size)


OUTPUTS = ("filtered", "features", "margin", "class")


def canonical_outputs(outputs) -> tuple:
    """Validate + canonically order an output selection. `None` means all
    four app outputs; any subset elides the unrequested HBM writes (the
    (R, S) `filtered` write is by far the largest — dropping it is the
    point for classification-only traffic)."""
    if outputs is None:
        return OUTPUTS
    sel = tuple(outputs)
    bad = [o for o in sel if o not in OUTPUTS]
    assert not bad, f"unknown outputs {bad}; choose from {OUTPUTS}"
    assert sel, "outputs selection must not be empty"
    return tuple(o for o in OUTPUTS if o in sel)


def _stages_from_filtered(filt, wr_ref, wi_ref, u_ref, w_ref, b_ref, *,
                          fft_size: int):
    """Stages 2-4 on a VMEM-resident filtered block: delineation mask
    algebra -> masked interval time features + packed-rFFT band powers ->
    linear SVM margin/class. Shared by the framed and raw-stream kernels."""
    # --- stage 2: delineation (predicated mask algebra, never leaves VMEM)
    is_max, is_min = delineate(filt)
    # --- stage 3a: time features (masked interval statistics) ---
    f_time = interval_time_features(is_max, is_min)
    # --- stage 3b: frequency features (packed rFFT band powers) ---
    f_freq = _rfft_band_powers(filt[:, :fft_size], wr_ref, wi_ref, u_ref,
                               fft_size=fft_size)
    feats = jnp.stack(f_time + f_freq, axis=-1)    # (rb, 12)
    # --- stage 4: linear SVM margin + class ---
    margin = jnp.dot(feats, w_ref[...], precision=HIGHEST,
                     preferred_element_type=jnp.float32) + b_ref[0]
    cls = jnp.argmax(margin, axis=-1).astype(jnp.int32)
    return feats, margin, cls


def _write_outputs(refs: dict, filt, feats, margin, cls):
    """The ONE HBM write per grid step — only the requested refs exist."""
    if "filtered" in refs:
        refs["filtered"][...] = filt.astype(refs["filtered"].dtype)
    if "features" in refs:
        refs["features"][...] = feats
    if "margin" in refs:
        refs["margin"][...] = margin
    if "class" in refs:
        refs["class"][...] = cls[:, None]


def pipeline_kernel(x_ref, taps_ref, wr_ref, wi_ref, u_ref, w_ref, b_ref,
                    *out_refs, n_taps: int,
                    fft_size: int, outputs: tuple = OUTPUTS):
    refs = dict(zip(outputs, out_refs))
    x = x_ref[...].astype(jnp.float32)             # (rb, S) staged once
    # --- stage 1: preprocessing (11-tap FIR) ---
    filt = _fir_stage(x, taps_ref, n_taps)
    feats = margin = cls = None
    if outputs != ("filtered",):
        feats, margin, cls = _stages_from_filtered(
            filt, wr_ref, wi_ref, u_ref, w_ref, b_ref, fft_size=fft_size)
    _write_outputs(refs, filt, feats, margin, cls)


def fft_tables(fft_size: int) -> tuple:
    """The packed rFFT's host tables: (stages, m) twiddles, real and
    imaginary, and the (2, m) untangle factors, m = fft_size / 2."""
    m = fft_size // 2
    assert 1 << int(np.log2(m)) == m, f"fft_size={fft_size} not a power of 2"
    return (*twiddle_table(m), untangle_table(fft_size))


def _table_operands(taps, w, b, fft_size: int):
    """The staged constant tables every pipeline kernel reads: FIR taps,
    FFT twiddles, untangle factors and SVM weights/bias — with their
    (broadcast) VMEM BlockSpecs."""
    k = int(taps.shape[0])
    F, C = w.shape
    m = fft_size // 2
    stages = int(np.log2(m))
    wr, wi, u = fft_tables(fft_size)
    operands = (jnp.asarray(taps, jnp.float32).reshape(1, k),
                jnp.asarray(wr), jnp.asarray(wi), jnp.asarray(u),
                jnp.asarray(w, jnp.float32),
                jnp.asarray(b, jnp.float32).reshape(1, C))
    shapes = ((1, k), (stages, m), (stages, m), (2, m), (F, C),
              (1, C))
    # broadcast index_map: every block reads the whole table
    specs = [pl.BlockSpec(s, lambda *_: (0, 0), memory_space=pltpu.VMEM)
             for s in shapes]
    return operands, specs


def _out_shapes_specs(R: int, S: int, F: int, C: int, rb: int, dtype,
                      outputs: tuple):
    """Output ShapeDtypeStructs + BlockSpecs for an R-row result written in
    rb-row blocks (block i -> rows [i*rb, (i+1)*rb))."""
    table = {
        "filtered": (jax.ShapeDtypeStruct((R, S), dtype), (rb, S)),
        "features": (jax.ShapeDtypeStruct((R, F), jnp.float32), (rb, F)),
        "margin": (jax.ShapeDtypeStruct((R, C), jnp.float32), (rb, C)),
        "class": (jax.ShapeDtypeStruct((R, 1), jnp.int32), (rb, 1)),
    }
    out_shape = tuple(table[o][0] for o in outputs)
    out_specs = tuple(pl.BlockSpec(table[o][1], lambda i: (i, 0),
                                   memory_space=pltpu.VMEM) for o in outputs)
    return out_shape, out_specs


def _as_output_dict(outs: tuple, outputs: tuple, n: int) -> dict:
    res = {}
    for o, v in zip(outputs, outs):
        res[o] = v[:n, 0] if o == "class" else v[:n]
    return res


# ---------------------------------------------------------------------------
# The biosignal app as a registered stage graph
# ---------------------------------------------------------------------------

@register_stage("delineate", requires=("filtered",),
                produces=("is_max", "is_min"))
def _delineate_body(state, tables, params):
    """Delineation mask algebra (`core.biosignal.delineate`, the paper's
    predicated RC code) on the VMEM-resident filtered block."""
    is_max, is_min = delineate(state["filtered"])
    return {"is_max": is_max, "is_min": is_min}


@register_stage("biosignal_features",
                operands=("twiddle_re", "twiddle_im", "untangle"),
                requires=("filtered", "is_max", "is_min"),
                produces=("features",))
def _features_body(state, tables, params):
    """Masked interval time features (counting-selection median) +
    packed-rFFT band powers, stacked to (rb, 12)."""
    f_time = interval_time_features(state["is_max"], state["is_min"])
    f_freq = _rfft_band_powers(
        state["filtered"][:, :params["fft_size"]], tables["twiddle_re"],
        tables["twiddle_im"], tables["untangle"],
        fft_size=params["fft_size"])
    return {"features": jnp.stack(f_time + f_freq, axis=-1)}


@register_stage("svm", operands=("svm_w", "svm_b"), requires=("features",),
                produces=("margin", "class"))
def _svm_body(state, tables, params):
    """Linear SVM margin + argmax class — the matmul epilogue stage."""
    margin = jnp.dot(state["features"], tables["svm_w"][...],
                     precision=HIGHEST, preferred_element_type=jnp.float32
                     ) + tables["svm_b"][0]
    return {"margin": margin,
            "class": jnp.argmax(margin, axis=-1).astype(jnp.int32)}


@functools.lru_cache(maxsize=None)
def biosignal_graph(n_taps: int, n_features: int, n_classes: int,
                    fft_size: int):
    """The biosignal app as a `StageGraph` — the first registered graph.
    Cached per static signature so the graph object is identical across
    calls (it is a static jit argument of the generic entries)."""
    return build_graph(
        "biosignal",
        ("fir", "delineate", "biosignal_features", "svm"),
        (("filtered", OutputSpec(("window",), "input")),
         ("features", OutputSpec(("n_features",), "float32")),
         ("margin", OutputSpec(("n_classes",), "float32")),
         ("class", OutputSpec((), "int32"))),
        # binding order == the `_table_operands` tuple order
        ("fir_taps", "twiddle_re", "twiddle_im", "untangle",
         "svm_w", "svm_b"),
        (("n_taps", int(n_taps)), ("fft_size", int(fft_size)),
         ("n_features", int(n_features)), ("n_classes", int(n_classes))))


def _biosignal_graph_operands(taps, w, b, fft_size: int):
    """(graph, operand arrays) for the legacy (taps, w, b) signature."""
    operands, _ = _table_operands(taps, w, b, fft_size)
    F, C = w.shape
    return (biosignal_graph(int(taps.shape[0]), int(F), int(C),
                            int(fft_size)), operands)


def _biosignal_factory(app):
    """Graph factory (`graph.py:register_graph_factory`): a
    `core.biosignal.BiosignalApp`'s taps/weights as the graph's host
    tables, in `_table_operands` order (the serving layer copies them to
    a stream's device, `serve/stream.py:StagedTables`)."""
    w = np.asarray(app.svm_w, np.float32)
    F, C = w.shape
    taps = np.asarray(app.fir_taps, np.float32)
    return (biosignal_graph(taps.shape[0], F, C, app.fft_size),
            (taps.reshape(1, -1), *fft_tables(app.fft_size), w,
             np.asarray(app.svm_b, np.float32).reshape(1, C)))


register_graph_factory("biosignal", _biosignal_factory,
                       default_app=make_app)


@functools.partial(jax.jit,
                   static_argnames=("fft_size", "interpret", "block_rows",
                                    "outputs"))
def pipeline_pallas(signal, taps, w, b, *, fft_size: int = 512,
                    interpret: bool = True, block_rows: int | None = None,
                    outputs: tuple = OUTPUTS):
    """Fused MBioTracker pipeline. signal: (R, S) windows, S >= fft_size.

    Returns the staged `BiosignalApp.__call__` dict restricted to
    `outputs` (default all four): {"filtered": (R,S), "features": (R,F),
    "margin": (R,C), "class": (R,)}. Exactly ONE `pallas_call` runs per
    window batch; unrequested outputs are never written to HBM. Compiles
    the biosignal `StageGraph` via `graph.py:graph_frames_call` —
    bit-identical to the frozen legacy `pipeline_kernel` body.
    """
    outputs = canonical_outputs(outputs)
    graph, operands = _biosignal_graph_operands(taps, w, b, fft_size)
    return graph_frames_call(signal, operands, graph=graph,
                             interpret=interpret, block_rows=block_rows,
                             outputs=outputs)


# ---------------------------------------------------------------------------
# Raw-signal streaming kernel: in-kernel framing, single residency
# ---------------------------------------------------------------------------

# stream_frame_count / min_stream_block_frames / resolve_stream_block_frames
# moved to graph.py (re-exported above): they are graph-generic framing
# arithmetic, not biosignal specifics.

def empty_outputs(window: int, F: int, C: int, dtype, outputs=None) -> dict:
    """The zero-frame result, with the SAME keys/shapes/dtypes as a
    non-empty call — the single source of truth for every degenerate path
    (short signal, empty stream batch)."""
    outputs = canonical_outputs(outputs)
    empty = {"filtered": jnp.zeros((0, window), dtype),
             "features": jnp.zeros((0, F), jnp.float32),
             "margin": jnp.zeros((0, C), jnp.float32),
             "class": jnp.zeros((0,), jnp.int32)}
    return {o: empty[o] for o in outputs}


def pipeline_stream_kernel(*refs, n_taps: int, fft_size: int, window: int,
                           hop: int, block_frames: int, outputs: tuple,
                           n_tails: int):
    """One grid step = one block of `block_frames` overlapping frames,
    built IN-KERNEL from the raw 1-D signal (the VWR/SPM single-residency
    analogue of the paper's §4.2 overlap reuse):

      * the body chunk (1, block_frames*hop) is this block's stride of raw
        samples — its BlockSpec index_map is the hop arithmetic: block j
        starts at sample j*block_frames*hop;
      * `n_tails` hop-sized chunks of the SAME signal, at the hop-blocks
        right after the body, supply the (window - hop) samples the last
        frames spill past it — so the staged bytes are exactly one
        contiguous chunk per block (~n_samples total), vs window/hop
        duplicated copies for host-side framing;
      * the 11-tap FIR runs ONCE over the chunk, frames are cut from the
        filtered chunk by static hop slices, and only the first
        n_taps - 1 columns of each frame are recomputed with frame-local
        zero history, which makes the result bit-identical to filtering
        host-framed windows;
      * stages 2-5 and the HBM writes are shared with `pipeline_kernel`.
    """
    body_ref, tail_refs = refs[0], refs[1: 1 + n_tails]
    i = 1 + n_tails
    taps_ref, wr_ref, wi_ref, u_ref, w_ref, b_ref = refs[i: i + 6]
    refs_out = dict(zip(outputs, refs[i + 6:]))
    chunk = jnp.concatenate(
        [r[0, :] for r in (body_ref,) + tuple(tail_refs)]
    )[: block_frames * hop + (window - hop)].astype(jnp.float32)
    # --- stage 1: FIR once over the chunk (overlap shared in VMEM) ---
    filt_chunk = _fir_stage(chunk[None, :], taps_ref, n_taps)[0]
    filt = jnp.stack([filt_chunk[r * hop: r * hop + window]
                      for r in range(block_frames)])
    # frame-local FIR transient: the framed reference zero-pads each
    # frame's history, the chunk FIR used real preceding samples — patch
    # the first n_taps-1 columns (the only ones that can differ)
    head = jnp.stack([chunk[r * hop: r * hop + n_taps - 1]
                      for r in range(block_frames)])
    filt = jnp.concatenate([_fir_stage(head, taps_ref, n_taps),
                            filt[:, n_taps - 1:]], axis=1)
    feats = margin = cls = None
    if outputs != ("filtered",):
        feats, margin, cls = _stages_from_filtered(
            filt, wr_ref, wi_ref, u_ref, w_ref, b_ref, fft_size=fft_size)
    _write_outputs(refs_out, filt, feats, margin, cls)


@functools.partial(jax.jit,
                   static_argnames=("window", "hop", "fft_size", "interpret",
                                    "block_frames", "outputs"))
def pipeline_stream_pallas(signal, taps, w, b, *, window: int, hop: int,
                           fft_size: int = 512, interpret: bool = True,
                           block_frames: int | None = None,
                           outputs: tuple = OUTPUTS):
    """Fused pipeline over a RAW 1-D signal: overlapping (window, hop)
    frames are built inside the kernel, so HBM traffic is ~n_samples
    instead of n_frames*window (§4.2/§4.4.2 single residency). Returns the
    framed `pipeline_pallas` dict over the signal's n_frames frames,
    restricted to `outputs`. Exactly ONE `pallas_call` per call. Compiles
    the biosignal `StageGraph` via `graph.py:graph_stream_call` — the
    in-kernel framing schedule is documented on the frozen legacy body
    `pipeline_stream_kernel` and pinned bit-identical against it.
    """
    outputs = canonical_outputs(outputs)
    graph, operands = _biosignal_graph_operands(taps, w, b, fft_size)
    return graph_stream_call(signal, operands, graph=graph, window=window,
                             hop=hop, interpret=interpret,
                             block_frames=block_frames, outputs=outputs)

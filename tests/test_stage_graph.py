"""Stage-graph layer: the biosignal graph must be BIT-IDENTICAL to the
pre-refactor fused kernel, and the registry/compiler error paths must be
typed.

The refactor's contract (`kernels/pipeline/graph.py` module docstring) is
that routing the legacy entries through the graph compiler changes ZERO
bits: the compiled body composes the same helpers in the same op order as
the frozen legacy bodies `kernel.py:pipeline_kernel` /
`kernel.py:pipeline_stream_kernel`, which this module keeps alive by
rebuilding the pre-refactor `pallas_call` from them verbatim and
comparing with `np.testing.assert_array_equal` (not allclose) across
(window, hop, outputs). The second half pins the
`stages.py` error taxonomy and exercises the authoring path end to end
with a brand-new throwaway graph — the `docs/STAGE_GRAPHS.md` recipe.
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.biosignal import make_app, synthetic_respiration
from repro.core.vwr import VWRSpec, resolve_block_rows
from repro.kernels.pipeline import asr as _asr  # noqa: F401 (registers the
#                                                 hann/power/logmel stages)
from repro.kernels.pipeline import graph as G
from repro.kernels.pipeline import stages as St
from repro.kernels.pipeline.kernel import (OUTPUTS, _as_output_dict,
                                           _out_shapes_specs,
                                           _table_operands, biosignal_graph,
                                           canonical_outputs, empty_outputs,
                                           min_stream_block_frames,
                                           pipeline_kernel, pipeline_pallas,
                                           pipeline_stream_kernel,
                                           pipeline_stream_pallas,
                                           resolve_stream_block_frames,
                                           stream_frame_count)


# ---------------------------------------------------------------------------
# The pre-refactor kernels, reconstructed from the frozen legacy bodies
# ---------------------------------------------------------------------------

def _legacy_frames(frames, taps, w, b, *, fft_size=512, block_rows=None,
                   outputs=OUTPUTS):
    """The pre-refactor `pipeline_pallas`: the frozen `pipeline_kernel`
    body behind the exact pallas_call the entry used to build itself."""
    outputs = canonical_outputs(outputs)
    R, S = frames.shape
    rb = resolve_block_rows(R, S * 4, spec=VWRSpec(n_vwrs=4),
                            override=block_rows)
    operands, op_specs = _table_operands(taps, w, b, fft_size)
    F, C = w.shape
    out_shape, out_specs = _out_shapes_specs(R, S, F, C, rb, frames.dtype,
                                             outputs)
    outs = pl.pallas_call(
        functools.partial(pipeline_kernel, n_taps=int(taps.shape[0]),
                          fft_size=fft_size, outputs=outputs),
        out_shape=out_shape,
        in_specs=[pl.BlockSpec((rb, S), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)] + op_specs,
        out_specs=out_specs,
        grid=(R // rb,),
        interpret=True,
    )(jnp.asarray(frames), *operands)
    return _as_output_dict(outs, outputs, R)


def _legacy_stream(signal, taps, w, b, *, window, hop, fft_size=512,
                   block_frames=None, outputs=OUTPUTS):
    """The pre-refactor `pipeline_stream_pallas`: the frozen
    `pipeline_stream_kernel` body behind the identical framing/padding
    arithmetic the entry used to own (now `graph.py:graph_stream_call`)."""
    outputs = canonical_outputs(outputs)
    signal = jnp.asarray(signal)
    (S,) = signal.shape
    n = stream_frame_count(S, window, hop)
    F, C = w.shape
    if n == 0:
        return empty_outputs(window, F, C, signal.dtype, outputs)
    rb = resolve_stream_block_frames(n, window, hop, block_frames)
    n_blocks = -(-n // rb)
    L = rb * hop
    n_tails = min_stream_block_frames(window, hop) if window > hop else 0
    total = -(-(n_blocks * rb + n_tails) // rb) * L
    sig = signal[:min(S, total)]
    if total > sig.shape[0]:
        sig = jnp.concatenate(
            [sig, jnp.zeros((total - sig.shape[0],), sig.dtype)])
    sig2 = sig.reshape(1, total)
    in_specs = [pl.BlockSpec((1, L), lambda j: (0, j),
                             memory_space=pltpu.VMEM)]
    for i in range(n_tails):
        in_specs.append(pl.BlockSpec(
            (1, hop), lambda j, i=i: (0, j * rb + rb + i),
            memory_space=pltpu.VMEM))
    operands, op_specs = _table_operands(taps, w, b, fft_size)
    out_shape, out_specs = _out_shapes_specs(n_blocks * rb, window, F, C, rb,
                                             signal.dtype, outputs)
    outs = pl.pallas_call(
        functools.partial(pipeline_stream_kernel,
                          n_taps=int(taps.shape[0]), fft_size=fft_size,
                          window=window, hop=hop, block_frames=rb,
                          outputs=outputs, n_tails=n_tails),
        out_shape=out_shape,
        in_specs=in_specs + op_specs,
        out_specs=out_specs,
        grid=(n_blocks,),
        interpret=True,
    )(*((sig2,) * (1 + n_tails)), *operands)
    return _as_output_dict(outs, outputs, n)


def _assert_bitwise(out, ref):
    assert sorted(out) == sorted(ref), (sorted(out), sorted(ref))
    for k in ref:
        a, b = np.asarray(ref[k]), np.asarray(out[k])
        assert a.dtype == b.dtype, (k, a.dtype, b.dtype)
        np.testing.assert_array_equal(b, a, err_msg=k)


def _raw(n_samples, seed):
    sig, _ = synthetic_respiration(1, n_samples, seed=seed)
    return sig[0]


# ---------------------------------------------------------------------------
# Bit-identity: graph-compiled biosignal == pre-refactor kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window,hop,n_samples", [
    (512, 128, 5000),              # deep overlap
    (512, 512, 3000),              # hop == window (no tail specs)
    (1024, 320, 7001),             # hop does not divide window
    (2048, 512, 2048 * 4 + 777),   # paper-default shape, ragged tail
    (2048, 512, 2048),             # exactly one frame
])
def test_stream_bit_identical_to_legacy_kernel(window, hop, n_samples):
    app = make_app()
    raw = _raw(n_samples, seed=window + hop)
    out = pipeline_stream_pallas(raw, app.fir_taps, app.svm_w, app.svm_b,
                                 window=window, hop=hop)
    ref = _legacy_stream(raw, app.fir_taps, app.svm_w, app.svm_b,
                         window=window, hop=hop)
    _assert_bitwise(out, ref)


@pytest.mark.parametrize("outputs", [
    None, ("filtered",), ("features", "class"), ("margin",),
    ("class", "filtered"),
])
def test_stream_outputs_subsets_bit_identical(outputs):
    """Every elision subset takes the same elided path on both sides —
    including ("filtered",), where the legacy body skipped stages 2-5
    via its special case and the graph compiler via `stages_to_run`."""
    app = make_app()
    raw = _raw(4000, seed=11)
    sel = canonical_outputs(outputs)
    out = pipeline_stream_pallas(raw, app.fir_taps, app.svm_w, app.svm_b,
                                 window=512, hop=160, outputs=sel)
    ref = _legacy_stream(raw, app.fir_taps, app.svm_w, app.svm_b,
                         window=512, hop=160, outputs=sel)
    assert sorted(out) == sorted(sel)
    _assert_bitwise(out, ref)


@pytest.mark.parametrize("outputs", [None, ("features", "class")])
def test_framed_bit_identical_to_legacy_kernel(outputs):
    app = make_app()
    sig, _ = synthetic_respiration(8, 2048, seed=5)
    sel = canonical_outputs(outputs)
    out = pipeline_pallas(sig, app.fir_taps, app.svm_w, app.svm_b,
                          outputs=sel)
    ref = _legacy_frames(sig, app.fir_taps, app.svm_w, app.svm_b,
                         outputs=sel)
    _assert_bitwise(out, ref)


def test_zero_frame_path_matches_legacy_empty():
    app = make_app()
    out = pipeline_stream_pallas(jnp.zeros((100,), jnp.float32),
                                 app.fir_taps, app.svm_w, app.svm_b,
                                 window=2048, hop=512)
    F, C = app.svm_w.shape
    ref = empty_outputs(2048, F, C, jnp.float32)
    assert sorted(out) == sorted(ref)
    for k in ref:
        assert out[k].shape == ref[k].shape, k
        assert out[k].dtype == ref[k].dtype, k


# ---------------------------------------------------------------------------
# Authoring path end to end: a brand-new throwaway graph
# ---------------------------------------------------------------------------

@St.register_stage("_test_gain", operands=("gain",),
                   requires=("filtered",), produces=("gained",))
def _gain_body(state, tables, params):
    return {"gained": state["filtered"] * tables["gain"][0, 0]}


def test_new_graph_end_to_end():
    """The `docs/STAGE_GRAPHS.md` recipe on a minimal FIR+gain graph: a
    new registered stage, `build_graph`, and the generic stream entry —
    no edits to any shipped module."""
    g = G.build_graph(
        "_test_gain_graph", ("fir", "_test_gain"),
        (("gained", G.OutputSpec(("window",), "float32")),),
        ("fir_taps", "gain"),
        (("n_taps", 2), ("fft_size", 8)))
    assert g.n_taps == 2 and g.fft_size == 8
    assert g.output_names == ("gained",)
    taps = np.array([1.0, -0.5], np.float32)
    operands = (jnp.asarray(taps).reshape(1, 2),
                jnp.full((1, 1), 2.0, jnp.float32))
    window, hop, n = 16, 6, 100
    raw = np.random.default_rng(0).standard_normal(n).astype(np.float32)
    out = G.graph_stream_pallas(jnp.asarray(raw), operands, graph=g,
                                window=window, hop=hop)
    n_frames = stream_frame_count(n, window, hop)
    assert out["gained"].shape == (n_frames, window)
    # host oracle: frame-local zero-history FIR, then the gain
    idx = np.arange(n_frames)[:, None] * hop + np.arange(window)[None, :]
    frames = raw[idx]
    xp = np.pad(frames, ((0, 0), (1, 0)))
    ref = 2.0 * (taps[0] * xp[:, 1:] + taps[1] * xp[:, :-1])
    np.testing.assert_allclose(np.asarray(out["gained"]), ref, rtol=1e-6)


def test_stages_to_run_elision():
    g = biosignal_graph(11, 12, 2, 512)

    def names(sel):
        return tuple(s.name for s in G.stages_to_run(g, sel))

    assert names(("filtered",)) == ()
    assert names(("features",)) == ("delineate", "biosignal_features")
    assert names(("class",)) == ("delineate", "biosignal_features", "svm")
    assert names(OUTPUTS) == ("delineate", "biosignal_features", "svm")


def test_graph_empty_outputs_shapes():
    g = biosignal_graph(11, 12, 2, 512)
    out = G.graph_empty_outputs(g, 2048, jnp.float32)
    assert out["filtered"].shape == (0, 2048)
    assert out["features"].shape == (0, 12)
    assert out["class"].shape == (0,) and out["class"].dtype == jnp.int32
    sub = G.graph_empty_outputs(g, 2048, jnp.float32, ("margin",))
    assert sorted(sub) == ["margin"] and sub["margin"].shape == (0, 2)


# ---------------------------------------------------------------------------
# Typed error taxonomy (`stages.py`)
# ---------------------------------------------------------------------------

_PARAMS = (("n_taps", 11), ("fft_size", 512))


def test_unknown_stage_error():
    with pytest.raises(St.UnknownStageError, match="unknown stage"):
        G.build_graph("g", ("fir", "nope"), (), ("fir_taps",), _PARAMS)
    with pytest.raises(St.UnknownStageError):
        St.get_stage("definitely_not_registered")


def test_operand_mismatch_unbound():
    with pytest.raises(St.OperandMismatchError, match="does not bind"):
        G.build_graph("g", ("fir", "hann"), (), ("fir_taps",), _PARAMS)


def test_operand_mismatch_unread():
    with pytest.raises(St.OperandMismatchError, match="read by no stage"):
        G.build_graph("g", ("fir",),
                      (("filtered", G.OutputSpec(("window",), "input")),),
                      ("fir_taps", "unused_table"), _PARAMS)


def test_operand_mismatch_unmet_dataflow():
    # power_spectrum requires "windowed", which nothing before it produces
    with pytest.raises(St.OperandMismatchError, match="no earlier stage"):
        G.build_graph("g", ("fir", "power_spectrum"), (),
                      ("fir_taps", "twiddle_re", "twiddle_im", "untangle"),
                      _PARAMS)


def test_graph_structure_errors():
    ok_out = (("filtered", G.OutputSpec(("window",), "input")),)
    with pytest.raises(St.StageGraphError, match="at least one stage"):
        G.build_graph("g", (), ok_out, (), _PARAMS)
    with pytest.raises(St.StageGraphError, match="first stage"):
        G.build_graph("g", ("delineate",), ok_out, (), _PARAMS)
    with pytest.raises(St.StageGraphError, match="only the first"):
        G.build_graph("g", ("fir", "fir"), ok_out, ("fir_taps",), _PARAMS)
    with pytest.raises(St.StageGraphError, match="missing param"):
        G.build_graph("g", ("fir",), ok_out, ("fir_taps",),
                      (("n_taps", 11),))
    with pytest.raises(St.StageGraphError, match="produced by no stage"):
        G.build_graph("g", ("fir",),
                      (("nope", G.OutputSpec(("window",))),),
                      ("fir_taps",), _PARAMS)


def test_duplicate_produces_error():
    @St.register_stage("_test_dup_filtered", requires=("filtered",),
                       produces=("filtered",))
    def _dup(state, tables, params):
        return {"filtered": state["filtered"]}

    with pytest.raises(St.StageGraphError, match="re-produces"):
        G.build_graph("g", ("fir", "_test_dup_filtered"),
                      (("filtered", G.OutputSpec(("window",), "input")),),
                      ("fir_taps",), _PARAMS)


def test_duplicate_stage_registration_error():
    with pytest.raises(St.StageGraphError, match="already registered"):
        St.register_stage("fir")(lambda state, tables, params: {})


def test_stage_kind_validation():
    with pytest.raises(St.StageGraphError, match="kind"):
        St.Stage("x", "bogus", (), (), (), lambda *a: {})
    with pytest.raises(St.OperandMismatchError, match="exactly one"):
        St.Stage("x", "fir", ("a", "b"), (), (), lambda *a: {})


def test_output_spec_dtype_validation():
    with pytest.raises(St.StageGraphError):
        G.OutputSpec((), "float64")
    spec = G.OutputSpec(("window", "n_mels"))
    assert spec.resolve(512, {"n_mels": 64}) == (512, 64)
    assert G.OutputSpec((), "input").np_dtype(jnp.int32) == jnp.int32


def test_canonical_graph_outputs_validation():
    g = biosignal_graph(11, 12, 2, 512)
    assert G.canonical_graph_outputs(g, None) == OUTPUTS
    assert G.canonical_graph_outputs(g, ("class", "filtered")) == \
        ("filtered", "class")
    with pytest.raises(St.StageGraphError, match="unknown outputs"):
        G.canonical_graph_outputs(g, ("bogus",))
    with pytest.raises(St.StageGraphError, match="not be empty"):
        G.canonical_graph_outputs(g, ())


def test_graph_registry():
    regs = G.registered_graphs()
    # force both lazy registrations through the lookup path
    G.get_graph_factory("biosignal"), G.get_graph_factory("asr")
    assert {"biosignal", "asr"} <= set(G.registered_graphs())
    with pytest.raises(St.UnknownGraphError, match="unknown graph"):
        G.get_graph_factory("not_a_graph")
    with pytest.raises(St.StageGraphError, match="already registered"):
        G.register_graph_factory("biosignal", lambda app: None)
    G.register_graph_factory("_test_nodefault", lambda app: None)
    with pytest.raises(St.StageGraphError, match="no default app"):
        G.default_app("_test_nodefault")
    del regs


def test_errors_are_value_errors():
    """Legacy ``except ValueError`` call sites keep catching."""
    for cls in (St.StageGraphError, St.UnknownStageError,
                St.OperandMismatchError, St.UnknownGraphError):
        assert issubclass(cls, ValueError), cls


def test_registered_stage_inventory():
    names = St.registered_stages()
    for want in ("fir", "delineate", "biosignal_features", "svm", "hann",
                 "power_spectrum", "logmel"):
        assert want in names, want

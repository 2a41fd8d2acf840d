"""Kaldi's fbank front end (`kernels/pipeline/fbank.py`, graph ``"fbank"``)
against its plain float64 reference, on the CPU with the kernel in the
interpreter.

The comparison reads two errors: a mel energy's error as a share of its
frame's largest energy (a float32 FFT errs by ~1e-7 of the frame's energy
in every bin, so quiet frames and the near-empty low bins read like loud
ones) and, on the bins holding at least 1e-3 of their frame's largest
energy, the feature's error in nepers. Float32 reads ~2e-6 and ~4e-6;
rounding the mel product's inputs to bfloat16, as a one-pass bf16 matmul
would, reads ~4e-3 and ~6e-3, and must fail.
"""
import dataclasses
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.pipeline import fbank as F
from repro.kernels.pipeline.graph import (get_graph_factory,
                                          graph_stream_pallas)
from repro.kernels.pipeline.stages import StageGraphError
from repro.serve.engine import ColumnScheduler
from repro.serve.frontend import ServeFrontend, StreamOpen
from repro.serve.stream import BiosignalStream, StagedTables, StreamConfig

WINDOW, HOP = 400, 160
ENERGY_TOL = 1e-4
LOG_TOL = 2e-4
APP = F.KaldiFbankApp()


def _audio(n, seed=0):
    """Whole int16-scale numbers: a vowel-like tone pair, noise and a DC
    offset, so DC removal and every mel bin have work."""
    rng = np.random.default_rng(seed)
    t = np.arange(n) / 16000.0
    x = (3000 * np.sin(2 * np.pi * 180 * t) + 1500 * np.sin(
        2 * np.pi * 1250 * t + 1.0) + 400 * rng.standard_normal(n) + 250)
    return np.round(x).astype(np.float32)


def _errors(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    if want.size == 0:
        return 0.0, 0.0
    e_got, e_want = np.exp(got), np.exp(want)
    scale = e_want.max(axis=1, keepdims=True)
    loud = e_want >= 1e-3 * scale
    return (float(np.max(np.abs(e_got - e_want) / scale)),
            float(np.max(np.abs(got - want), where=loud, initial=0.0)))


def _cfg(**kw):
    kw = {"window": WINDOW, "hop": HOP, "batch_windows": 8,
          "graph": "fbank", "outputs": ("fbank",)} | kw
    return StreamConfig(**kw)


@pytest.mark.parametrize("n", [0, 399, 400, 401, 160 * 12 + 37, 4000])
def test_graph_matches_reference(n):
    graph, ops = get_graph_factory("fbank")(APP)
    sig = _audio(n, seed=n)
    got = graph_stream_pallas(jnp.asarray(sig), ops, graph=graph,
                              window=WINDOW, hop=HOP)["fbank"]
    want = F.fbank_reference(APP, sig, hop=HOP)
    assert want.shape == (0 if n < WINDOW else 1 + (n - WINDOW) // HOP, 80)
    energy, log = _errors(got, want)
    assert energy <= ENERGY_TOL and log <= LOG_TOL, (energy, log)


def test_upload_loop_is_bit_identical_to_per_batch_loop():
    """`process` (one on-device loop) and `stream` (a dispatch at a time)
    run the same chunks through the same kernel; host framing zero-pads
    each frame to the FFT outside the kernel and agrees to tolerance."""
    sig = _audio(160 * 19 + 400 + 77, seed=3)      # 20 frames, 3 dispatches
    stream = BiosignalStream(APP, _cfg())
    loop = np.asarray(stream.process(sig)["fbank"])
    batches = np.concatenate([np.asarray(b["fbank"])
                              for b in stream.stream(sig)])
    np.testing.assert_array_equal(loop, batches)
    host = BiosignalStream(APP, _cfg(framing="host")).process(sig)["fbank"]
    want = F.fbank_reference(APP, sig, hop=HOP)
    for got in (loop, host):
        energy, log = _errors(got, want)
        assert energy <= ENERGY_TOL and log <= LOG_TOL, (energy, log)


def test_served_through_stream_open():
    front = ServeFrontend(scheduler=ColumnScheduler(jax.devices()[:1]))
    ticket = front.submit(StreamOpen(
        stream_id="utt-1", app=APP,
        cfg=_cfg(batch_windows=64)))
    front.run()
    sig = _audio(16000, seed=4)
    got = ticket.result().process(sig)["fbank"]
    energy, log = _errors(got, F.fbank_reference(APP, sig, hop=HOP))
    assert energy <= ENERGY_TOL and log <= LOG_TOL, (energy, log)


def test_the_frame_rule_is_the_graphs():
    """A zero-padded frame is exactly ``frame_length`` samples; a graph
    that windows a frame's first fft_size samples needs window >=
    fft_size."""
    with pytest.raises(StageGraphError, match="frames 400 samples"):
        BiosignalStream(APP, _cfg(window=512))
    with pytest.raises(StageGraphError, match="window >= fft_size"):
        BiosignalStream(None, StreamConfig(window=400, hop=160))


@pytest.mark.parametrize("length,fft", [(400, 512), (512, 512), (200, 256),
                                        (513, 1024)])
def test_fft_size_is_the_frame_length_rounded_to_a_power_of_two(length, fft):
    """--round-to-power-of-two=true is the only FFT size Kaldi has: the
    app derives it and takes no other."""
    assert F.KaldiFbankApp(frame_length=length).fft_size == fft
    with pytest.raises(TypeError):
        F.KaldiFbankApp(fft_size=fft)


def test_streams_of_one_scheduler_share_one_device_copy_of_the_tables():
    """The factory builds host tables; the serving layer keeps one copy
    per (graph, app, device), on the stream's device, for every stream it
    places, and another serving layer keeps its own."""
    graph, host = get_graph_factory("fbank")(APP)
    assert all(isinstance(t, np.ndarray) for t in host)
    dev = jax.devices()[0]
    sched = ColumnScheduler([dev])
    (ga, oa), (gb, ob) = (sched.place_stream(APP, _cfg(), stream_id=s)._staged
                          for s in ("a", "b"))
    assert ga is gb is graph
    assert all(x is y for x, y in zip(oa, ob))
    for staged, table in zip(oa, host):
        assert staged.devices() == {dev}
        np.testing.assert_array_equal(np.asarray(staged), table)
    other = ColumnScheduler([dev]).place_stream(APP, _cfg(), stream_id="c")
    assert other._staged[1][0] is not oa[0]


def test_staged_tables_keep_the_most_recently_used(monkeypatch):
    monkeypatch.setattr(StagedTables, "CAPACITY", 2)
    tables = StagedTables()
    apps = [F.KaldiFbankApp(preemph=p) for p in (0.97, 0.95, 0.9)]
    first = tables.get("fbank", apps[0])[1]
    second = tables.get("fbank", apps[1])[1]
    assert tables.get("fbank", apps[0])[1] is first
    tables.get("fbank", apps[2])                # drops apps[1]'s
    assert tables.get("fbank", apps[0])[1] is first
    assert tables.get("fbank", apps[1])[1] is not second


def test_povey_window_by_hand():
    w = F.povey_window(400, 512)
    assert w.shape == (512,)
    assert w[0] == 0.0 and abs(w[399]) < 1e-15
    # i = 133 is a third of the way: 0.5 - 0.5 cos(2 pi / 3) = 0.75
    assert w[133] == pytest.approx(0.75 ** 0.85, rel=1e-12)
    assert w[266] == pytest.approx(0.75 ** 0.85, rel=1e-12)
    assert np.all(w[400:] == 0.0)
    assert np.all(w[:400] <= 1.0) and np.argmax(w) in (199, 200)


def test_kaldi_preemphasis_first_sample_rule():
    """DC removal over the frame's samples only, then x[i] -= 0.97 x[i-1]
    and x[0] -= 0.97 x[0]: the stage body on a 4-sample frame read 8
    lanes wide."""
    x = jnp.asarray([[1.0, 3.0, 2.0, 6.0, 100.0, -7.0, 5.0, 9.0]])
    got = np.asarray(jax.jit(lambda v: F._kaldi_preemph_body(
        {"filtered": v}, {}, {"frame_length": 4, "preemph": 0.97}
    )["preemph"])(x))[0, :4]
    d = np.array([1.0, 3.0, 2.0, 6.0]) - 3.0           # mean of 4 is 3
    want = [d[0] - 0.97 * d[0], d[1] - 0.97 * d[0], d[2] - 0.97 * d[1],
            d[3] - 0.97 * d[2]]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[0] == pytest.approx(0.03 * -2.0, rel=1e-6)


def test_htk_mel_banks_by_hand():
    w = F.htk_mel_banks(80, 512, 16000.0, 20.0, 8000.0)
    assert w.shape == (257, 80)
    assert np.all(w[256] == 0.0) and np.all(w[0] == 0.0)   # Nyquist, DC
    assert np.all((w >= 0.0) & (w <= 1.0))

    def mel(f):
        return 1127.0 * math.log(1.0 + f / 700.0)

    lo = mel(20.0)
    d = (mel(8000.0) - lo) / 81
    # bin 0 spans 20 Hz .. 65.7 Hz, its peak at 42.5 Hz: FFT bin 1
    # (31.25 Hz) on its rising edge, bin 2 (62.5 Hz) on its falling one
    assert w[1, 0] == pytest.approx((mel(31.25) - lo) / d, rel=1e-12)
    assert w[2, 0] == pytest.approx((lo + 2 * d - mel(62.5)) / d, rel=1e-12)
    assert np.count_nonzero(w[:, 0]) == 2
    # inside every triangle, and nowhere else
    m = np.array([mel(31.25 * i) for i in range(256)])
    for b in (0, 40, 79):
        left, right = lo + b * d, lo + (b + 2) * d
        assert np.all((w[:256, b] > 0) == ((m > left) & (m < right)))
    # neighbouring triangles cross: between the first and last peaks the
    # weights of every FFT bin sum to one (peak 1, no area normalisation)
    mid = (m >= lo + d) & (m <= lo + 80 * d)
    np.testing.assert_allclose(w[:256][mid].sum(axis=1), 1.0, atol=1e-12)


def test_a_bf16_mel_product_fails_the_comparison():
    """The mel product on the MXU stays at HIGHEST precision: with its
    inputs rounded to bfloat16, as one bf16 pass would, the comparison
    fails."""
    graph, ops = get_graph_factory("fbank")(APP)

    def bf16_inputs(state, tables, params):
        def bf16(v):
            return v.astype(jnp.bfloat16).astype(jnp.float32)
        return F._log_fbank_body({"power": bf16(state["power"])},
                                 {"mel_w": bf16(tables["mel_w"][...])},
                                 params)

    rounded = dataclasses.replace(graph, stages=tuple(
        dataclasses.replace(s, body=bf16_inputs) if s.name == "log_fbank"
        else s for s in graph.stages))
    sig = _audio(4000, seed=4000)
    want = F.fbank_reference(APP, sig, hop=HOP)
    got = graph_stream_pallas(jnp.asarray(sig), ops, graph=rounded,
                              window=WINDOW, hop=HOP)["fbank"]
    energy, log = _errors(got, want)
    assert energy > ENERGY_TOL and log > LOG_TOL, (energy, log)


def _launch_spans(tmp_path, fn):
    """The ``serve.prepare`` and ``serve.launch`` events' stats, with
    ``fn`` run under a profiler session."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (xp,) = Path(tmp_path).rglob("*.xplane.pb")
    events = [(e.start_ns, e.name, dict(e.stats))
              for plane in jax.profiler.ProfileData.from_file(str(xp)).planes
              if plane.name.startswith("/host")
              for line in plane.lines for e in line.events
              if e.name in ("serve.prepare", "serve.launch")]
    return [(name, stats) for _, name, stats in sorted(events)]


def test_launch_span_carries_graph_and_compiled(tmp_path):
    """On the per-upload loop ``serve.prepare`` carries the upload's
    samples, and ``serve.launch`` the registered graph and whether this
    upload's length compiled a new loop program: 1 for a length not seen
    yet, 0 for the same length again."""
    stream = BiosignalStream(APP, _cfg(), stream_id="utt-2")
    sig = _audio(160 * 9 + 400 + 13, seed=9)        # a length no other test
    #                                                 uses: 10 frames
    spans = _launch_spans(tmp_path, lambda: (stream.process(sig),
                                             stream.process(sig)))
    assert [name for name, _ in spans] == ["serve.prepare", "serve.launch"] * 2
    for (_, prep), (_, launch), compiled in zip(spans[::2], spans[1::2],
                                                (1, 0)):
        assert prep == {"dispatch": 0, "samples": sig.shape[0]}
        assert launch == {"dispatch": 0, "valid": 10, "slots": 16,
                          "batches": 2, "graph": "fbank",
                          "compiled": compiled}

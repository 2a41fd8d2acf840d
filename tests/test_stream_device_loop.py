"""The per-upload loop of `BiosignalStream.process` (`_upload_loop`).

On a raw-chunk, single-column stream `process` runs every dispatch of an
upload in one compiled `lax.scan`. It must return, bit for bit, what the
per-batch loop (`stream`) yields concatenated, for every graph, tail and
column pin; report the same telemetry totals; compile once per upload
length; and keep the fault injector's per-dispatch contract: one
`on_dispatch` per dispatch, in order, each through the retry.
"""
import jax
import numpy as np
import pytest

import repro.kernels.pipeline.graph as G
from repro.core.biosignal import make_app
from repro.runtime.fault import ColumnDeadError
from repro.serve.fault import FaultInjector
from repro.serve.stream import (BiosignalStream, StreamConfig,
                                StreamTelemetry)

BW = 4
CFGS = {"biosignal": StreamConfig(window=512, hop=256, batch_windows=BW),
        "asr": StreamConfig(window=512, hop=128, batch_windows=BW,
                            graph="asr")}


@pytest.fixture(scope="module")
def app():
    return make_app()


def _signal(cfg, n_frames: int, seed: int = 0):
    """A signal of ``n_frames`` frames and a few samples past the last
    (no frame at all for 0)."""
    n = (n_frames - 1) * cfg.hop + cfg.window + 7 if n_frames else 100
    return np.random.default_rng(seed).normal(size=n).astype(np.float32)


def _stream(app, graph, **kw):
    return BiosignalStream(app if graph == "biosignal" else None,
                           CFGS[graph], **kw)


@pytest.mark.parametrize("pinned", [False, True])
@pytest.mark.parametrize("n_frames", [0, 1, BW - 1, BW, BW + 1, 3 * BW + 5])
@pytest.mark.parametrize("graph", sorted(CFGS))
def test_process_equals_the_per_batch_loop(app, graph, n_frames, pinned):
    device = jax.devices()[0] if pinned else None
    sig = _signal(CFGS[graph], n_frames, seed=n_frames)
    loop_tel, batch_tel = StreamTelemetry(), StreamTelemetry()
    out = _stream(app, graph, device=device, telemetry=loop_tel,
                  stream_id="s", column=1).process(sig)
    batches = list(_stream(app, graph, device=device, telemetry=batch_tel,
                           stream_id="s", column=1).stream(sig))
    assert len(batches) == -(-n_frames // BW)
    ref = _stream(app, graph)._empty(np.float32) if not batches else {
        k: np.concatenate([np.asarray(b[k]) for b in batches])
        for k in batches[0]}
    assert out.keys() == ref.keys()
    for k in ref:
        got = np.asarray(out[k])
        assert got.dtype == np.asarray(ref[k]).dtype, k
        np.testing.assert_array_equal(got, np.asarray(ref[k]), err_msg=k)
        assert got.shape[0] == n_frames
    if pinned and n_frames:
        assert out["features" if graph == "biosignal" else "logmel"] \
            .devices() == {device}
    assert loop_tel.column_stats(2)[1].windows == \
        batch_tel.column_stats(2)[1].windows == n_frames


def test_an_autotuned_upload_uses_the_per_batch_block(app):
    """Under ``autotune`` the loop dispatches with the frame block the
    per-batch path tunes for one dispatch's chunk: one winner, cached
    under one key, and the same outputs."""
    from repro.core import autotune

    autotune.clear_cache()
    cfg = StreamConfig(window=512, hop=256, batch_windows=BW, autotune=True)
    sig = _signal(cfg, 2 * BW + 1, seed=4)
    out = BiosignalStream(app, cfg).process(sig)
    (key,) = autotune.cache_snapshot()
    assert key[:4] == ("biosignal_pipeline_stream", BW, 512, 256)
    batches = list(BiosignalStream(app, cfg).stream(sig))
    assert len(autotune.cache_snapshot()) == 1
    for k in out:
        np.testing.assert_array_equal(
            np.asarray(out[k]),
            np.concatenate([np.asarray(b[k]) for b in batches]))
    autotune.clear_cache()


def test_a_second_upload_of_one_length_traces_nothing(app, monkeypatch):
    """One traced `pallas_call` for an upload length never seen before
    (the loop's body), none for the next upload of that length."""
    calls = []
    real = G.pl.pallas_call

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(G.pl, "pallas_call", counting)
    cfg = CFGS["biosignal"]
    n = 5 * BW + 3                      # a length no other test uses
    first = BiosignalStream(app, cfg).process(_signal(cfg, n, seed=1))
    assert len(calls) == 1
    second = BiosignalStream(app, cfg).process(_signal(cfg, n, seed=2))
    assert len(calls) == 1
    assert first["class"].shape == second["class"].shape == (n,)


@pytest.mark.parametrize("kill_at", [0, 2, 3])
def test_a_kill_stops_the_upload_at_its_dispatch(app, kill_at):
    """The injector is consulted once per dispatch, in order, before the
    program runs: a kill at ``seq`` k raises after k + 1 calls, and
    nothing retires."""
    cfg = CFGS["biosignal"]
    seen = []
    inj = FaultInjector(kill={1: kill_at})
    real = inj.on_dispatch
    inj.on_dispatch = lambda col: (seen.append(col), real(col))
    tel = StreamTelemetry()
    stream = BiosignalStream(app, cfg, injector=inj, telemetry=tel,
                             stream_id="s", column=1)
    with pytest.raises(ColumnDeadError):
        stream.process(_signal(cfg, 4 * BW))
    assert seen == [1] * (kill_at + 1)
    assert tel.column_stats(2)[1].windows == 0


def test_a_transient_fault_is_retried(app):
    cfg = CFGS["biosignal"]
    sig = _signal(cfg, 3 * BW + 1)
    inj = FaultInjector(transient={(0, 1)})
    out = BiosignalStream(app, cfg, injector=inj).process(sig)
    assert inj._seq[0] == 4 + 1             # four dispatches, one retry
    ref = BiosignalStream(app, cfg).process(sig)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(ref[k]))

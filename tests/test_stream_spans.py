"""The serving path's profiler spans (`serve/trace.py`).

`BiosignalStream.process` and `ServeFrontend` record ``serve.*``
`jax.profiler.TraceAnnotation` spans. Each case here runs the path under a
`jax.profiler` session on the CPU and reads the ``.xplane.pb`` it wrote
with `jax.profiler.ProfileData`: one ``serve.process`` per call; in the
per-batch loop one ``serve.launch`` per dispatch carrying its real frames
(``valid``), its slots and ``batches=1``, ``prepare``/``launch``/
``retire`` tied by ``dispatch``, each ``serve.wait`` inside its
``serve.retire``; in the per-upload loop one of each, the launch carrying
every batch of the upload; and an admission span that ties a ticket to
the stream it opened. The outputs do not depend on a
session being active.
"""
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.biosignal import make_app
from repro.serve.engine import ColumnScheduler
from repro.serve.frontend import ServeFrontend, StreamOpen
from repro.serve.stream import BiosignalStream, StreamConfig

WINDOW, HOP, BW = 512, 256, 64
N_FRAMES = 300          # four full dispatches and a tail of 44 frames


@pytest.fixture(scope="module")
def app():
    return make_app()


@pytest.fixture(scope="module")
def signal():
    n = (N_FRAMES - 1) * HOP + WINDOW
    return np.random.default_rng(5).normal(size=n).astype(np.float32)


def _cfg(framing="kernel"):
    return StreamConfig(window=WINDOW, hop=HOP, batch_windows=BW,
                        framing=framing, outputs=("features", "margin",
                                                  "class"))


def _traced(tmp_path, fn):
    """(fn's result, [(name, start_ns, end_ns, stats)] of the ``serve.*``
    host events) with ``fn`` run under a profiler session."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (xp,) = Path(tmp_path).rglob("*.xplane.pb")
    events = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
              for plane in jax.profiler.ProfileData.from_file(str(xp)).planes
              if plane.name.startswith("/host")
              for line in plane.lines for e in line.events
              if e.name.startswith("serve.")]
    return out, sorted(events, key=lambda ev: ev[1])


def _named(events, name):
    return [ev for ev in events if ev[0] == "serve." + name]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("framing", ["kernel", "host"])
def test_process_records_one_span_tree(app, signal, framing, tmp_path):
    """`process`: one ``serve.process`` around the call. Host framing runs
    the per-batch loop (an upload, a launch of one batch per dispatch, a
    concatenation); raw chunks run the per-upload loop (one prepare, one
    launch of every batch, one retire around one wait)."""
    stream = BiosignalStream(app, _cfg(framing), stream_id="rec-7")
    stream.process(signal)                      # compile outside the trace
    out, ev = _traced(tmp_path, lambda: stream.process(signal))
    assert out["class"].shape[0] == N_FRAMES

    (proc,) = _named(ev, "process")
    assert proc[3] == {"stream": "rec-7", "samples": signal.shape[0]}
    assert all(_inside(sp, proc) for sp in ev if sp is not proc)
    k = -(-N_FRAMES // BW)
    launches = _named(ev, "launch")
    assert sum(sp[3]["valid"] for sp in launches) == N_FRAMES
    assert sum(sp[3]["slots"] for sp in launches) == k * BW
    assert sum(sp[3]["batches"] for sp in launches) == k
    if framing == "host":
        for name in ("upload", "concat"):
            (sp,) = _named(ev, name)
            assert sp[3] == {"stream": "rec-7"}
        assert len(launches) == k
        assert all(sp[3]["batches"] == 1 for sp in launches)
        return
    assert not _named(ev, "upload") and not _named(ev, "concat")
    (prepare,) = _named(ev, "prepare")
    (launch,) = launches
    (retire,) = _named(ev, "retire")
    (wait,) = _named(ev, "wait")
    assert launch[3] == {"dispatch": 0, "valid": N_FRAMES, "slots": k * BW,
                         "batches": k}
    assert prepare[3] == {"dispatch": 0} and wait[3] == {"dispatch": 0}
    assert retire[3] == {"dispatch": 0, "valid": N_FRAMES}
    assert prepare[2] <= launch[1] and launch[2] <= retire[1]
    assert _inside(wait, retire)


@pytest.mark.parametrize("framing", ["kernel", "host"])
def test_dispatch_spans_tie_prepare_launch_retire(app, signal, framing,
                                                  tmp_path):
    """The per-batch loop (`stream`): one ``serve.launch`` of one batch per
    dispatch carrying its real frames and its slots, ``prepare`` /
    ``launch`` / ``retire`` tied by ``dispatch``, each ``serve.wait``
    inside its ``serve.retire``."""
    stream = BiosignalStream(app, _cfg(framing), stream_id="rec-7")
    list(stream.stream(signal))                 # compile outside the trace
    out, ev = _traced(tmp_path, lambda: list(stream.stream(signal)))
    assert sum(b["class"].shape[0] for b in out) == N_FRAMES
    (upload,) = _named(ev, "upload")
    assert upload[3] == {"stream": "rec-7"}

    launches = _named(ev, "launch")
    k = -(-N_FRAMES // BW)
    assert len(launches) == k
    assert sum(sp[3]["valid"] for sp in launches) == N_FRAMES
    assert all(sp[3]["slots"] == BW for sp in launches)
    assert all(sp[3]["batches"] == 1 for sp in launches)
    assert [sp[3]["valid"] for sp in launches][-1] == N_FRAMES % BW

    ids = list(range(k))
    for name in ("prepare", "launch", "retire", "wait"):
        spans = _named(ev, name)
        assert sorted(sp[3]["dispatch"] for sp in spans) == ids, name
    retire = {sp[3]["dispatch"]: sp for sp in _named(ev, "retire")}
    assert {d: sp[3]["valid"] for d, sp in retire.items()} == \
        {sp[3]["dispatch"]: sp[3]["valid"] for sp in launches}
    for wait in _named(ev, "wait"):
        assert _inside(wait, retire[wait[3]["dispatch"]])
    # a dispatch is prepared, then launched, then retired
    prepare = {sp[3]["dispatch"]: sp for sp in _named(ev, "prepare")}
    for sp in launches:
        d = sp[3]["dispatch"]
        assert prepare[d][2] <= sp[1] and sp[2] <= retire[d][1]


def test_outputs_do_not_depend_on_a_profiler_session(app, signal, tmp_path):
    stream = BiosignalStream(app, _cfg())
    plain = stream.process(signal)
    traced, ev = _traced(tmp_path, lambda: stream.process(signal))
    assert len(_named(ev, "process")) == 1
    assert plain.keys() == traced.keys()
    for key in plain:
        np.testing.assert_array_equal(np.asarray(plain[key]),
                                      np.asarray(traced[key]))


def test_admission_ties_the_ticket_to_the_stream(app, signal, tmp_path):
    front = ServeFrontend(scheduler=ColumnScheduler(jax.devices()[:1]))

    def serve():
        ticket = front.submit(StreamOpen(stream_id="rec-9", app=app,
                                         cfg=_cfg()))
        front.run()
        ticket.result().process(signal[:(BW - 1) * HOP + WINDOW])
        return ticket

    ticket, ev = _traced(tmp_path, serve)
    (sub,) = _named(ev, "submit")
    assert sub[3] == {"ticket": ticket.tid, "cls": "stream"}
    (admit,) = _named(ev, "admit")
    assert admit[3] == {"ticket": ticket.tid, "cls": "stream",
                        "stream": "rec-9"}
    assert sub[2] <= admit[1]
    (proc,) = _named(ev, "process")
    assert proc[3]["stream"] == admit[3]["stream"]
    assert admit[2] <= proc[1]
    (launch,) = _named(ev, "launch")
    assert (launch[3]["valid"], launch[3]["slots"],
            launch[3]["batches"]) == (BW, BW, 1)

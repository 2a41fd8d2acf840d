"""Unified admission front-end (`serve/frontend.py`) + error taxonomy
(`serve/errors.py`).

One `submit` verb for both traffic classes, typed `Ticket` handles,
per-class QoS weighting, backpressure-aware pumping, column
re-provisioning between the classes, and the `DeprecationWarning` shims
on the three old entry points. The taxonomy tests pin that every serving
error roots at `ServeError` AND keeps its legacy base (so existing
``except RuntimeError`` / ``except ValueError`` callers still catch),
and that the historical import locations keep working.
"""
import dataclasses

import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import build_model, init_model_params
from repro.serve import errors as err
from repro.serve.engine import ColumnScheduler, Engine, PagedEngine, Request
from repro.serve.engine_fault import FaultTolerantEngine
from repro.serve.frontend import (AsrResult, AsrTranscribe, ServeFrontend,
                                  StreamOpen, Ticket)

PROMPTS = {0: [3, 1, 4, 1], 1: [5, 9, 2], 2: [6, 5], 3: [8, 9, 7, 9, 3]}


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(reduced(get_config("qwen1.5-0.5b")),
                              vocab_size=64)
    model = build_model(cfg)
    params = init_model_params(model, seed=3)
    compiled = Engine.compile_model(model)
    return model, params, compiled


def _engine(setup, cls=Engine, **kw):
    model, params, compiled = setup
    return cls(model, params, slots=2, max_len=64, temperature=0.0,
               seed=7, compiled=compiled, **kw)


# ------------------------------------------------------- ticket lifecycle

def test_lm_ticket_lifecycle(setup):
    front = ServeFrontend(engine=_engine(setup))
    t = front.submit(Request(0, list(PROMPTS[0]), max_new=4))
    assert isinstance(t, Ticket)
    assert (t.work_class, t.status) == ("lm", "queued")
    with pytest.raises(err.TicketNotReady):
        t.result()
    front.run()
    assert t.status == "done"
    req = t.result()
    assert req.rid == 0 and len(req.out) == 4


def test_stream_ticket_resolves_at_dispatch(setup):
    sched = ColumnScheduler(devices=["c0", "c1"])
    front = ServeFrontend(scheduler=sched)
    t = front.submit(StreamOpen(stream_id="s-1"))
    assert t.status == "queued"
    front.pump()
    assert t.status == "done"
    assert t.result().column == sched.column_of("s-1")


def test_run_reads_no_ticket_resolved_before_it():
    """Admission costs nothing per ticket already resolved (a server's
    tickets grow with its uptime): `run` reads none of them, and returns
    the front end's own list."""
    class Resolved:
        def __getattr__(self, name):
            raise AssertionError(f"run read {name!r} of a resolved ticket")

    front = ServeFrontend(scheduler=ColumnScheduler(devices=["c0"]))
    for i in range(3):
        front.submit(StreamOpen(stream_id=f"s-{i}"))
    front.run()
    front.tickets[:] = [Resolved() for _ in front.tickets]
    t = front.submit(StreamOpen(stream_id="s-3"))
    assert front.run() is front.tickets
    assert t.status == "done" and front.tickets[-1] is t


def test_both_classes_one_front_end(setup):
    """The headline: LM requests and stream opens through ONE verb, one
    queue, both resolving with class-appropriate results."""
    sched = ColumnScheduler(devices=["c0", "c1"])
    front = ServeFrontend(engine=_engine(setup, PagedEngine, page_size=8),
                          scheduler=sched)
    tickets = [front.submit(Request(r, list(p), max_new=4))
               for r, p in PROMPTS.items()]
    tickets += [front.submit(StreamOpen(stream_id=f"s{i}"))
                for i in range(3)]
    front.run()
    assert all(t.status == "done" for t in tickets)
    dense = {r.rid: tuple(r.out) for r in
             _serve_dense(setup, PROMPTS)}
    assert {t.result().rid: tuple(t.result().out)
            for t in tickets[:4]} == dense
    assert sorted(sched.loads()) == [1, 2]     # streams balanced


def _serve_dense(setup, prompts):
    eng = _engine(setup)
    for rid, p in prompts.items():
        eng.add_request(Request(rid, list(p), max_new=4))
    return eng.run_to_completion(max_steps=500)


def test_submit_rejects_unknown_work(setup):
    front = ServeFrontend(engine=_engine(setup))
    with pytest.raises(TypeError):
        front.submit("not a work item")
    with pytest.raises(ValueError):
        front.submit(StreamOpen(stream_id="s"))   # no scheduler wired


def test_typed_rejection_lands_on_ticket(setup):
    front = ServeFrontend(engine=_engine(setup))
    t = front.submit(Request(0, list(range(2, 80)), max_new=4))
    front.pump()
    assert t.status == "failed"
    with pytest.raises(err.PromptTooLong):
        t.result()


def test_qos_round_robin_interleaves_classes(setup):
    """A burst of one class cannot starve the other: with weights
    {lm: 1, stream: 2}, each pump cycle dispatches 1 LM per 2 streams
    while both classes wait."""
    order = []

    class SpyEngine:
        def add_request(self, req):
            order.append(("lm", req.rid))

    class SpyScheduler:
        def place_stream(self, app=None, cfg=None, *, stream_id):
            order.append(("stream", stream_id))
            return stream_id

    front = ServeFrontend(engine=SpyEngine(), scheduler=SpyScheduler(),
                          qos={"lm": 1, "stream": 2})
    for i in range(3):
        front.submit(Request(i, [1, 2], max_new=1))
    for i in range(6):
        front.submit(StreamOpen(stream_id=i))
    front.pump()
    assert order[:6] == [("lm", 0), ("stream", 0), ("stream", 1),
                         ("lm", 1), ("stream", 2), ("stream", 3)]
    assert len(order) == 9                     # everything dispatched


def test_queue_full_backpressure_retries_next_pump(setup):
    """`QueueFull` leaves the ticket QUEUED (not failed); `run`
    re-pumps as the engine frees queue space until every ticket
    resolves."""
    eng = _engine(setup, FaultTolerantEngine, max_queue=2)
    front = ServeFrontend(engine=eng)
    tickets = [front.submit(Request(r, list(p), max_new=4))
               for r, p in PROMPTS.items()]
    n = front.pump()
    assert n == 2                              # the queue bound
    statuses = [t.status for t in tickets]
    assert statuses == ["running", "running", "queued", "queued"]
    front.run()
    assert all(t.status == "done" for t in tickets)


# ------------------------------------------------------- the ASR class

@pytest.fixture(scope="module")
def asr_setup():
    """Reduced whisper-medium enc-dec engine — the ASR decode backend."""
    cfg = dataclasses.replace(reduced(get_config("whisper-medium")),
                              vocab_size=64)
    model = build_model(cfg)
    params = init_model_params(model, seed=3)
    compiled = Engine.compile_model(model)
    return model, params, compiled


def _audio(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal(n).astype(np.float32)


def test_asr_ticket_lifecycle(asr_setup):
    """The third class end to end: fused featurize at dispatch, enc-dec
    decode, AsrResult pairing log-mel features with the finished
    request."""
    front = ServeFrontend(engine=_engine(asr_setup))
    t = front.submit(AsrTranscribe(7, _audio(512 * 3), max_new=4))
    assert (t.work_class, t.status) == ("asr", "queued")
    with pytest.raises(err.TicketNotReady):
        t.result()
    front.run()
    assert t.status == "done"
    res = t.result()
    assert isinstance(res, AsrResult) and res.rid == 7
    # 512*3 samples at (window=512, hop=160) -> 7 frames of 64 mels
    assert res.features.shape == (7, 64)
    assert np.isfinite(np.asarray(res.features)).all()
    assert res.tokens == res.request.out
    assert 1 <= len(res.tokens) <= 4
    assert front._features == {}               # stash drained on finish


def test_asr_requires_engine():
    front = ServeFrontend(scheduler=ColumnScheduler(devices=["c0"]))
    with pytest.raises(ValueError, match="no engine"):
        front.submit(AsrTranscribe(0, _audio(1024)))


def test_asr_default_qos_covers_three_classes(asr_setup):
    front = ServeFrontend(engine=_engine(asr_setup))
    assert front.qos == {"lm": 1, "stream": 1, "asr": 1}


def test_three_classes_one_front_end(asr_setup):
    """LM requests, stream opens, AND transcriptions through the ONE
    submit verb, each resolving with its class-typed result."""
    sched = ColumnScheduler(devices=["c0", "c1"])
    front = ServeFrontend(engine=_engine(asr_setup), scheduler=sched)
    t_lm = front.submit(Request(0, [3, 1, 4], max_new=4))
    t_st = front.submit(StreamOpen(stream_id="s-0"))
    t_asr = front.submit(AsrTranscribe(1, _audio(512 * 2, seed=2),
                                       max_new=4))
    front.run()
    assert [t.status for t in (t_lm, t_st, t_asr)] == ["done"] * 3
    assert t_lm.result().rid == 0
    assert t_st.result().column == sched.column_of("s-0")
    res = t_asr.result()
    assert isinstance(res, AsrResult)
    assert res.features.shape[1] == 64


def test_asr_backpressure_reuses_feature_stash(asr_setup):
    """`QueueFull` leaves ASR tickets queued; the features computed at
    the first dispatch attempt are stashed and reused on the retry (and
    every ticket still resolves)."""
    eng = _engine(asr_setup, FaultTolerantEngine, max_queue=1)
    front = ServeFrontend(engine=eng)
    tickets = [front.submit(AsrTranscribe(r, _audio(512 * 2, seed=r),
                                          max_new=2)) for r in range(3)]
    n = front.pump()
    assert n == 1                              # the queue bound
    assert [t.status for t in tickets] == ["running", "queued", "queued"]
    front.run()
    assert all(t.status == "done" for t in tickets)
    assert {t.result().rid for t in tickets} == {0, 1, 2}
    assert front._features == {}


# --------------------------------------------------------- re-provisioning

def test_lend_and_return_columns():
    sched = ColumnScheduler(devices=["c0", "c1", "c2"])
    for i in range(3):
        sched.admit(f"s{i}")
    front = ServeFrontend(scheduler=sched)
    devs = front.lend_columns(2)
    assert len(devs) == 2 and len(sched.healthy_columns()) == 1
    # the lent columns' streams drained onto the survivor
    survivor = sched.healthy_columns()[0]
    assert all(sched.column_of(f"s{i}") == survivor for i in range(3))
    # a failed column is NOT restorable; a withdrawn one is
    with pytest.raises(err.InsufficientHealthyWorkers):
        front.lend_columns(1)                  # quorum of one holds
    assert front.return_columns() == sorted(
        set(range(3)) - {survivor}, reverse=True)
    assert sched.healthy_columns() == [0, 1, 2]


def test_withdraw_restore_guards():
    sched = ColumnScheduler(devices=["c0", "c1"])
    sched.withdraw(1)
    with pytest.raises(ValueError):
        sched.withdraw(1)                      # already withdrawn
    with pytest.raises(ValueError):
        sched.restore(0)                       # never withdrawn
    sched.restore(1)
    assert sched.healthy_columns() == [0, 1]
    # a genuinely dead column is not restorable
    sched.mark_dead(1)
    with pytest.raises(ValueError):
        sched.restore(1)


# ------------------------------------------------------ deprecation shims

def test_engine_submit_shim_warns(setup):
    eng = _engine(setup)
    with pytest.warns(DeprecationWarning, match="Engine.submit"):
        eng.submit(Request(0, [1, 2], max_new=1))
    assert eng.queue[0].rid == 0               # still lands in the queue


def test_fault_tolerant_submit_shim_warns(setup):
    eng = _engine(setup, FaultTolerantEngine, max_queue=4)
    with pytest.warns(DeprecationWarning, match="Engine.submit"):
        eng.submit(Request(0, [1, 2], max_new=1), ttl=10.0)
    assert 0 in eng.deadlines                  # kwargs reach add_request


def test_open_stream_shim_warns():
    sched = ColumnScheduler(devices=["c0"])
    with pytest.warns(DeprecationWarning, match="open_stream"):
        sched.open_stream(stream_id="s-legacy")
    assert sched.column_of("s-legacy") == 0


# --------------------------------------------------------- error taxonomy

def test_every_serving_error_roots_at_serve_error():
    for name in err.__all__:
        cls = getattr(err, name)
        if isinstance(cls, type) and issubclass(cls, Exception):
            assert issubclass(cls, err.ServeError), name


def test_legacy_bases_preserved():
    """Old call sites catch by the legacy base; the taxonomy keeps it."""
    assert issubclass(err.PromptTooLong, ValueError)
    assert issubclass(err.PagedCacheUnsupported, TypeError)
    for cls in (err.QueueFull, err.RequestExpired, err.EngineStalled,
                err.InsufficientHealthyWorkers, err.InsufficientPages,
                err.TransientDispatchError, err.TicketNotReady):
        assert issubclass(cls, RuntimeError), cls
    # the two errors the dispatch retry loop must NOT swallow stay
    # OUTSIDE RuntimeError
    for cls in (err.ColumnDeadError, err.ColumnHungError):
        assert issubclass(cls, err.ServeError)
        assert not issubclass(cls, RuntimeError), cls


def test_historical_import_locations_still_work():
    from repro.runtime.fault import (ColumnDeadError,
                                     InsufficientHealthyWorkers,
                                     TransientDispatchError)
    from repro.serve.engine import EngineStalled, PromptTooLong
    from repro.serve.engine_fault import QueueFull, RequestExpired
    from repro.serve.fault import ColumnHungError
    assert ColumnDeadError is err.ColumnDeadError
    assert InsufficientHealthyWorkers is err.InsufficientHealthyWorkers
    assert TransientDispatchError is err.TransientDispatchError
    assert EngineStalled is err.EngineStalled
    assert PromptTooLong is err.PromptTooLong
    assert QueueFull is err.QueueFull
    assert RequestExpired is err.RequestExpired
    assert ColumnHungError is err.ColumnHungError

"""Chaos tests for fault-tolerant column serving (`serve/fault.py`).

THE INVARIANT under test everywhere: for ANY injected fault schedule —
column deaths at arbitrary dispatch steps, transient dispatch faults,
stragglers, hangs — the recovered output is **bit-identical** to the
fault-free single-column run; only the work
distribution changes. Every scenario runs on the injected `VirtualClock`
so heartbeat timeouts, EWMA rates, and straggler medians replay
deterministically.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.biosignal import make_app
from repro.kernels.pipeline.shard import column_shares, requeue_ranges
from repro.runtime.fault import (InsufficientHealthyWorkers,
                                 StragglerDetector)
from repro.serve.engine import ColumnScheduler
from repro.serve.fault import (ColumnHungError, FaultInjector,
                               FaultTolerantColumnRunner, VirtualClock)
from repro.serve.stream import (BiosignalStream, StreamConfig,
                                StreamTelemetry)

WINDOW, HOP, BW = 512, 256, 2
CFG = StreamConfig(window=WINDOW, hop=HOP, batch_windows=BW)


@pytest.fixture(scope="module")
def app():
    return make_app()


def _signal(n_frames: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    n_samples = WINDOW + (n_frames - 1) * HOP
    return rng.normal(size=n_samples).astype(np.float32)


@pytest.fixture(scope="module")
def reference(app):
    """Fault-free single-column outputs, keyed by frame count."""
    cache = {}

    def get(n_frames: int):
        if n_frames not in cache:
            cache[n_frames] = BiosignalStream(app, CFG).process(
                _signal(n_frames))
        return cache[n_frames]

    return get


def _assert_identical(ref, out):
    assert set(ref) == set(out)
    for k in ref:
        a, b = jnp.asarray(ref[k]), jnp.asarray(out[k])
        assert a.dtype == b.dtype and a.shape == b.shape, k
        assert (a == b).all(), k


def _runner(app, n_columns, injector, clock, **kw):
    return FaultTolerantColumnRunner(app, CFG, n_columns=n_columns,
                                     injector=injector, clock=clock, **kw)


# ------------------------------------------------------- requeue algebra

def test_requeue_ranges_cover_exactly_and_stay_ordered():
    ranges = [(3, 4), (10, 1), (20, 7)]
    parts = requeue_ranges(ranges, 3, (1.0, 0.0, 2.0))
    assert parts[1] == []                          # zero weight: nothing
    flat = [r for col in parts for r in col]
    assert sum(c for _, c in flat) == 12
    # reassembled coverage equals the input coverage exactly
    covered = sorted(f for s, c in flat for f in range(s, s + c))
    wanted = sorted(f for s, c in ranges for f in range(s, s + c))
    assert covered == wanted
    # shares follow column_shares on the total
    assert [sum(c for _, c in col) for col in parts] == \
        list(column_shares(12, 3, (1.0, 0.0, 2.0)))


def test_requeue_ranges_degenerate():
    assert requeue_ranges([], 3) == [[], [], []]
    assert requeue_ranges([(5, 0)], 2) == [[], []]
    parts = requeue_ranges([(7, 3)], 1)
    assert parts == [[(7, 3)]]


# --------------------------------------------------------- death sweeps

@pytest.mark.parametrize("n_frames,n_columns,kill_step", [
    (9, 2, 0),       # D=2 degenerate, death on the very first dispatch
    (9, 2, 1),
    (13, 3, 0),
    (13, 3, 2),      # near the end of the column's share
    (21, 4, 1),
    (21, 4, 2),
])
def test_killed_column_recovers_bit_identical(app, reference, n_frames,
                                              n_columns, kill_step):
    clk = VirtualClock()
    inj = FaultInjector(kill={0: kill_step}, dispatch_s=0.01, clock=clk)
    r = _runner(app, n_columns, inj, clk)
    out = r.process(_signal(n_frames))
    _assert_identical(reference(n_frames), out)
    assert r.scheduler.dead == {0}
    # the killed dispatch's range was never retired, so it must requeue
    assert r.requeues >= 1


def test_multi_kill_recovers_bit_identical(app, reference):
    clk = VirtualClock()
    inj = FaultInjector(kill={0: 1, 2: 0}, dispatch_s=0.01, clock=clk)
    r = _runner(app, 4, inj, clk)
    out = r.process(_signal(21))
    _assert_identical(reference(21), out)
    assert r.scheduler.dead == {0, 2}


def test_kill_interleaved_with_transients(app, reference):
    """Transients on survivors while another column dies: the retry layer
    absorbs the former, the requeue layer the latter, independently."""
    clk = VirtualClock()
    inj = FaultInjector(kill={1: 1},
                        transient={(0, 0), (2, 1), (2, 2)},
                        dispatch_s=0.01, clock=clk)
    r = _runner(app, 3, inj, clk)
    out = r.process(_signal(13))
    _assert_identical(reference(13), out)
    assert r.scheduler.dead == {1}


def test_all_columns_dead_raises_typed_error(app):
    clk = VirtualClock()
    inj = FaultInjector(kill={0: 0, 1: 1}, dispatch_s=0.01, clock=clk)
    r = _runner(app, 2, inj, clk)
    with pytest.raises(InsufficientHealthyWorkers):
        r.process(_signal(9))


# -------------------------------------------------- hangs and stragglers

def test_hung_column_dies_by_heartbeat_timeout(app, reference):
    """A wedged column (no retire, no error) is only resolvable through
    the heartbeat timeout: the retire feed goes quiet, supervision
    declares it dead, its queue requeues."""
    clk = VirtualClock()
    inj = FaultInjector(hang_from={2: 1}, dispatch_s=0.5, clock=clk)
    r = _runner(app, 4, inj, clk, heartbeat_timeout=2.0)
    out = r.process(_signal(21))
    _assert_identical(reference(21), out)
    assert 2 in r.scheduler.dead


def test_hung_column_without_supervision_stalls_loudly(app):
    clk = VirtualClock()
    inj = FaultInjector(hang_from={1: 0}, dispatch_s=0.5, clock=clk)
    r = _runner(app, 2, inj, clk, max_idle_passes=5)
    with pytest.raises(RuntimeError, match="stopped progressing"):
        r.process(_signal(9))


def test_straggler_column_is_evicted_and_work_requeued(app, reference):
    clk = VirtualClock()
    inj = FaultInjector(slow={3: 0.2}, dispatch_s=0.01, clock=clk)
    det = StragglerDetector(straggler_factor=2.0, evict_after=2)
    r = _runner(app, 4, inj, clk, straggler=det)
    out = r.process(_signal(21))
    _assert_identical(reference(21), out)
    assert r.scheduler.dead == {3}


# ------------------------------------------------- injector determinism

def test_injector_reset_replays_identically(app):
    clk = VirtualClock()
    inj = FaultInjector(kill={0: 1}, transient={(1, 0)},
                        dispatch_s=0.01, clock=clk)
    r1 = _runner(app, 3, inj, clk)
    out1 = r1.process(_signal(13))
    inj.reset()                            # counters rewind, clock doesn't
    r2 = _runner(app, 3, inj, clk)
    out2 = r2.process(_signal(13))
    _assert_identical(out1, out2)
    assert r1.scheduler.dead == r2.scheduler.dead == {0}


def test_injector_sequences_are_per_column():
    inj = FaultInjector(kill={1: 1})
    inj.on_dispatch(0)
    inj.on_dispatch(0)                     # column 0 seq advances alone
    inj.on_dispatch(1)                     # column 1 seq 0: alive
    with pytest.raises(Exception) as ei:
        inj.on_dispatch(1)                 # column 1 seq 1: dies
    assert ei.value.column == 1
    with pytest.raises(ColumnHungError):
        FaultInjector(hang_from={0: 0}).on_dispatch(0)


# --------------------------------------------------- scheduler contract

def test_scheduler_mark_dead_drains_and_requeues_admission():
    clk = VirtualClock()
    tel = StreamTelemetry(clock=clk)
    sched = ColumnScheduler(["d0", "d1", "d2"], telemetry=tel, clock=clk)
    for sid in ("a", "b", "c"):
        sched.admit(sid)
    assert sched.column_of("b") == 1
    moves = sched.mark_dead(1)
    assert set(moves) == {"b"}             # the dead column's stream moved
    assert sched.column_of("b") != 1
    assert sched.pop_moves() == moves      # drain moves ride pending_moves
    assert sched.healthy_columns() == [0, 2]
    # new admissions never land on the dead column
    for i in range(4):
        sched.admit(f"n{i}")
    assert all(sched.column_of(f"n{i}") != 1 for i in range(4))
    assert sched.mark_dead(1) == {}        # idempotent


def test_scheduler_deal_weights_zero_dead_columns():
    clk = VirtualClock()
    tel = StreamTelemetry(clock=clk)
    sched = ColumnScheduler(["d0", "d1", "d2"], telemetry=tel, clock=clk)
    for sid in ("a", "b", "c"):
        sched.admit(sid)
    for _ in range(3):                     # warm all EWMAs equally
        clk.advance(1.0)
        for sid in ("a", "b", "c"):
            tel.record_retire(sid, 8)
    sched.mark_dead(0)
    w = sched.deal_weights()
    assert w[0] == 0.0 and w[1] > 0.0 and w[2] > 0.0
    shares = column_shares(12, 3, w)
    assert shares[0] == 0 and sum(shares) == 12
    sched.mark_dead(2)
    with pytest.raises(InsufficientHealthyWorkers):
        sched.mark_dead(1)


def test_scheduler_supervise_heartbeat_and_straggler_paths():
    clk = VirtualClock()
    tel = StreamTelemetry(clock=clk)
    det = StragglerDetector(straggler_factor=2.0, evict_after=2)
    sched = ColumnScheduler(["d0", "d1", "d2", "d3"], telemetry=tel,
                            clock=clk, heartbeat_timeout=5.0, straggler=det)
    for sid in ("a", "b", "c", "d"):
        sched.admit(sid)
    # retires beat the stream's column; column 3 stays silent past the
    # timeout while the straggler detector condemns column 1
    for _ in range(3):
        clk.advance(1.0)
        for sid in ("a", "b", "c"):
            tel.record_retire(sid, 4)
        for col, dt in ((0, 0.1), (1, 0.9), (2, 0.1), (3, 0.1)):
            sched.record_batch_time(col, dt)
    clk.advance(3.0)                       # t=6: column 3 beat only at t=0
    for sid in ("a", "b", "c"):
        tel.record_retire(sid, 4)
    first = sched.supervise()
    assert first == [3]                    # heartbeat timeout; straggler
    #                                        strike 1 is below evict_after
    second = sched.supervise()
    assert second == [1]                   # straggler strike 2 evicts
    assert sched.healthy_columns() == [0, 2]
    assert sched.supervise() == []         # stable afterwards

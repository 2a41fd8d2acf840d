"""Load-aware column runtime: non-uniform deal, telemetry, scheduler,
trajectory accumulation, and the no-baseline gate path.

The deal properties mirror the PR-4 equal-deal suite: whatever weight
vector the scheduler produces, the deal must stay hop-aligned, cover
every frame exactly once, and be numerically invisible (sharded ==
single-device). Telemetry and scheduler tests run on an injected virtual
clock so the EWMA math is deterministic."""
import json
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.biosignal import make_app, synthetic_respiration
from repro.kernels.pipeline.ops import app_pipeline_stream
from repro.kernels.pipeline.shard import column_chunks, column_shares
from repro.serve.engine import ColumnScheduler
from repro.serve.stream import (BiosignalStream, ColumnStats, StreamConfig,
                                StreamTelemetry, column_mesh, frame_count)

ROOT = Path(__file__).resolve().parent.parent

# weight sweeps: uniform, skewed, zero-weight (cold column), float mix,
# single-column degenerate — paired with dividing and non-dividing
# (n_frames, D) combinations below
WEIGHTS = [
    (1, (1.0,)),
    (2, (3, 1)),
    (3, (0, 1, 0)),
    (4, (1, 1, 1, 1)),
    (4, (0.5, 2.0, 1.0, 0.25)),
    (4, (0, 1, 1, 2)),
    (8, (1, 3, 0, 1, 1, 0, 2, 1)),
]


class VirtualClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


# ---------------------------------------------------------------- shares

@pytest.mark.parametrize("n_frames", [1, 7, 16, 64, 101])
@pytest.mark.parametrize("n_columns,weights", WEIGHTS)
def test_column_shares_cover_exactly(n_frames, n_columns, weights):
    shares = column_shares(n_frames, n_columns, weights)
    assert len(shares) == n_columns
    assert sum(shares) == n_frames          # full coverage, no overlap
    assert all(s >= 0 for s in shares)
    total = sum(weights)
    for s, w in zip(shares, weights):
        if w == 0:
            assert s == 0                   # cold column gets nothing
        else:                               # quantization error < 1 frame
            assert abs(s - n_frames * w / total) < 1.0 + 1e-9


def test_column_shares_equal_deal_and_errors():
    assert column_shares(10, 4) == (3, 3, 3, 3)      # padded equal deal
    assert column_shares(10, 1) == (10,)
    assert column_shares(5, 2, (1, 1)) == (3, 2)     # ties -> lower index
    with pytest.raises(AssertionError):
        column_shares(10, 2, (1,))                   # wrong length
    with pytest.raises(AssertionError):
        column_shares(10, 2, (-1, 2))                # negative weight
    with pytest.raises(AssertionError):
        column_shares(10, 2, (0, 0))                 # all-zero


@pytest.mark.parametrize("window,hop,n_samples", [
    (512, 128, 512 * 9),
    (512, 512, 512 * 5 + 17),
    (1024, 320, 7001),
])
@pytest.mark.parametrize("n_columns,weights", WEIGHTS)
def test_weighted_chunks_hop_aligned_and_cover(window, hop, n_samples,
                                               n_columns, weights):
    """Chunk d starts exactly at its first owned frame's sample (a hop
    multiple), frames to >= its share, and the in-signal part matches the
    signal (zero-pad past the end)."""
    sig = np.arange(n_samples, dtype=np.float32)
    n = frame_count(n_samples, window, hop)
    deal = column_chunks(sig, window, hop, n_columns, weights)
    chunks, n_out, shares = deal.chunks, deal.n_frames, deal.shares
    assert n_out == n and sum(shares) == n
    n_max = max(shares)
    assert chunks.shape == (n_columns, n_max * hop + window - hop)
    offsets = np.concatenate([[0], np.cumsum(shares)[:-1]])
    for d in range(n_columns):
        start = int(offsets[d]) * hop           # hop-aligned by construction
        got = np.asarray(chunks[d])
        want = sig[start: start + got.shape[0]]
        np.testing.assert_array_equal(got[: want.shape[0]], want)
        assert (got[want.shape[0]:] == 0).all()
        if shares[d]:
            own = got[: shares[d] * hop + window - hop]
            assert frame_count(own.shape[0], window, hop) == shares[d]


@pytest.mark.parametrize("window,hop,n_samples", [
    (512, 128, 512 * 9),        # deep overlap
    (512, 512, 512 * 5 + 17),   # no overlap, non-dividing signal
])
@pytest.mark.parametrize("n_columns,weights", WEIGHTS)
def test_weighted_sharded_matches_single_device(window, hop, n_samples,
                                                n_columns, weights):
    """THE property: arbitrary valid weight vectors are numerically
    invisible — sharded output bit-matches the single-device kernel."""
    app = make_app()
    sig, _ = synthetic_respiration(1, n_samples, seed=n_samples + n_columns)
    raw = sig[0]
    ref = app_pipeline_stream(app, raw, window=window, hop=hop)
    # real shard_map when the device set allows (the CI multi-device leg
    # forces 8 host devices), serial fallback everywhere else
    out = app_pipeline_stream(app, raw, window=window, hop=hop,
                              n_columns=n_columns, column_weights=weights,
                              mesh=column_mesh(n_columns))
    assert sorted(out) == sorted(ref)
    for k in ref:
        a, b = np.asarray(ref[k]), np.asarray(out[k])
        assert a.shape == b.shape, (k, a.shape, b.shape)
        if k == "class":
            np.testing.assert_array_equal(b, a)
        else:
            np.testing.assert_allclose(b, a, atol=1e-4)


def test_weighted_autotune_key_carries_share_signature():
    """A winner measured on a weighted deal must not leak onto the equal
    deal of the same traffic shape (and vice versa)."""
    from repro.core import autotune

    autotune.clear_cache()
    app = make_app()
    sig, _ = synthetic_respiration(1, 512 * 8, seed=21)
    raw = sig[0]
    app_pipeline_stream(app, raw, window=512, hop=256, autotune=True,
                        n_columns=4)
    app_pipeline_stream(app, raw, window=512, hop=256, autotune=True,
                        n_columns=4, column_weights=(1, 2, 2, 3))
    keys = sorted(autotune.cache_snapshot(), key=len)
    assert len(keys) == 2
    n = frame_count(512 * 8, 512, 256)
    assert "w" not in keys[0]
    sig_tail = keys[1][keys[1].index("w") + 1:]
    assert sig_tail == column_shares(n, 4, (1, 2, 2, 3))
    autotune.clear_cache()


# ------------------------------------------------------------- telemetry

def test_telemetry_ewma_math_and_column_aggregation():
    clk = VirtualClock()
    tel = StreamTelemetry(alpha=0.5, clock=clk)
    tel.attach("a", 0)
    tel.attach("b", 1)
    assert not tel.warm
    tel.record_retire("a", 8)           # first retire: seeds the clock only
    assert not tel.warm and tel.stream_rate("a") == 0.0
    clk.advance(1.0)
    tel.record_retire("a", 8)           # 8 windows / 1 s
    assert tel.warm
    assert tel.stream_rate("a") == pytest.approx(8.0)
    clk.advance(0.5)
    tel.record_retire("a", 8)           # inst 16 w/s -> EWMA 0.5*16+0.5*8
    assert tel.stream_rate("a") == pytest.approx(12.0)
    assert tel.column_rate(0) == pytest.approx(12.0)
    assert tel.column_rate(1) == 0.0    # b never retired
    stats = tel.column_stats(2)
    assert stats[0] == ColumnStats(column=0, streams=1, windows=24,
                                   rate=pytest.approx(12.0),
                                   load=pytest.approx(12.0))
    assert stats[1].streams == 1 and stats[1].rate == 0.0
    # two streams on one column: load sums their rates
    tel.attach("b", 0)
    clk.advance(1.0)
    tel.record_retire("b", 4)
    clk.advance(1.0)
    tel.record_retire("b", 4)
    assert tel.column_load(0) == pytest.approx(tel.stream_rate("a") + 4.0)
    tel.detach("a")
    assert tel.column_load(0) == pytest.approx(4.0)
    assert tel.column_stats(1)[0].streams == 1


def test_stream_reports_retires_to_telemetry():
    """The runtime integration: every processed upload retires its frames
    through the telemetry under the stream's id/column (one retire a
    call on the per-upload loop, so a rate needs two calls)."""
    app = make_app()
    tel = StreamTelemetry()
    sig, _ = synthetic_respiration(1, 512 * 10 + 3, seed=17)
    raw = sig[0]
    cfg = StreamConfig(window=512, hop=256, batch_windows=4)
    stream = BiosignalStream(app, cfg, telemetry=tel, stream_id="s0",
                             column=2)
    n = frame_count(raw.shape[0], 512, 256)
    stream.process(raw)
    assert tel.column_stats(3)[2].windows == n
    assert not tel.warm                 # one retire seeds the clock only
    stream.process(raw)
    stats = tel.column_stats(3)
    assert stats[2].windows == 2 * n
    assert stats[2].streams == 1
    assert tel.warm                     # >= 2 retires -> real rate
    assert tel.stream_rate("s0") > 0.0


def test_stream_column_weights_runtime_equivalence_and_repin():
    app = make_app()
    sig, _ = synthetic_respiration(1, 512 * 21 + 77, seed=19)
    raw = sig[0]
    ref = BiosignalStream(app, StreamConfig(
        window=512, hop=256, batch_windows=6)).process(raw)
    cfg = StreamConfig(window=512, hop=256, batch_windows=2, n_columns=3,
                       column_weights=(1.0, 2.5, 0.5))
    out = BiosignalStream(app, cfg).process(raw)
    for k in ref:
        a, b = np.asarray(ref[k]), np.asarray(out[k])
        assert a.shape == b.shape
        if k == "class":
            np.testing.assert_array_equal(b, a)
        else:
            np.testing.assert_allclose(b, a, atol=1e-4)
    # weights demand a kernel framing and a matching length
    with pytest.raises(AssertionError):
        BiosignalStream(app, StreamConfig(n_columns=2,
                                          column_weights=(1,)))
    with pytest.raises(AssertionError):
        BiosignalStream(app, StreamConfig(n_columns=2, framing="host",
                                          column_weights=(1, 1)))
    # repin moves future dispatches (pinned streams only)
    dev = jax.devices()[0]
    s = BiosignalStream(app, StreamConfig(window=512, hop=256))
    s.repin(dev)
    assert s.device is dev
    with pytest.raises(AssertionError):
        BiosignalStream(app, cfg).repin(dev)


# ------------------------------------------------------------- scheduler

def _warm_scheduler(rates, *, alpha=0.5, ratio=2.0):
    """A D-column scheduler with one stream per column retiring at the
    given windows/s on a virtual clock."""
    clk = VirtualClock()
    tel = StreamTelemetry(alpha=alpha, clock=clk)
    devs = [jax.devices()[0]] * len(rates)
    sched = ColumnScheduler(devs, telemetry=tel, rebalance_ratio=ratio)
    for i in range(len(rates)):
        sched.admit(f"s{i}")
    for _ in range(3):
        for i, r in enumerate(rates):
            # each stream's inter-retire gap is one full 1.0 s cycle, so
            # retiring r windows per cycle measures r windows/s
            clk.advance(1.0 / len(rates))
            tel.record_retire(f"s{i}", r)
    return sched, tel, clk


def test_scheduler_cold_falls_back_to_counts():
    sched = ColumnScheduler([jax.devices()[0]] * 3,
                            telemetry=StreamTelemetry())
    assert sched.measured_loads() is None
    for i in range(4):
        sched.admit(f"s{i}")
    # round-robin fill, then double up on the lowest index
    assert [sched.column_of(f"s{i}") for i in range(4)] == [0, 1, 2, 0]


def test_scheduler_places_by_measured_load():
    """Column 0 hosts one HEAVY stream (24 w/s), columns 1-2 one light
    stream each (4 w/s): counts tie everywhere but measured load says the
    new stream belongs anywhere but column 0."""
    sched, tel, clk = _warm_scheduler([24.0, 4.0, 4.0])
    loads = sched.measured_loads()
    assert loads == pytest.approx([24.0, 4.0, 4.0], rel=1e-3)
    sched.admit("new")
    assert sched.column_of("new") == 1      # least load, tie -> low index
    # count-based would have put it on column 0 (all counts were 1)


def test_scheduler_rebalance_moves_from_hot_to_cold():
    """Two heavies pile on column 0 while column 2 idles: rebalance
    re-pins one of them and reports the move for repin()."""
    clk = VirtualClock()
    tel = StreamTelemetry(alpha=0.5, clock=clk)
    devs = [jax.devices()[0]] * 3
    sched = ColumnScheduler(devs, telemetry=tel, rebalance_ratio=1.5)
    for sid, col in [("h0", 0), ("h1", 0), ("l0", 1)]:
        sched.admit(sid)
        sched._move(sid, col)               # force the pathological layout
    for _ in range(3):
        for sid, r in [("h0", 10.0), ("h1", 10.0), ("l0", 2.0)]:
            clk.advance(0.33)
            tel.record_retire(sid, r * 0.33)
    before = sched.measured_loads()
    assert max(before) / min(b for b in before if b > 0) > 1.5 \
        or min(before) == 0.0
    moves = sched.rebalance()
    assert moves                            # something moved...
    assert all(sched.column_of(s) != 0 for s in moves)
    after = sched.measured_loads()
    assert max(after) < max(before)         # ...and the spread shrank
    # a balanced scheduler is a no-op
    sched2, _, _ = _warm_scheduler([8.0, 8.0, 8.0], ratio=2.0)
    assert sched2.rebalance() == {}


def test_scheduler_rebalance_count_fallback():
    """Cold telemetry: rebalance still evens out raw stream counts."""
    sched = ColumnScheduler([jax.devices()[0]] * 2, rebalance_ratio=1.5)
    for i in range(4):
        sched.admit(f"s{i}")
        sched._move(f"s{i}", 0)             # all four on column 0
    moves = sched.rebalance()
    assert sched.loads() == [2, 2]
    assert len(moves) == 2


def test_scheduler_deal_weights_from_column_rates():
    sched, tel, clk = _warm_scheduler([6.0, 12.0, 12.0])
    w = sched.deal_weights()
    assert w == pytest.approx((6.0, 12.0, 12.0), rel=1e-3)
    # unobserved column gets the mean observed rate, not zero
    tel2 = StreamTelemetry(alpha=0.5, clock=clk)
    sched2 = ColumnScheduler([jax.devices()[0]] * 3, telemetry=tel2)
    assert sched2.deal_weights() is None    # cold
    tel2.attach("a", 0)
    tel2.record_retire("a", 4)
    clk.advance(1.0)
    tel2.record_retire("a", 4)
    assert sched2.deal_weights() == pytest.approx((4.0, 4.0, 4.0))


def test_cold_streams_count_at_mean_warm_rate():
    """A burst of cold admissions must not pile onto one column: against
    measured windows/s loads each cold stream weighs the MEAN warm rate
    (not a unitless 1.0), so the burst spreads."""
    sched, tel, clk = _warm_scheduler([50.0, 60.0, 70.0])
    for i in range(6):                  # 6 cold streams, none retired yet
        sched.admit(f"cold{i}")
    # each cold stream weighed ~60 w/s -> 2 land on every column
    assert sorted(sched.loads()) == [3, 3, 3]
    loads = sched.measured_loads()
    assert max(loads) / min(loads) < 1.5


def test_manual_repin_reattributes_telemetry():
    app = make_app()
    tel = StreamTelemetry()
    # batch_windows=5: the default 8 would pre-trace the exact dispatch
    # shape test_stream_kernel's one-pallas_call-per-batch contract test
    # counts traces on
    s = BiosignalStream(app, StreamConfig(window=512, hop=256,
                                          batch_windows=5),
                        telemetry=tel, stream_id="s0", column=0)
    sig, _ = synthetic_respiration(1, 512 * 4, seed=31)
    s.process(sig[0])
    assert tel.column_stats(2)[0].windows > 0
    w0 = tel.column_stats(2)[0].windows
    s.repin(jax.devices()[0], column=1)     # manual move: new column
    assert s.column == 1
    s.process(sig[0])
    stats = tel.column_stats(2)
    assert stats[0].windows == w0           # old column stopped accruing
    assert stats[1].windows == w0           # ...the new one took over


def test_deal_weights_band_clusters_near_ties():
    """The deadband: rates within the band collapse to their cluster
    mean (EWMA jitter between identical columns must not deal them
    unequal shares); a genuinely slow column stays its own cluster."""
    sched, tel, clk = _warm_scheduler([5.0, 10.0, 11.0, 9.5])
    w = sched.deal_weights(band=0.3)
    assert w[0] == pytest.approx(5.0, rel=1e-3)      # 2x away: own cluster
    assert w[1] == w[2] == w[3] == pytest.approx(10.17, rel=1e-2)
    # band=0 keeps the raw rates
    raw = sched.deal_weights()
    assert raw == pytest.approx((5.0, 10.0, 11.0, 9.5), rel=1e-3)
    # the clustered weights deal the three equal columns equal shares
    assert column_shares(64, 4, w) == (9, 19, 18, 18)


def test_open_stream_wires_telemetry_through():
    app = make_app()
    tel = StreamTelemetry()
    sched = ColumnScheduler(telemetry=tel)
    sig, _ = synthetic_respiration(1, 512 * 6, seed=23)
    cfg = StreamConfig(window=512, hop=256, batch_windows=4)
    stream = sched.open_stream(app, cfg, stream_id="sensor-a")
    stream.process(sig[0])
    col = sched.column_of("sensor-a")
    assert tel.column_stats(col + 1)[col].windows == \
        frame_count(512 * 6, 512, 256)
    sched.release("sensor-a")
    assert tel.column_load(col) == 0.0      # detached on release


def test_retire_trigger_feeds_on_upload_retires():
    """The scheduler's retire-count trigger consumes the per-upload loop's
    retires exactly like per-batch retires — no host poller anywhere."""
    clock = VirtualClock()
    tel = StreamTelemetry(clock=clock)
    sched = ColumnScheduler(devices=[None], telemetry=tel,
                            rebalance_every=10 ** 9)
    device = sched.admit("up-stream")
    assert device is None                   # the placeholder column
    app = make_app()
    cfg = StreamConfig(window=512, hop=256, batch_windows=4)
    sig, _ = synthetic_respiration(1, 256 * 24 + 512, seed=5)
    stream = BiosignalStream(app, cfg, device=device, telemetry=tel,
                             stream_id="up-stream")
    stream.process(sig[0])
    n = frame_count(sig.shape[1], 512, 256)
    assert sched._retired_since_rebalance == n


def test_retire_trigger_rebalances_and_queues_moves():
    clock = VirtualClock()
    tel = StreamTelemetry(clock=clock)
    sched = ColumnScheduler(devices=["d0", "d1"], telemetry=tel,
                            rebalance_every=60)
    for sid in ("s1", "s2", "s3"):
        sched.admit(sid)                    # round-robin: s1,s3 -> col0
    assert sched.column_of("s3") == 0
    # warm the rates: s1 and s3 are heavy (10 windows per tick), s2 light
    for _ in range(6):
        clock.advance(1.0)
        tel.record_retire("s1", 10)
        tel.record_retire("s3", 10)
        tel.record_retire("s2", 1)
    # the trigger fired mid-loop (>= 60 windows retired) and queued the
    # work-stealing move off the overloaded column 0
    moves = sched.pop_moves()
    assert moves, "retire-count trigger never rebalanced"
    assert set(moves.values()) <= {"d0", "d1"}
    assert sched.pop_moves() == {}          # drained
    # a foreign stream sharing the telemetry never counts
    before = sched._retired_since_rebalance
    tel.record_retire("not-mine", 500)
    assert sched._retired_since_rebalance == before


# ----------------------------------------------------- trajectory + gate

def _bench_json(path, rows):
    path.write_text(json.dumps(
        {"rows": [{"name": n, "us_per_call": us, "derived": ""}
                  for n, us in rows], "failed": 0}))


def test_trajectory_accumulates_replaces_and_survives_corruption(tmp_path):
    from benchmarks.trajectory import _load_trajectory, append

    traj = tmp_path / "BENCH_trajectory.json"
    bench = tmp_path / "BENCH_smoke.json"
    _bench_json(bench, [("table5/stream_fused", 100.0)])
    auto = tmp_path / "BENCH_autotune.json"
    auto.write_text(json.dumps(
        {"autotune_winners": [],
         "pinned": {"table5/stream_fused": {"us": 100.0, "ratio": 1.4,
                                            "spread": 0.02, "reps": 5}}}))
    assert append(str(traj), str(bench), commit="aaa", branch="main",
                  autotune_path=str(auto), timestamp=1.0) == 1
    _bench_json(bench, [("table5/stream_fused", 90.0)])
    assert append(str(traj), str(bench), commit="bbb", branch="main",
                  timestamp=2.0) == 2
    entries = _load_trajectory(str(traj))
    assert [e["commit"] for e in entries] == ["aaa", "bbb"]
    assert entries[0]["pinned"]["table5/stream_fused"]["ratio"] == 1.4
    assert entries[1]["rows"]["table5/stream_fused"] == 90.0
    # re-running a commit replaces, not duplicates
    _bench_json(bench, [("table5/stream_fused", 95.0)])
    assert append(str(traj), str(bench), commit="bbb", branch="main",
                  timestamp=3.0) == 2
    entries = _load_trajectory(str(traj))
    assert entries[-1]["rows"]["table5/stream_fused"] == 95.0
    # max-entries cap drops the oldest
    assert append(str(traj), str(bench), commit="ccc", branch="main",
                  max_entries=2, timestamp=4.0) == 2
    assert [e["commit"] for e in _load_trajectory(str(traj))] == \
        ["bbb", "ccc"]
    # corrupt restore re-seeds instead of crashing
    traj.write_text("{not json")
    assert append(str(traj), str(bench), commit="ddd", branch="main",
                  timestamp=5.0) == 1


def _run_diff(tmp_path, *args):
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.diff_autotune", *args],
        capture_output=True, text=True, cwd=ROOT, timeout=120)


def test_diff_autotune_missing_baseline_is_loud(tmp_path):
    """A vanished/broken baseline artifact must not look like a green
    gate: distinct exit code by default, explicit SKIPPED warning with
    --missing-baseline-ok (the first-run case)."""
    new = tmp_path / "new.json"
    new.write_text(json.dumps({"autotune_winners": [], "pinned": {}}))
    missing = str(tmp_path / "nope.json")
    r = _run_diff(tmp_path, missing, str(new), "--gate")
    assert r.returncode == 3, r.stdout + r.stderr
    assert "gate SKIPPED" in r.stdout
    r = _run_diff(tmp_path, missing, str(new), "--gate",
                  "--missing-baseline-ok")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "gate SKIPPED" in r.stdout and "no baseline" in r.stdout
    # unreadable (corrupt) baseline takes the same explicit path
    bad = tmp_path / "bad.json"
    bad.write_text("{corrupt")
    r = _run_diff(tmp_path, str(bad), str(new), "--gate")
    assert r.returncode == 3
    assert "gate SKIPPED" in r.stdout
    # a broken CURRENT artifact is a bench bug -> hard failure
    r = _run_diff(tmp_path, str(bad), str(bad), "--gate",
                  "--missing-baseline-ok")
    assert r.returncode == 1
    # intact baseline still gates regressions
    old = tmp_path / "old.json"
    old.write_text(json.dumps(
        {"autotune_winners": [],
         "pinned": {"p": {"us": 100.0, "ratio": 2.0, "spread": 0.01}}}))
    new.write_text(json.dumps(
        {"autotune_winners": [],
         "pinned": {"p": {"us": 100.0, "ratio": 1.0, "spread": 0.01}}}))
    r = _run_diff(tmp_path, str(old), str(new), "--gate",
                  "--missing-baseline-ok")
    assert r.returncode == 1
    assert "REGRESSED" in r.stdout


def test_diff_autotune_retired_record_is_reported_not_failed():
    """A pinned record whose code was deleted on purpose (``RETIRED``) is
    reported when the baseline still holds it; any other record that
    disappears keeps failing the gate."""
    from benchmarks.diff_autotune import RETIRED, gate_pinned

    kept = {"us": 100.0, "ratio": 1.4, "spread": 0.01}
    retired = next(iter(RETIRED))
    report, failures = gate_pinned({"p": kept, retired: kept}, {"p": kept})
    assert failures == []
    assert any(retired in line and "retired" in line for line in report)
    report, failures = gate_pinned({"p": kept, "gone": kept}, {"p": kept})
    assert len(failures) == 1 and "gone" in failures[0]
    assert "DISAPPEARED" in failures[0]

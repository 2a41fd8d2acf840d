"""The trace reduction: busy time as a union of intervals, kernel time by
name, device idle time inside host spans, idle gaps attributed to what the
host was doing; and the reader on a trace recorded here (on the CPU, which
has no device planes, so only its host spans are read)."""
import pytest

from bench import trace as T

MS = 1_000_000      # ns


def _events():
    """Window 0-100 ms on two chips. Chip 0 runs the kernel twice (10-20,
    50-60 ms) with a copy overlapping the first run (15-25 ms); chip 1
    runs it once (10-40 ms). The host is in `process` 5-30 and 45-65 ms,
    in `to_host` 30-35 ms and waits 70-95 ms."""
    return {
        "devices": {
            "0": [["graph_stream_kernel.1", 10 * MS, 20 * MS],
                  ["copy.3", 15 * MS, 25 * MS],
                  ["graph_stream_kernel.1", 50 * MS, 60 * MS],
                  ["graph_stream_kernel.1", 120 * MS, 130 * MS]],
            "1": [["graph_stream_kernel.2", 10 * MS, 40 * MS]]},
        "spans": [["bench.window", 0, 100 * MS],
                  ["bench.process", 5 * MS, 30 * MS],
                  ["bench.to_host", 30 * MS, 35 * MS],
                  ["bench.process", 45 * MS, 65 * MS],
                  ["bench.wait", 70 * MS, 95 * MS]]}


def test_busy_is_the_union_inside_the_window():
    t = T.Trace(_events())
    assert t.window_s == pytest.approx(0.1)
    assert t.busy_s(0) == pytest.approx(0.025)     # 10-25 and 50-60
    assert t.busy_s(1) == pytest.approx(0.030)


def test_kernel_time_by_name():
    t = T.Trace(_events())
    assert t.kernel_s(0, ("graph_stream_kernel",)) == pytest.approx(0.020)
    assert len(t.kernel_events(0, ("graph_stream_kernel",))) == 2
    assert t.kernel_s(0, ("no_such_kernel",)) == 0.0
    ops = dict(t.device_ops())
    assert ops["graph_stream_kernel.1"] == pytest.approx(0.020)
    assert ops["graph_stream_kernel.2"] == pytest.approx(0.030)


def test_idle_inside_spans():
    t = T.Trace(_events())
    # process spans: 5-30 (busy 10-25) and 45-65 (busy 50-60) on chip 0
    assert t.idle_inside(0, "bench.process") == pytest.approx(0.020)
    # chip 1: busy 10-30 of 5-30, nothing of 45-65
    assert t.idle_inside(1, "bench.process") == pytest.approx(0.025)


def test_idle_gaps_are_attributed_to_host_spans():
    gaps = dict(T.Trace(_events()).idle_gaps())
    # chip 0 idle: 0-10, 25-50, 60-100; chip 1 idle: 0-10, 40-100
    assert gaps["bench.wait"] == pytest.approx(0.050)
    # chip 0: 5-10, 25-30, 45-50, 60-65; chip 1: 5-10, 45-65
    assert gaps["bench.process"] == pytest.approx(0.020 + 0.025)
    assert gaps["bench.to_host"] == pytest.approx(0.005)
    assert sum(gaps.values()) == pytest.approx(0.2 - 0.055)


def test_union_overlap_and_gaps():
    m = T.union([[5, 7], [1, 3], [2, 4], [9, 9]])
    assert m == [[1, 4], [5, 7]]
    assert T.overlap(m, 0, 6) == 4
    assert T.gaps(m, 0, 10) == [(0, 1), (4, 5), (7, 10)]


def test_read_xplane_keeps_the_harness_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones((128,))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.process"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = T.read_xplane(tmp_path)
    names = [n for n, _, _ in ev["spans"]]
    assert names.count("bench.process") == 3 and "bench.window" in names
    t = T.Trace(ev)
    assert len(t.spans_named("bench.process")) == 3
    assert all(t.start <= s <= e <= t.end
               for s, e in t.spans_named("bench.process"))

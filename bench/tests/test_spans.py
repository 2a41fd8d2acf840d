"""The program's spans (`bench/spans.py`): each part of the stream loop's
split on recorded events; the harness's reduction unchanged by them; the
reader on a trace recorded here; and `bench/split.py` rehearsed on the
CPU, where the pad share is a count."""
import json

import pytest

from bench import run, split
from bench import trace as T
from bench.registry import BENCH, ROOT, Registry
from bench.spans import ProgramSpans, read_program

MS = 1_000_000      # ns


def _events():
    """Window 0-100 ms, one chip busy 10-16 and 20-25 ms. The host is in
    `bench.process` 5-45 ms."""
    return {"devices": {"0": [["graph_stream_kernel.biosignal.1",
                               10 * MS, 16 * MS],
                              ["graph_stream_kernel.biosignal.1",
                               20 * MS, 25 * MS]]},
            "spans": [["bench.window", 0, 100 * MS],
                      ["bench.admit", 1 * MS, 4 * MS],
                      ["bench.process", 5 * MS, 45 * MS],
                      ["bench.to_host", 45 * MS, 47 * MS]]}


def _program():
    """One upload of 100 frames in two 64-slot dispatches inside
    `serve.process` 5-45 ms, its stream admitted 1-4 ms; an LM admission
    at 50-60 ms, and a process span after the window."""
    def ev(name, s, e, **st):
        return ["serve." + name, s * MS, e * MS, st]
    return [ev("admit", 1, 4, ticket=0, cls="stream", stream="r1"),
            ev("process", 5, 45, stream="r1", samples=52736),
            ev("upload", 5, 7, stream="r1"),
            ev("prepare", 7, 9, dispatch=0),
            ev("launch", 9, 10, dispatch=0, valid=64, slots=64),
            ev("prepare", 10, 12, dispatch=1),
            ev("launch", 12, 13, dispatch=1, valid=36, slots=64),
            ev("retire", 13, 20, dispatch=0, valid=64),
            ev("wait", 14, 18, dispatch=0),
            ev("retire", 20, 30, dispatch=1, valid=36),
            ev("wait", 21, 29, dispatch=1),
            ev("concat", 30, 33, stream="r1"),
            ev("admit", 50, 60, ticket=1, cls="lm"),
            ev("process", 120, 130, stream="r2", samples=2048)]


@pytest.fixture
def split_ms():
    return ProgramSpans(T.Trace(_events()), _program()).split()


@pytest.mark.parametrize("part, want", [
    # idle inside process 5-45: 40 - 6 - 5 = 29 ms over two launches
    ("serve_gap_ms", 14.5),
    ("prepare_ms", 2.0),
    ("launch_ms", 1.0),
    # retires of 7 and 10 ms less their waits of 4 and 8 ms
    ("retire_ms", 2.5),
    ("wait_ms", 6.0),
    ("process_ms", 20.0),
    # upload 2 ms + concat 3 ms, one process in the window
    ("upload_ms", 5.0),
    # the stream admission only
    ("admit_ms", 3.0),
    # 100 real frames of 128 slots
    ("pad_share", 21.875),
])
def test_each_part_of_the_split(split_ms, part, want):
    assert split_ms[part] == pytest.approx(want)


def test_spans_outside_the_window_do_not_count():
    p = ProgramSpans(T.Trace(_events()), _program())
    assert [s[2]["stream"] for s in p.named("process")] == ["r1"]
    assert p.self_s("retire", "wait") == pytest.approx(0.005)


def test_a_trace_without_program_spans_reads_nothing():
    got = ProgramSpans(T.Trace(_events()), []).split()
    assert got == dict.fromkeys(got)


def test_program_spans_leave_the_harness_reduction_as_it_is():
    plain = T.Trace(_events())
    both = T.Trace({**_events(), "program": _program()})
    kernel = ("graph_stream_kernel",)
    assert both.spans == plain.spans
    assert both.idle_gaps() == plain.idle_gaps()
    assert both.idle_inside(0, "bench.process") == \
        plain.idle_inside(0, "bench.process")
    assert both.kernel_s(0, kernel) == plain.kernel_s(0, kernel)
    assert both.device_ops() == plain.device_ops()


def test_read_program_keeps_the_program_spans_apart(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.cos(x) + 1)
    x = jnp.ones((128,))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.process"):
            with jax.profiler.TraceAnnotation("serve.launch", dispatch=1,
                                              valid=3, slots=4):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    assert all(not n.startswith("serve.")
               for n, _, _ in T.read_xplane(tmp_path)["spans"])
    (launch,) = read_program(tmp_path)
    assert launch[0] == "serve.launch"
    assert launch[3] == {"dispatch": 1, "valid": 3, "slots": 4}
    assert launch[1] < launch[2]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The archive cell with recordings of 200 frames: four 64-frame
    dispatches, the last holding 8."""
    d = tmp_path_factory.mktemp("tiny")
    (d / "mixes").mkdir()
    (d / "mixes" / "archive-backlog.json").write_text(json.dumps(
        {"arrivals": "backlog", "recording_s": 1624, "pool": 2}))
    return Registry(json.loads((ROOT / "BENCHMARK.json").read_text()),
                    (d, BENCH))


def test_split_rehearsal_counts_the_pad_share(tiny, tmp_path, monkeypatch):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    cell = run.load_cell(tiny, "mbio.archive", jax.devices()[:1])
    row = split.traced(tiny, cell, 2 ** 31 + 7, 1.0)
    parts = row["split"]
    assert parts["pad_share"] == pytest.approx(100.0 * (1 - 200 / 256))
    assert parts["admit_ms"] > 0 and parts["launch_ms"] > 0
    # the CPU trace has no device plane: nothing to be idle
    assert parts["serve_gap_ms"] is None and row["kernel_ops"] == []
    assert row["frames_per_s"] > 0

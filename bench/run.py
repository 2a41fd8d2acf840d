#!/usr/bin/env python3
"""Chip benchmark of the stage-graph stream path: one cell, one run.

    python3 bench/run.py --workload mbio.archive --seed 7 --seconds 10 --trace 0

A cell (an entry of BENCHMARK.json's ``workloads``) is a deployment from
``bench/configs/`` under a traffic mix from ``bench/mixes/``. The run:

1. refuses to go on unless JAX's first device is a TPU and there are as many
   chips as the cell asks for;
2. set-up: turns on the compile cache, draws the data and the
   application's numbers from ``--seed``, opens the streams through
   ``ServeFrontend(scheduler=ColumnScheduler(...)).submit(StreamOpen(...))``
   and runs every upload length the mix uses once, so that nothing compiles
   in the window;
3. drives the mix for ``--seconds`` through ``BiosignalStream.process``,
   each upload's signal starting in host memory and its outputs brought
   back to the host;
4. reads the peak device memory, frees the program's state, and checks a
   seeded sample of the finished uploads against the plain reference under
   ``bench/ref/``;
5. prints the cell's end-to-end metrics (``--trace 0``) or, from a profiler
   trace of the window, its per-layer metrics (``--trace 1``), and as its
   last stdout line one JSON object. The numbers compared for ``correct``
   end standard error and the JSON line, each beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import traffic as traffic_mod  # noqa: E402
from bench.registry import BENCH, Registry  # noqa: E402

DRAIN_S = 60.0          # an open-loop upload not done this long after the
#                         window closes never came
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def process_age_s() -> float:
    """Seconds since this process started (from /proc), so that set-up
    counts the interpreter's start too; 0 where /proc is not there."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


T_PROCESS = T_START - process_age_s()


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Record:
    """One upload: host-clock times (perf_counter seconds) and outputs."""
    upload: traffic_mod.Upload
    due: float
    start: float | None = None
    end: float | None = None
    frames: int = 0
    outputs: dict | None = None
    error: str | None = None


class CompileCounter:
    """Counts backend compilations (and persistent-cache loads) as JAX
    reports them."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            self.n += 1


def compile_cache() -> str:
    """The program's persistent compile cache (`.jax_cache/` in the
    checkout, or ``JAX_COMPILATION_CACHE_DIR``), keeping every program
    however fast it compiled, so that a run after the first compiles
    nothing."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def devices_for(chips: int, require_chip: bool) -> list:
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


class Served:
    """The program under test, entered as a client would: the stream front
    end over a column scheduler of the cell's chips."""

    def __init__(self, cfg: dict, app, devices):
        from repro.serve.engine import ColumnScheduler
        from repro.serve.frontend import ServeFrontend
        from repro.serve.stream import StreamConfig

        st = cfg["stream"]
        self.app = app
        self.scfg = StreamConfig(window=st["window"], hop=st["hop"],
                                 batch_windows=st["batch_windows"],
                                 outputs=tuple(st["outputs"]),
                                 graph=cfg["graph"])
        self.sched = ColumnScheduler(devices)
        self.front = ServeFrontend(scheduler=self.sched)

    def open(self, stream_ids) -> list:
        """One stream per id, admitted together (one pump of the front
        end for the lot)."""
        from repro.serve.frontend import StreamOpen

        tickets = [self.front.submit(StreamOpen(stream_id=s, app=self.app,
                                                cfg=self.scfg))
                   for s in stream_ids]
        self.front.run()
        return [t.result() for t in tickets]

    def close(self, stream_id) -> None:
        self.sched.release(stream_id)


def to_host(out: dict) -> dict:
    return {k: np.asarray(v) for k, v in out.items()}


def serve_one(stream, rec: Record, signal) -> None:
    """One upload through the timed path, outputs on the host."""
    rec.start = time.perf_counter()
    try:
        with span("bench.process"):
            out = stream.process(signal)
        with span("bench.to_host"):
            rec.outputs = to_host(out)
        rec.frames = int(next(iter(rec.outputs.values())).shape[0])
    except Exception:                           # a failed upload is counted
        rec.error = traceback.format_exc()
        log(f"upload failed:\n{rec.error}")
    rec.end = time.perf_counter()


@dataclasses.dataclass
class Cell:
    """Everything a run of one cell needs, found by name."""
    cfg: dict
    mix: dict
    devices: list
    app_mod: object
    ref_mod: object
    signal: object
    arrivals: object


def load_cell(reg: Registry, workload: str, devices) -> Cell:
    w = reg.workload(workload)
    cfg = reg.config(w["config"])
    mix = reg.mix(w["traffic"])
    return Cell(cfg, mix, devices,
                reg.module("apps", cfg["graph"]),
                reg.module("ref", cfg["graph"]),
                reg.module("signals", cfg["signal"]).make,
                reg.module("arrivals", mix["arrivals"]))


@dataclasses.dataclass
class Prepared:
    traffic: traffic_mod.Traffic
    params: dict
    served: Served
    streams: dict           # tenant -> stream (open loop)


def prepare(cell: Cell, seed: int, seconds: float, timings: dict) -> Prepared:
    """Data and numbers from the seed, streams opened, every upload length
    run once."""
    t = time.perf_counter()
    tr = traffic_mod.build(cell.mix, cell.cfg, seed, seconds, cell.signal,
                            cell.arrivals)
    params = cell.app_mod.params(cell.cfg, np.random.default_rng([seed, 1]))
    timings["data_s"] = time.perf_counter() - t
    t = time.perf_counter()
    served = Served(cell.cfg, cell.app_mod.build(cell.cfg, params),
                    cell.devices)
    streams = {}
    if tr.loop == "open_loop":
        streams = dict(enumerate(served.open(
            [f"tenant-{k}" for k in range(tr.tenants)])))
    timings["open_s"] = time.perf_counter() - t
    t = time.perf_counter()
    for n in tr.lengths:
        sid = f"warm-{n}"
        stream = streams[0] if streams else served.open([sid])[0]
        to_host(stream.process(tr.pool[0][:n]))
        if not streams:
            served.close(sid)
    timings["warm_s"] = time.perf_counter() - t
    return Prepared(tr, params, served, streams)


def drive(prep: Prepared, seconds: float) -> tuple[list, float, float, list]:
    """The measured window: (records, start, end, generator lateness)."""
    tr = prep.traffic
    records: list = []
    lateness: list = []
    t0 = time.perf_counter()
    with span("bench.window"):
        if tr.loop == "backlog":
            for i, up in enumerate(tr.backlog()):
                rec = Record(up, t0)
                records.append(rec)
                sid = f"recording-{i}"
                with span("bench.admit"):
                    stream = prep.served.open([sid])[0]
                serve_one(stream, rec, tr.samples(up))
                prep.served.close(sid)
                if rec.end - t0 >= seconds:
                    break
        else:
            for up in tr.uploads:
                rec = Record(up, t0 + up.due_s)
                records.append(rec)
                now = time.perf_counter()
                if now < rec.due:
                    with span("bench.wait"):
                        time.sleep(rec.due - now)
                    lateness.append(time.perf_counter() - rec.due)
                if time.perf_counter() > t0 + seconds + DRAIN_S:
                    rec.error = "not served within the drain limit"
                    continue
                serve_one(prep.streams[up.tenant], rec, tr.samples(up))
    t1 = time.perf_counter()
    return records, t0, t1, lateness


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def check(cell: Cell, prep_params: dict, traffic, records: list,
          seed: int) -> dict:
    """The numbers compared: a seeded sample of the finished uploads, the
    longest first, up to the configuration's ``check_frames``, each
    against the plain reference."""
    st = cell.cfg["stream"]
    done = [r for r in records if r.outputs is not None]
    rng = np.random.default_rng([seed, 2])
    order = [int(i) for i in rng.permutation(len(done))]
    if done:
        longest = max(range(len(done)), key=lambda i: done[i].frames)
        order.remove(longest)
        order.insert(0, longest)
    budget = cell.cfg["guarantees"]["check_frames"]
    parts, frames = [], 0
    for i in order:
        if frames >= budget:
            break
        rec = done[i]
        want = cell.ref_mod.reference(
            prep_params, traffic.samples(rec.upload), window=st["window"],
            hop=st["hop"], fft_size=cell.cfg["app"]["fft_size"])
        parts.append(cell.ref_mod.compare(rec.outputs, want, prep_params))
        frames += parts[-1]["frames"]
    numbers = cell.ref_mod.merge(parts)
    numbers["uploads_checked"] = len(parts)
    return numbers


def judge(cell: Cell, numbers: dict, failed: int) -> tuple[bool, dict]:
    """Each number compared beside its limit; correct when every number is
    within its limit, some upload was checked, and none failed."""
    limits = cell.cfg["guarantees"]["limits"]
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    checks["failed_uploads"] = {"value": failed, "limit": 0}
    ok = numbers.get("uploads_checked", 0) > 0 and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    return ok, checks


@dataclasses.dataclass
class Context:
    """What a metric reader reads (`bench/metrics/<name>.py:read`)."""
    cfg: dict
    records: list
    window_s: float
    setup_s: float
    chips: int
    work: object
    peaks: dict
    kernel: tuple                   # names the kernel's operations carry
    trace: object = None            # bench.trace.Trace in a traced run


def json_number(v):
    return v if math.isfinite(v) else str(v)


def main(argv=None, *, reg: Registry | None = None,
         require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    reg = reg or Registry.from_root(ROOT)
    chips = reg.workload(args.workload)["chips"]

    timings: dict = {}
    try:
        devices = devices_for(chips, require_chip)
    except NoChip as e:
        log(str(e))
        return 2
    import jax

    log(f"compile cache: {compile_cache()}")
    compiles = CompileCounter()
    timings["import_s"] = time.perf_counter() - T_PROCESS
    cell = load_cell(reg, args.workload, devices)
    with open(BENCH / "peaks.json") as f:
        peaks = json.load(f)
    kind = devices[0].device_kind
    if require_chip and kind not in peaks:
        log(f"no peaks for device kind {kind!r} in bench/peaks.json")
        return 2
    prep = prepare(cell, args.seed, args.seconds, timings)
    setup_compiles = compiles.n

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t_window = time.perf_counter()
    setup_s = t_window - T_PROCESS
    n0 = compiles.n
    records, t0, t1, lateness = drive(prep, args.seconds)
    in_window = compiles.n - n0
    if trace_dir:
        jax.profiler.stop_trace()
    peak = memory_peak(devices)

    log(f"set-up {setup_s:.3f} s: import {timings['import_s']:.3f}, data "
        f"{timings['data_s']:.3f}, open {timings['open_s']:.3f}, warm-up and "
        f"compile {timings['warm_s']:.3f}; {setup_compiles} compilations")
    log(f"in-window compilations: {in_window}")
    if prep.traffic.loop == "open_loop":
        late = sorted(lateness)
        log(f"generator lateness over {len(late)} idle arrivals: p50 "
            f"{1e3 * late[len(late) // 2] if late else 0:.3f} ms, max "
            f"{1e3 * late[-1] if late else 0:.3f} ms")

    failed = sum(1 for r in records if r.error is not None or r.end is None)
    ctx = Context(cell.cfg, records, t1 - t0, setup_s, chips,
                  reg.module("work", cell.cfg["graph"]),
                  peaks.get(kind, {}), cell.app_mod.KERNEL)
    breakdown = None
    if trace_dir:
        from bench.trace import Trace, read_xplane

        t = time.perf_counter()
        ctx.trace = Trace(read_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        breakdown = {"device_ops": ctx.trace.device_ops(),
                     "idle_gaps": ctx.trace.idle_gaps()}
        log(f"trace read in {time.perf_counter() - t:.3f} s")

    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    on_chip = devices[0].platform == "tpu"
    if not on_chip:
        log("no TPU: a rehearsal, so no metric is reported")
    for m in reg.metrics(args.workload, section) if on_chip else ():
        value = reg.module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": json_number(float(value)),
                                  "unit": m["unit"]}

    # the program's state goes before the reference runs
    params, tr = prep.params, prep.traffic
    del prep
    gc.collect()
    t = time.perf_counter()
    numbers = check(cell, params, tr, records, args.seed)
    log(f"reference check of {numbers['uploads_checked']} uploads in "
        f"{time.perf_counter() - t:.3f} s")
    correct, checks = judge(cell, numbers, failed)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    if ctx.trace is not None and ctx.trace.chips:
        device["busy_s"] = float(np.mean([ctx.trace.busy_s(c)
                                          for c in ctx.trace.chips]))
        device["window_s"] = ctx.trace.window_s
    result = {"correct": bool(correct), "attempted": len(records),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": json_number(float(c["value"])),
                            "limit": c["limit"]} for k, c in checks.items()}
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

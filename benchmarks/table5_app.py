"""Table 5 — MBioTracker biosignal application (paper §5.2).

Per-step cycles/energy from the simulator vs the paper's CPU / CPU+FFT-ACCEL
/ CPU+VWR2A columns. The CPU and accelerator columns are the paper's
measurements; `savings` compares our simulated VWR2A against them.

Also times the fused single-`pallas_call` application kernel against the
staged per-stage execution (the software analogue of the paper's
whole-application SPM residency vs kernel-at-a-time offload); the CI bench
smoke gates on fused <= staged via ``run.py --check-fused``.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.table2_fft import F_HZ

PAPER_CPU = {"preprocessing": (49760, 0.74), "delineation": (46268, 0.74),
             "feat_extraction": (70639, 1.1), "total": (166667, 2.6)}
PAPER_VWR2A = {"preprocessing": (3763, 0.26), "delineation": (2723, 0.13),
               "feat_extraction": (8627, 0.47), "total": (15113, 0.86)}


def _paired_times(fns: list, reps: int = 15) -> list[list[float]]:
    """Paired per-rep wall times in us: the candidates are timed
    ALTERNATELY inside one loop so machine noise hits all of them equally
    (an unpaired comparison at the ~3%-level is a coin flip). The full
    rep lists feed the pinned-shape regression gate, whose tolerance is
    the run's own rep spread."""
    import jax

    for fn in fns:
        jax.block_until_ready(fn())          # compile + warm
    times = [[] for _ in fns]
    for _ in range(reps):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            times[i].append((time.perf_counter() - t0) * 1e6)
    return times


def _paired_best(fns: list, reps: int = 15) -> list[float]:
    return [min(ts) for ts in _paired_times(fns, reps)]


def _pipeline_rows():
    """Fused application kernel vs the staged executions (paper Table 5's
    execution models: whole-app residency vs kernel-at-a-time offload)."""
    from repro.core.biosignal import make_app, synthetic_respiration
    from repro.kernels.pipeline.ops import app_pipeline
    from repro.kernels.pipeline.ref import staged_kernel_fns, staged_stage_fns

    app = make_app()
    sig, _ = synthetic_respiration(32, 2048, seed=0)
    from repro.core import autotune

    staged = staged_kernel_fns(app.fir_taps, app.svm_w, app.svm_b,
                               fft_size=app.fft_size)
    fir_fn, feat_fn, svm_fn = staged_stage_fns(
        app.fir_taps, app.svm_w, app.svm_b, fft_size=app.fft_size)
    t_fused, t_staged, t_jnp = _paired_times([
        lambda: app_pipeline(app, sig),
        lambda: staged(sig),
        lambda: svm_fn(feat_fn(fir_fn(sig))),
    ])
    us_fused, us_staged, us_jnp = min(t_fused), min(t_staged), min(t_jnp)
    autotune.record_pinned("table5/pipeline_fused", t_fused,
                           baseline_us=t_staged)
    return [
        ("table5/pipeline_staged", us_staged,
         "kernel-at-a-time: 4 launches/batch (FIR kernel; delineation; "
         "rFFT kernel; SVM) with per-stage HBM round trips"),
        ("table5/pipeline_staged_jnp", us_jnp,
         "3 jnp-only jit calls/batch (no per-kernel staging); info only"),
        ("table5/pipeline_fused", us_fused,
         f"ONE pallas_call per batch;speedup_vs_staged="
         f"{us_staged / us_fused:.2f}x"),
    ]


def _stream_rows():
    """Raw-signal single-residency streaming vs host-framed feeds at the
    default overlap (hop = window/4, every sample duplicated 4x by host
    framing). Candidates are timed PAIRED (alternating min-of-reps); the CI
    bench smoke gates on stream-fused >= 1.25x framed-fused via
    ``run.py --check-stream``."""
    from repro.core.biosignal import make_app, synthetic_respiration
    from repro.kernels.pipeline.ops import (app_pipeline,
                                            app_pipeline_stream)
    from repro.kernels.pipeline.ref import staged_kernel_fns
    from repro.serve.stream import frame_signal

    app = make_app()
    window, hop, n_frames = 2048, 512, 32
    sig, _ = synthetic_respiration(1, (n_frames - 1) * hop + window, seed=1)
    raw = sig[0]
    cls_outputs = ("features", "margin", "class")   # elide filtered write
    staged = staged_kernel_fns(app.fir_taps, app.svm_w, app.svm_b,
                               fft_size=app.fft_size)
    # populate the autotune cache (these warmup calls are what lands in
    # BENCH_autotune.json), but GATE on pinned whole-batch blocks: the
    # near-tied candidates make autotune's pick a coin flip under CI load,
    # and a flapping gate is worse than a fixed one
    app_pipeline_stream(app, raw, window=window, hop=hop,
                        outputs=cls_outputs, autotune=True)
    app_pipeline(app, frame_signal(raw, window, hop), autotune=True)
    t_stream, t_framed, t_staged = _paired_times([
        lambda: app_pipeline_stream(app, raw, window=window, hop=hop,
                                    outputs=cls_outputs,
                                    block_frames=n_frames),
        lambda: app_pipeline(app, frame_signal(raw, window, hop),
                             block_rows=n_frames),
        lambda: staged(frame_signal(raw, window, hop)),
    ], reps=25)
    us_stream, us_framed, us_staged = (min(t_stream), min(t_framed),
                                       min(t_staged))
    from repro.core import autotune

    autotune.record_pinned("table5/stream_fused", t_stream,
                           baseline_us=t_framed)
    return [
        ("table5/stream_fused", us_stream,
         f"raw {raw.shape[0]}-sample feed, frames built in-kernel "
         f"(window={window},hop={hop}), outputs=features+margin+class;"
         f"speedup_vs_framed={us_framed / us_stream:.2f}x"),
        ("table5/stream_framed_fused", us_framed,
         f"host frame gather ({window // hop}x HBM duplication) + fused "
         f"kernel, all outputs"),
        ("table5/stream_framed_staged", us_staged,
         "host frame gather + kernel-at-a-time staged execution"),
    ]


def _asr_rows():
    """Streaming ASR feature front-end — the SECOND workload on the
    stage-graph substrate: the fused ``"asr"`` graph (ONE `pallas_call`,
    in-kernel (window, hop) framing, pre-emphasis FIR -> Hann -> packed
    rFFT power -> log-mel matmul) vs the staged 4-launch reference
    (`kernels/pipeline/asr.py:asr_staged`: host frame gather, FIR
    kernel, jnp Hann, rFFT kernel, jnp mel/log — per-stage HBM round
    trips). Numerically equal to f32 tolerance (`tests/test_asr.py`);
    timed paired; the CI bench smoke gates fused >= 1.2x staged via
    ``run.py --check-asr``."""
    from repro.kernels.pipeline.asr import asr_staged, make_asr_frontend
    from repro.kernels.pipeline.ops import graph_pipeline_stream

    app = make_asr_frontend()
    window, hop, n_frames = 512, 160, 64
    rng = np.random.default_rng(9)
    raw = rng.standard_normal(
        (n_frames - 1) * hop + window).astype(np.float32)
    t_fused, t_staged = _paired_times([
        lambda: graph_pipeline_stream("asr", app, raw, window=window,
                                      hop=hop, outputs=("logmel",),
                                      block_frames=n_frames),
        lambda: asr_staged(app, raw, window=window, hop=hop),
    ], reps=25)
    us_fused, us_staged = min(t_fused), min(t_staged)
    from repro.core import autotune

    autotune.record_pinned("table5/asr_fused", t_fused,
                           baseline_us=t_staged)
    return [
        ("table5/asr_staged", us_staged,
         f"4 launches/utterance (host frame gather; FIR kernel; Hann; "
         f"rFFT kernel; mel/log) with per-stage HBM round trips "
         f"(window={window},hop={hop},{n_frames} frames)"),
        ("table5/asr_fused", us_fused,
         f"ONE pallas_call/utterance, 'asr' stage graph with in-kernel "
         f"framing, outputs=logmel;"
         f"speedup_vs_staged={us_staged / us_fused:.2f}x"),
    ]


def _column_rows():
    """Column-scaling sweep for the STREAMING Pallas path — the mirror of
    `table2_fft._column_sweep` (which sweeps archsim's n_columns): a fixed
    64-frame raw feed dealt across D column replicas.

    The headline metric is the measured PER-COLUMN latency (one column's
    ~n/D-frame chunk through the fused kernel) — on a real D-device
    machine that IS the dispatch wall clock, and it is what the
    ``--check-columns`` monotonicity gate checks; host-fake devices
    sharing a 2-core CPU would make the aggregate wall a core-count
    artifact. When the process does have >= D devices the true shard_map
    wall is measured too and recorded in `derived` alongside.
    """
    import jax

    from repro.core.biosignal import make_app, synthetic_respiration
    from repro.kernels.pipeline.ops import app_pipeline_stream
    from repro.kernels.pipeline.shard import column_chunks
    from repro.serve.stream import column_mesh

    app = make_app()
    window, hop, n_frames = 2048, 512, 64
    sig, _ = synthetic_respiration(1, (n_frames - 1) * hop + window, seed=2)
    raw = sig[0]
    cls_outputs = ("features", "margin", "class")
    sweep = (1, 2, 4, 8)
    # one column's chunk per D (identical per-column shapes, frames n/D)
    col0 = {d: column_chunks(raw, window, hop, d).chunks[0] for d in sweep}
    fns = [
        # block pinned to the D=8 share so every D runs the same kernel
        # variant and the sweep isolates the work-per-column scaling
        (lambda d: lambda: app_pipeline_stream(
            app, col0[d], window=window, hop=hop, outputs=cls_outputs,
            block_frames=n_frames // max(sweep)))(d)
        for d in sweep
    ]
    times = _paired_times(fns, reps=10)
    rows, t1 = [], min(times[0])
    for d, ts in zip(sweep, times):
        t_col = min(ts)
        extra = ""
        mesh = column_mesh(d)
        if d > 1 and mesh is not None:
            fn = lambda: app_pipeline_stream(  # noqa: E731
                app, raw, window=window, hop=hop, outputs=cls_outputs,
                block_frames=n_frames // max(sweep), n_columns=d, mesh=mesh)
            jax.block_until_ready(fn())
            wall = min(_paired_times([fn], reps=5)[0])
            extra = f";shard_map_wall_us={wall:.1f}"
        rows.append((
            f"table5/stream_ncols{d}", t_col,
            f"per-column latency, {n_frames // d} of {n_frames} frames "
            f"(window={window},hop={hop});scaling={t1 / t_col:.2f}x;"
            f"model_windows_per_s={n_frames / t_col * 1e6:.0f}{extra}"))
    return rows


def _hetero_rows():
    """Heterogeneous-load column deal: static equal split vs the
    telemetry-driven dynamic deal when ONE of D=4 columns carries a 2x
    background load (a second tenant's 16-frame dispatch riding on column
    0 — the Versa-style column-shared-with-an-LM-engine scenario).

    Columns are timed serially (the serial-fallback path, measurable on
    one device, same convention as `_column_rows`); the modelled dispatch
    wall is max over columns of (column share time + its background
    time), which on a real D-device machine IS the wall clock. The
    dynamic deal replays measured per-column times through
    `StreamTelemetry` (injected clock), takes `ColumnScheduler.
    deal_weights(band=0.3)` — measured windows/s per column, deadband-
    clustered so jitter between the identical light columns cannot skew
    the deal — and re-deals via `column_chunks(weights=...)`; one
    refinement round (the periodic rebalance in miniature) converges the
    deal against the loaded column's ADDITIVE background cost. Both
    deals' columns are then timed alternately in ONE paired rep loop.
    Measured on CPU interpret: deterministic deal (7, 19, 19, 19) and
    1.27-1.39x over the static wall across trials; CI gates dynamic >=
    1.15x static throughput via ``run.py --check-hetero``.
    """
    import jax

    from repro.core.biosignal import make_app, synthetic_respiration
    from repro.kernels.pipeline.ops import app_pipeline_stream
    from repro.kernels.pipeline.shard import column_chunks
    from repro.serve.engine import ColumnScheduler
    from repro.serve.stream import StreamTelemetry

    app = make_app()
    # hop = window/2 keeps the kernel's frame-block floor at 1, and
    # block_frames=1 makes a column's cost LINEAR in its share — a deal
    # quantized to an 8-frame grid block would round a 9-frame share back
    # up to 16 frames of compute and erase the re-deal's win
    window, hop, n_frames, D = 2048, 1024, 64, 4
    cls_outputs = ("features", "margin", "class")
    block = 1                     # pinned: every share runs the same block
    sig, _ = synthetic_respiration(1, (n_frames - 1) * hop + window, seed=6)
    raw = sig[0]
    bg_sig, _ = synthetic_respiration(
        1, (n_frames // D - 1) * hop + window, seed=7)
    bg = bg_sig[0]                # the tenant's own 16-frame dispatch

    def col_fn(chunk):
        return lambda: app_pipeline_stream(
            app, chunk, window=window, hop=hop, outputs=cls_outputs,
            block_frames=block)

    def col_slices(shares, chunks):
        return [chunks[d][: s * hop + (window - hop)] if s else None
                for d, s in enumerate(shares)]

    def walls(per_col_times, bg_times):
        """Per-rep modelled dispatch wall: max over columns, background
        load added onto column 0. Used for the pinned record's rep
        spread; the headline wall takes each column's best-of-reps first
        (`wall_best`) — on a real D-device machine the columns run
        independently, so one host-jitter rep on one column must not
        inflate the modelled wall."""
        return [max(ts[i] + (bg_times[i] if d == 0 else 0.0)
                    for d, ts in enumerate(per_col_times))
                for i in range(len(bg_times))]

    def wall_best(per_col_times, bg_times):
        return max(min(ts) + (min(bg_times) if d == 0 else 0.0)
                   for d, ts in enumerate(per_col_times))

    deal_s = column_chunks(raw, window, hop, D)
    shares_s = deal_s.shares
    cols_s = col_slices(shares_s, deal_s.chunks)

    # CALIBRATION round: measure the static deal's per-column busy times
    # and replay them through the telemetry (virtual clock: retires of
    # share windows spaced by the MEDIAN-of-reps busy time — on a noisy
    # runner the median is the tightest per-column estimator: min still
    # jitters ~15% between identical columns, median ~8%), then ask the
    # scheduler for the deal weights with a 30% deadband (`band`) so
    # residual jitter between the three identical light columns cannot
    # deal them unequal shares (the 2x-loaded column sits ~100% away —
    # far outside the band)
    cal = _paired_times([col_fn(bg)] + [col_fn(c) for c in cols_s],
                        reps=13)
    bg_cal, col_cal = cal[0], cal[1:]

    def _median(ts):
        return sorted(ts)[len(ts) // 2]

    now = [0.0]
    tel = StreamTelemetry(alpha=0.5, clock=lambda: now[0])
    vt = [0.0] * D
    busy = [_median(ts) + (_median(bg_cal) if d == 0 else 0.0)
            for d, ts in enumerate(col_cal)]
    for d in range(D):
        tel.attach(f"col{d}", d)
    for _ in range(3):
        for d in range(D):
            vt[d] += busy[d] * 1e-6
            now[0] = vt[d]
            tel.record_retire(f"col{d}", shares_s[d])
    sched = ColumnScheduler([jax.devices()[0]] * D, telemetry=tel)

    def redeal():
        weights = sched.deal_weights(band=0.3)
        deal_w = column_chunks(raw, window, hop, D, weights)
        return weights, deal_w.shares, col_slices(deal_w.shares,
                                                  deal_w.chunks)

    weights, shares_d, cols_d = redeal()
    # one REFINEMENT round — the periodic rebalance in miniature: measure
    # the first re-deal, feed the new retires into the same telemetry,
    # deal again. A single rate-proportional step under-shifts when the
    # background load is additive (the loaded column's cost is fixed +
    # share, not proportional); the closed loop converges on it.
    ref = _paired_times([col_fn(bg)] +
                        [col_fn(c) for c in cols_d if c is not None],
                        reps=9)
    ref_cols = iter(ref[1:])
    busy = [(next(ref_cols) if s else None) for s in shares_d]
    for _ in range(3):
        for d in range(D):
            if shares_d[d] == 0:
                continue
            vt[d] += (_median(busy[d]) +
                      (_median(ref[0]) if d == 0 else 0.0)) * 1e-6
            now[0] = vt[d]
            tel.record_retire(f"col{d}", shares_d[d])
    weights, shares_d, cols_d = redeal()
    cols_d = [c for c in cols_d if c is not None]

    # FINAL round: BOTH deals' columns timed alternately in ONE paired
    # rep loop (machine drift between two separate rounds was measurable
    # as a coin-flip headline; within-loop pairing hits both deals
    # equally), walls computed per rep from the same loop
    fns = [col_fn(bg)] + [col_fn(c) for c in cols_s] + \
        [col_fn(c) for c in cols_d]
    times = _paired_times(fns, reps=12)
    bg_t = times[0]
    per_col_s = times[1: 1 + D]
    dyn_iter = iter(times[1 + D:])
    per_col_d = [next(dyn_iter) if s else [0.0] * len(bg_t)
                 for s in shares_d]
    wall_s = walls(per_col_s, bg_t)
    wall_d = walls(per_col_d, bg_t)
    us_s = wall_best(per_col_s, bg_t)
    us_d = wall_best(per_col_d, bg_t)
    from repro.core import autotune

    autotune.record_pinned("table5/stream_hetero", wall_d,
                           baseline_us=wall_s)
    rates = ";".join(f"{w:.1f}" for w in weights)
    return [
        ("table5/stream_hetero_static", us_s,
         f"modelled dispatch wall, equal deal {tuple(shares_s)} with a "
         f"{n_frames // D}-frame background tenant on column 0;"
         f"windows_per_s={n_frames / us_s * 1e6:.0f}"),
        ("table5/stream_hetero_dynamic", us_d,
         f"telemetry-driven deal {tuple(shares_d)} (measured col rates "
         f"w/s: {rates});windows_per_s={n_frames / us_d * 1e6:.0f};"
         f"speedup_vs_static={us_s / us_d:.2f}x"),
    ]


def _depth_rows():
    """Streaming-runtime pipelining depth: depth=1 (the classic double
    buffer — consume batch k while k+1 is in flight) vs depth=2 (two
    batches in flight). Measured within noise on the CPU interpret path
    (±4%, winner flips across trials), so `StreamConfig.depth` defaults
    to the simpler 1; the rows keep the comparison honest across commits
    and will show if a real accelerator target changes the answer."""
    from repro.core.biosignal import make_app, synthetic_respiration
    from repro.serve.stream import BiosignalStream, StreamConfig

    app = make_app()
    window, hop = 2048, 512
    sig, _ = synthetic_respiration(1, 512 * 120 + window, seed=4)
    raw = sig[0]
    streams = {d: BiosignalStream(app, StreamConfig(
        window=window, hop=hop, batch_windows=8, depth=d,
        outputs=("features", "margin", "class"))) for d in (1, 2)}
    t1, t2 = _paired_times([lambda: streams[1].process(raw),
                            lambda: streams[2].process(raw)], reps=7)
    us1, us2 = min(t1), min(t2)
    win = "depth2" if us2 <= us1 else "depth1"
    return [
        ("table5/stream_depth1", us1,
         "runtime end-to-end, 1 batch in flight (classic double buffer)"),
        ("table5/stream_depth2", us2,
         f"runtime end-to-end, 2 batches in flight;speedup_vs_depth1="
         f"{us1 / us2:.2f}x;winner={win} (measured within noise on CPU; "
         f"StreamConfig.depth stays 1)"),
    ]


def _fault_rows():
    """Fault-tolerant serving: the cost of losing one of D=4 columns
    mid-run. Both runs go through `serve/fault.py:
    FaultTolerantColumnRunner`; the fault run kills column 0 at its
    second dispatch (`FaultInjector`), after which its unretired
    hop-aligned frame ranges requeue across the three survivors under
    the degraded deal (dead column zeroed). The modelled dispatch wall
    is max over per-column busy time — same convention as
    `_hetero_rows`: on a real D-device machine the columns run
    independently, so that max IS the wall clock. Outputs must be
    BIT-IDENTICAL to the fault-free run (the chaos invariant,
    `tests/test_chaos.py`); the CI bench smoke gates recovered wall <=
    1.5x fault-free AND bit-identity via ``run.py --check-fault``."""
    import jax
    import jax.numpy as jnp

    from repro.core.biosignal import make_app, synthetic_respiration
    from repro.serve.fault import FaultInjector, FaultTolerantColumnRunner
    from repro.serve.stream import StreamConfig

    app = make_app()
    # 64 frames over D=4: 16 per column, 4 dispatches of bw=4. Killing
    # column 0 at its 2nd dispatch loses 12 unretired frames -> 4 extra
    # frames (ONE extra dispatch, requeued runs coalesce) per survivor:
    # modelled recovery ratio ~5/4 even if dispatch cost were flat per
    # call, comfortably inside the 1.5 gate
    window, hop, bw, D, n_frames = 2048, 1024, 4, 4, 64
    cfg = StreamConfig(window=window, hop=hop, batch_windows=bw,
                       outputs=("features", "margin", "class"))
    sig, _ = synthetic_respiration(1, (n_frames - 1) * hop + window, seed=8)
    raw = sig[0]

    def run_once(injector):
        if injector is not None:
            injector.reset()
        r = FaultTolerantColumnRunner(app, cfg, n_columns=D,
                                      injector=injector)
        out = r.process(raw)
        jax.block_until_ready(out)
        return max(r.column_busy) * 1e6, out

    kill = FaultInjector(kill={0: 1})
    run_once(None)                   # compile + warm
    run_once(kill)
    walls_ok, walls_f = [], []
    out_ok = out_f = None
    for _ in range(7):               # paired: alternate inside one loop
        w, out_ok = run_once(None)
        walls_ok.append(w)
        w, out_f = run_once(kill)
        walls_f.append(w)
    identical = set(out_ok) == set(out_f) and all(
        bool((jnp.asarray(out_ok[k]) == jnp.asarray(out_f[k])).all())
        for k in out_ok)
    us_ok, us_f = min(walls_ok), min(walls_f)
    from repro.core import autotune

    autotune.record_pinned("table5/stream_fault_recovered", walls_f,
                           baseline_us=walls_ok)
    return [
        ("table5/stream_faultfree", us_ok,
         f"modelled dispatch wall, D={D} healthy columns, equal deal of "
         f"{n_frames} frames (window={window},hop={hop},bw={bw})"),
        ("table5/stream_fault_recovered", us_f,
         f"column 0 killed at its 2nd dispatch, unretired frames "
         f"requeued over {D - 1} survivors;bit_identical={identical};"
         f"recovery_ratio={us_f / us_ok:.2f}x"),
    ]


def _engine_fault_rows():
    """Fault-tolerant LM serving: the cost of losing one of four engine
    slots mid-decode. Both runs go through
    `serve/engine_fault.py:FaultTolerantEngine`; the fault run kills
    slot 0 at its 5th dispatch (`FaultInjector` seq 4 — prefill is seq 0,
    so mid-decode), after which the slot is poisoned, its request
    requeues to the queue front, and the survivors replay it from the
    re-prefilled prompt + generated prefix. Wall time is the real
    run_to_completion wall (one batched decode dispatch per step — the
    degraded engine pays more steps on fewer slots). Tokens must be
    BIT-IDENTICAL to the fault-free run for every request (the per-
    request-key chaos invariant, `tests/test_engine_fault.py`); the CI
    bench smoke gates recovered wall <= 1.5x fault-free AND bit-identity
    via ``run.py --check-engine-fault``."""
    import dataclasses as dc

    from repro.configs import get_config, reduced
    from repro.core import autotune
    from repro.models import build_model, init_model_params
    from repro.serve.engine import Engine, Request
    from repro.serve.engine_fault import FaultInjector, FaultTolerantEngine

    cfg = dc.replace(reduced(get_config("qwen1.5-0.5b")), vocab_size=64)
    model = build_model(cfg)
    params = init_model_params(model, seed=3)
    compiled = Engine.compile_model(model)
    # 14 requests over 4 slots, 12 new tokens each, equal-length prompts
    # (one prefill bucket): fault-free serves 14x12 = 168 tokens in ~48
    # batched decode steps (3.5 waves). Killing slot 0 at seq 4 (its 4th
    # dispatch, mid-decode) poisons it, so the remaining tokens drain
    # over 3 slots in ~60 steps — a 1.25x step ratio whose tail slack
    # absorbs the replay prefill and the staggered wave admissions
    # inside the 1.5 gate.
    slots, max_new, n_req = 4, 12, 14
    prompts = {rid: [1 + rid % 8, (rid % 5) + 1] for rid in range(n_req)}

    def run_once(injector):
        if injector is not None:
            injector.reset()
        eng = FaultTolerantEngine(model, params, slots=slots, max_len=64,
                                  temperature=0.8, seed=7,
                                  compiled=compiled, injector=injector)
        for rid, p in prompts.items():
            eng.add_request(Request(rid, list(p), max_new=max_new))
        t0 = time.perf_counter()
        done = eng.run_to_completion(max_steps=500)
        wall = (time.perf_counter() - t0) * 1e6
        return wall, {r.rid: tuple(r.out) for r in done}

    kill = FaultInjector(kill={0: 4})
    run_once(None)                   # compile + warm (incl. decode trace)
    run_once(kill)                   # warm the replay-prefill trace too
    walls_ok, walls_f = [], []
    out_ok = out_f = None
    for _ in range(7):               # paired: alternate inside one loop
        w, out_ok = run_once(None)
        walls_ok.append(w)
        w, out_f = run_once(kill)
        walls_f.append(w)
    identical = out_ok == out_f
    us_ok, us_f = min(walls_ok), min(walls_f)
    autotune.record_pinned("table5/engine_fault_recovered", walls_f,
                           baseline_us=walls_ok)
    return [
        ("table5/engine_faultfree", us_ok,
         f"LM engine wall, {slots} healthy slots, {n_req} requests x "
         f"{max_new} tokens, temperature-sampled per-request streams"),
        ("table5/engine_fault_recovered", us_f,
         f"slot 0 killed mid-decode (seq 4), request replayed on "
         f"{slots - 1} survivors;bit_identical={identical};"
         f"recovery_ratio={us_f / us_ok:.2f}x"),
    ]


def _engine_paged_rows():
    """Paged KV cache vs dense slots at OVERSUBSCRIBED admission.

    Both engines serve 14 short requests (2-token prompts, 12 new tokens)
    with 4 decode lanes and max_len=256. The dense engine admits at most
    4 at a time and every decode step attends over the full 256-slot
    cache rows. The paged engine (`serve/engine.py:PagedEngine`,
    page_size=16) admits ALL 14 up front — admission is bounded by free
    pages, and a 14-token worst case fits ONE page — so
    ``peak_admitted`` hits 14 > 4 lanes, and each decode step gathers a
    16-wide page view instead of 256 dense columns: the compute saving
    that pays for the block-table indirection. Tokens must be
    BIT-IDENTICAL (temperature-sampled per-request streams — the
    `tests/test_paged.py` invariant); the CI bench smoke gates paged
    wall <= dense wall AND bit-identity via ``run.py --check-paged``."""
    import dataclasses as dc

    from repro.configs import get_config, reduced
    from repro.core import autotune
    from repro.models import build_model, init_model_params
    from repro.serve.engine import Engine, PagedEngine, Request

    cfg = dc.replace(reduced(get_config("qwen1.5-0.5b")), vocab_size=64)
    model = build_model(cfg)
    params = init_model_params(model, seed=3)
    compiled = Engine.compile_model(model)
    slots, max_len, max_new, n_req = 4, 256, 12, 14
    prompts = {rid: [1 + rid % 8, (rid % 5) + 1] for rid in range(n_req)}

    peak = [0]

    def run_once(paged: bool):
        cls = PagedEngine if paged else Engine
        kw = {"page_size": 16} if paged else {}
        eng = cls(model, params, slots=slots, max_len=max_len,
                  temperature=0.8, seed=7, compiled=compiled, **kw)
        for rid, p in prompts.items():
            eng.add_request(Request(rid, list(p), max_new=max_new))
        t0 = time.perf_counter()
        done = eng.run_to_completion(max_steps=500)
        wall = (time.perf_counter() - t0) * 1e6
        if paged:
            peak[0] = eng.peak_admitted
        return wall, {r.rid: tuple(r.out) for r in done}

    run_once(False)                  # compile + warm both paths
    run_once(True)
    walls_d, walls_p = [], []
    out_d = out_p = None
    for _ in range(7):               # paired: alternate inside one loop
        w, out_d = run_once(False)
        walls_d.append(w)
        w, out_p = run_once(True)
        walls_p.append(w)
    identical = out_d == out_p
    us_d, us_p = min(walls_d), min(walls_p)
    autotune.record_pinned("table5/engine_paged", walls_p,
                           baseline_us=walls_d)
    return [
        ("table5/engine_dense", us_d,
         f"dense-slot LM engine wall, {slots} slots x max_len={max_len}, "
         f"{n_req} requests x {max_new} tokens (admission bound: slots)"),
        ("table5/engine_paged", us_p,
         f"paged KV (page_size=16), admission bound: free pages — "
         f"peak_admitted={peak[0]} on {slots} lanes;"
         f"bit_identical={identical};paged_speedup={us_d / us_p:.2f}x"),
    ]


def run():
    from repro.archsim.energy import vwr2a_energy_uj
    from repro.archsim.programs.app import run_app
    from repro.core.fir import lowpass_taps

    rng = np.random.default_rng(0)
    t = np.arange(1024) / 64.0
    sig = 0.4 * np.sin(2 * np.pi * 0.3 * t) + 0.05 * rng.standard_normal(1024)
    out = run_app(sig, lowpass_taps(11), rng.normal(size=(12, 2)) * 0.3,
                  np.zeros(2))
    rows = []
    tot_c, tot_e = 0, 0.0
    steps = ("preprocessing", "delineation", "feat_extraction", "svm")
    for step in steps:
        counters, cycles = out[step]
        e = vwr2a_energy_uj(counters)
        key = step if step != "svm" else "feat_extraction"
        tot_c += cycles
        tot_e += e
        if step == "svm":
            rows.append((f"table5/svm", cycles / F_HZ * 1e6,
                         f"sim_cycles={cycles};sim_uJ={e:.4f}"))
            continue
        cpu_c, cpu_e = PAPER_CPU[step]
        v_c, v_e = PAPER_VWR2A[step]
        rows.append((f"table5/{step}", cycles / F_HZ * 1e6,
                     f"sim_cycles={cycles};paper_vwr2a={v_c};"
                     f"cycle_savings_vs_cpu={100 * (1 - cycles / cpu_c):.1f}%"
                     f"(paper {100 * (1 - v_c / cpu_c):.1f}%);"
                     f"sim_uJ={e:.3f};"
                     f"energy_savings_vs_cpu={100 * (1 - e / cpu_e):.1f}%"))
    cpu_c, cpu_e = PAPER_CPU["total"]
    v_c, v_e = PAPER_VWR2A["total"]
    rows.append(("table5/total", tot_c / F_HZ * 1e6,
                 f"sim_cycles={tot_c};paper_vwr2a={v_c};"
                 f"cycle_savings_vs_cpu={100 * (1 - tot_c / cpu_c):.1f}%"
                 f"(paper 90.9%);sim_uJ={tot_e:.3f};"
                 f"energy_savings_vs_cpu={100 * (1 - tot_e / cpu_e):.1f}%"
                 f"(paper 66.3%)"))
    rows += _pipeline_rows()
    rows += _stream_rows()
    rows += _asr_rows()
    rows += _column_rows()
    rows += _hetero_rows()
    rows += _depth_rows()
    rows += _fault_rows()
    rows += _engine_fault_rows()
    rows += _engine_paged_rows()
    return rows

#!/usr/bin/env python3
"""Smoke run of the served path on a TPU, checked against references.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the 4-column stream path, 2x2 host

One chip, in this one process and through the normal entry points:

1. device check: the first JAX device must be a TPU (no CPU fallback);
2. biosignal stream: the MBioTracker config (2048-sample windows, hop 512,
   11-tap FIR, 512-point rFFT, SVM) admitted with
   `ServeFrontend.submit(StreamOpen(...))`; 2^23 seeded samples through
   the per-upload loop (`BiosignalStream.process`), the per-batch loop
   (`BiosignalStream.stream`) and the device-resident loop
   (`ResidentStream.process`). The three must agree bit for bit, and
   match the staged jnp reference (`kernels/pipeline/ref.py`);
3. ASR front-end: 10 minutes of seeded 16 kHz audio through the "asr"
   stage graph at 512 / 128, checked against `asr.py:asr_reference`;
4. LM serving: qwen1.5-0.5b at its published widths with weights drawn
   from --seed, 8 requests (prompts of 32-256 tokens, 32 new tokens each)
   through `ServeFrontend` to `Engine` (4 slots, max_len 512); the greedy
   tokens are checked against the model's own uncached `forward`.

`--four-chips` runs only the multi-column stream (`StreamConfig(
n_columns=4)`, `shard_map` over a 4-device `data` mesh) and the one-device
kernel it is compared with.

Each stage-graph kernel must have lowered to Mosaic (`tpu_custom_call` in
its compiled HLO). The per-phase lines are smoke timings of a single run
(compile seconds, steady wall time after `block_until_ready`, peak device
bytes), not benchmark metrics. The last stdout line is the JSON result,
printed only when every phase passed; any failure exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

BIO_SAMPLES = 1 << 23          # ~16k windows of 2048 at hop 512
ASR_SECONDS = 600              # 10 minutes of 16 kHz audio
BIO_TOL = 1e-4                 # scale-relative, tests/test_stream_kernel.py
ASR_TOL = 1e-5                 # scale-relative, tests/test_asr.py
LM_PROMPTS = (32, 256, 77, 128, 200, 45, 160, 99)
LM_NEW = 32
# a greedy token may differ from the uncached forward's argmax only where
# that forward's top-2 logit gap is below this share of the row's std
LM_GAP_TOL = 0.05


def log(msg: str) -> None:
    print(msg, flush=True)


def timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def report(phase: str, compile_s: float, steady_s: float, extra: str = ""):
    import jax

    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"[smoke timing] {phase}: compile_s={compile_s:.3f} "
        f"steady_wall_s={steady_s:.3f} device_kind={dev.device_kind} "
        f"peak_bytes_in_use={peak}{' ' + extra if extra else ''}")


def assert_mosaic(name: str, jitted, *args, **kwargs) -> None:
    """The kernel lowered to Mosaic, not to the interpreter."""
    hlo = jitted.lower(*args, **kwargs).compile().as_text()
    assert "tpu_custom_call" in hlo, f"{name}: no Mosaic kernel in its HLO"


def assert_close(name: str, got, want, tol: float) -> float:
    a = np.asarray(want, np.float64)
    b = np.asarray(got, np.float64)
    assert a.shape == b.shape, (name, a.shape, b.shape)
    assert np.isfinite(b).all(), f"{name}: non-finite values"
    err = float(np.abs(a - b).max()) / max(1.0, float(np.abs(a).max()))
    assert err < tol, f"{name}: scale-relative error {err:.3g} >= {tol}"
    return err


def device_check(n_chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found "
                 f"{devs[0].platform!r} devices")
    assert len(devs) >= n_chips, (len(devs), n_chips)
    from repro.kernels import interpret_mode

    assert not interpret_mode(), "kernels would run in the interpreter"
    log(f"[smoke] device: {devs[0].platform} {devs[0].device_kind} "
        f"x{len(devs)}")
    return devs


# ---------------------------------------------------------------------------
# Biosignal stream: per-batch and device-resident loops
# ---------------------------------------------------------------------------

def check_biosignal_reference(app, sig, out, window, hop, frames):
    """Frames [f0, f0 + k) of ``out`` against the staged jnp reference."""
    import jax

    from repro.kernels.pipeline.ref import pipeline_staged
    from repro.serve.stream import frame_signal

    f0, k = frames
    seg = sig[f0 * hop: (f0 + k - 1) * hop + window]
    with jax.default_matmul_precision("highest"):
        ref = pipeline_staged(frame_signal(seg, window, hop), app.fir_taps,
                              app.svm_w, app.svm_b, fft_size=app.fft_size)
    for key in ("filtered", "features", "margin"):
        assert_close(f"biosignal {key}[{f0}:{f0 + k}]",
                     out[key][f0: f0 + k], ref[key], BIO_TOL)
    np.testing.assert_array_equal(np.asarray(out["class"][f0: f0 + k]),
                                  np.asarray(ref["class"]),
                                  err_msg="biosignal class")


def biosignal_phase(front, seed: int) -> None:
    import jax

    from repro.configs.vwr2a_biosignal import CONFIG as BIO
    from repro.core.biosignal import make_app, synthetic_respiration
    from repro.kernels.fft.kernel import fft_pallas
    from repro.kernels.fir.kernel import fir_pallas
    from repro.kernels.pipeline.kernel import (OUTPUTS,
                                               pipeline_ring_pallas,
                                               pipeline_stream_pallas)
    from repro.serve.frontend import StreamOpen
    from repro.serve.resident import ResidentConfig
    from repro.serve.stream import StreamConfig, frame_count

    window, hop = BIO.window_samples, 512
    app = make_app(BIO, seed=seed)
    cfg = StreamConfig(window=window, hop=hop, batch_windows=64)
    ticket = front.submit(StreamOpen(stream_id="mbiotracker-0", app=app,
                                     cfg=cfg))
    front.run()
    stream = ticket.result()
    sig = synthetic_respiration(1, BIO_SAMPLES, seed=seed)[0][0]
    n = frame_count(BIO_SAMPLES, window, hop)

    chunk = sig[: stream.chunk_samples]
    kw = dict(window=window, hop=hop, fft_size=app.fft_size,
              interpret=False, block_frames=None, outputs=OUTPUTS)
    taps, w, b = app.fir_taps, app.svm_w, app.svm_b
    assert_mosaic("biosignal stream kernel", pipeline_stream_pallas,
                  chunk, taps, w, b, **kw)
    assert_mosaic("biosignal ring kernel", pipeline_ring_pallas,
                  jax.numpy.stack([chunk] * 4), taps, w, b, **kw)
    assert_mosaic("fir kernel", fir_pallas, sig[None, :window],
                  jax.numpy.asarray(taps), interpret=False)
    seg = sig[None, :app.fft_size // 2]
    assert_mosaic("fft kernel", fft_pallas, seg, seg, interpret=False)

    _, first_s = timed(lambda: stream.process(sig))
    out, steady_s = timed(lambda: stream.process(sig))
    assert out["class"].shape == (n,), out["class"].shape
    report("biosignal per-upload loop", first_s - steady_s, steady_s,
           f"frames={n} samples={BIO_SAMPLES} "
           "(compile_s = first call - steady call)")

    def per_batch():
        batches = list(stream.stream(sig))
        return {k: np.concatenate([np.asarray(b[k]) for b in batches])
                for k in batches[0]}

    _, compile_s = timed(lambda: list(stream.stream(chunk)))
    ref, steady_b = timed(per_batch)
    report("biosignal per-batch loop", compile_s, steady_b,
           f"frames={n} (stream(), outputs copied to the host)")
    assert sorted(ref) == sorted(out), (sorted(ref), sorted(out))
    for key in out:
        np.testing.assert_array_equal(
            np.asarray(out[key]), ref[key],
            err_msg=f"per-upload vs per-batch {key}")
    log("[smoke] biosignal: per-upload == per-batch, bit for bit")

    rcfg = ResidentConfig(ring_depth=4)
    _, first_s = timed(lambda: stream.process_resident(sig, rcfg))
    res, steady_r = timed(lambda: stream.process_resident(sig, rcfg))
    report("biosignal resident loop", first_s - steady_r, steady_r,
           f"frames={n} (compile_s = first call - steady call)")

    assert sorted(res) == sorted(out), (sorted(res), sorted(out))
    for key in out:
        np.testing.assert_array_equal(
            np.asarray(res[key]), np.asarray(out[key]),
            err_msg=f"resident vs per-upload {key}")
    log("[smoke] biosignal: resident == per-upload, bit for bit")
    for frames in ((0, 128), (n - 128, 128)):
        check_biosignal_reference(app, sig, out, window, hop, frames)
    log("[smoke] biosignal: matches the staged jnp reference "
        f"(scale-relative < {BIO_TOL}, class exact)")


# ---------------------------------------------------------------------------
# ASR front-end
# ---------------------------------------------------------------------------

def synthetic_audio(seconds: int, sr: int, seed: int) -> np.ndarray:
    """Seeded speech-like audio: gliding harmonics under a syllable-rate
    envelope, plus background noise."""
    rng = np.random.default_rng(seed)
    t = np.arange(seconds * sr, dtype=np.float64) / sr
    f0 = 140.0 + 40.0 * np.sin(2 * np.pi * 0.3 * t)
    phase = 2 * np.pi * np.cumsum(f0) / sr
    voiced = sum(np.sin(k * phase) / k for k in (1, 2, 3, 5))
    envelope = 0.5 * (1 + np.sin(2 * np.pi * 4.0 * t)) ** 2
    audio = 0.3 * envelope * voiced + 0.02 * rng.standard_normal(t.size)
    return audio.astype(np.float32)


def asr_phase(front, seed: int) -> None:
    import jax

    from repro.kernels.pipeline.asr import asr_reference, make_asr_frontend
    from repro.kernels.pipeline.graph import get_graph_factory
    from repro.kernels.pipeline.graph import graph_stream_pallas
    from repro.serve.frontend import StreamOpen
    from repro.serve.stream import StreamConfig, frame_count

    window, hop = 512, 128
    app = make_asr_frontend()
    cfg = StreamConfig(window=window, hop=hop, batch_windows=512,
                       graph="asr")
    ticket = front.submit(StreamOpen(stream_id="asr-0", app=app, cfg=cfg))
    front.run()
    stream = ticket.result()
    audio_np = synthetic_audio(ASR_SECONDS, int(app.sample_rate), seed)
    audio = jax.numpy.asarray(audio_np)
    n = frame_count(audio_np.size, window, hop)

    chunk = audio[: stream.chunk_samples]
    graph, operands = get_graph_factory("asr")(app)
    assert_mosaic("asr stream kernel", graph_stream_pallas, chunk, operands,
                  graph=graph, window=window, hop=hop, interpret=False,
                  block_frames=None, outputs=("filtered", "logmel"))

    _, compile_s = timed(lambda: stream.process(chunk))
    out, steady_s = timed(lambda: stream.process(audio))
    assert out["logmel"].shape == (n, app.n_mels), out["logmel"].shape
    report("asr front-end", compile_s, steady_s,
           f"frames={n} audio_s={ASR_SECONDS}")
    for f0 in (0, n // 2, n - 256):
        ref = asr_reference(app, audio_np[f0 * hop: (f0 + 255) * hop + window],
                            window=window, hop=hop)
        for key in ("filtered", "logmel"):
            assert_close(f"asr {key}[{f0}:]", out[key][f0: f0 + 256],
                         ref[key], ASR_TOL)
    log(f"[smoke] asr: matches asr_reference (scale-relative < {ASR_TOL})")


# ---------------------------------------------------------------------------
# LM serving
# ---------------------------------------------------------------------------

def lm_phase(seed: int) -> None:
    import jax

    from repro.configs import get_config
    from repro.models import build_model, init_model_params
    from repro.serve.engine import Engine, Request
    from repro.serve.frontend import ServeFrontend

    cfg = get_config("qwen1.5-0.5b")
    model = build_model(cfg)
    params = init_model_params(model, seed)
    compiled = Engine.compile_model(model)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, size=k).tolist()
               for k in LM_PROMPTS]

    def serve():
        eng = Engine(model, params, slots=4, max_len=512, compiled=compiled)
        front = ServeFrontend(engine=eng)
        tickets = [front.submit(Request(i, list(p), max_new=LM_NEW))
                   for i, p in enumerate(prompts)]
        front.run()
        return [t.result() for t in tickets]

    first, first_s = timed(serve)
    done, steady_s = timed(serve)
    assert [r.out for r in done] == [r.out for r in first], \
        "greedy serving is not deterministic"
    assert all(len(r.out) == LM_NEW for r in done)
    tokens = len(done) * LM_NEW
    report("qwen1.5-0.5b serving", first_s - steady_s, steady_s,
           f"requests={len(done)} new_tokens={tokens} "
           f"(compile_s = first run - steady run)")

    fwd = jax.jit(model.forward)
    width = max(LM_PROMPTS) + LM_NEW
    near_ties = 0
    for r in done:
        seq = r.prompt + r.out
        toks = np.zeros((1, width), np.int32)
        toks[0, : len(seq)] = seq
        logits = np.asarray(fwd(params, {"tokens": jax.numpy.asarray(toks)}
                                )[0][0], np.float32)
        rows = logits[len(r.prompt) - 1: len(r.prompt) - 1 + LM_NEW]
        want = rows.argmax(-1)
        for i in np.flatnonzero(want != np.asarray(r.out)):
            top2 = np.sort(rows[i])[-2:]
            gap, tol = float(top2[1] - top2[0]), LM_GAP_TOL * rows[i].std()
            assert gap < tol, (f"request {r.rid} token {i}: engine "
                               f"{r.out[i]} vs forward {want[i]}, top-2 "
                               f"gap {gap:.4g} >= {tol:.4g}")
            near_ties += 1
    log(f"[smoke] qwen1.5-0.5b: greedy tokens match the uncached forward "
        f"({near_ties} of {tokens} differ, each at a near-tie)")


# ---------------------------------------------------------------------------
# Four chips: column replication over a data mesh
# ---------------------------------------------------------------------------

def four_chip_phase(seed: int) -> None:
    import jax

    from repro.core.biosignal import make_app, synthetic_respiration
    from repro.serve.stream import BiosignalStream, StreamConfig

    app = make_app(seed=seed)
    sig = synthetic_respiration(1, BIO_SAMPLES, seed=seed)[0][0]
    cfg4 = StreamConfig(window=2048, hop=512, batch_windows=64, n_columns=4,
                        outputs=("margin", "class"))
    stream4 = BiosignalStream(app, cfg4)
    mesh_devices = {d.id for d in np.asarray(stream4.mesh.devices).flat} \
        if stream4.mesh is not None else set()
    assert len(mesh_devices) == 4, \
        f"column mesh spans {len(mesh_devices)} devices, not 4"
    ref_cfg = StreamConfig(window=2048, hop=512, batch_windows=256,
                           outputs=("margin", "class"))
    ref_stream = BiosignalStream(app, ref_cfg, device=jax.devices()[0])

    _, compile4 = timed(lambda: stream4.process(sig[:stream4.chunk_samples]))
    out4, steady4 = timed(lambda: stream4.process(sig))
    report("biosignal 4-column stream", compile4, steady4,
           f"frames={out4['class'].shape[0]}")
    _, compile1 = timed(lambda: ref_stream.process(
        sig[:ref_stream.chunk_samples]))
    out1, steady1 = timed(lambda: ref_stream.process(sig))
    report("biosignal one-device stream", compile1, steady1,
           f"frames={out1['class'].shape[0]}")
    np.testing.assert_array_equal(np.asarray(out4["class"]),
                                  np.asarray(out1["class"]),
                                  err_msg="4-column class")
    diff = float(np.abs(np.asarray(out4["margin"])
                        - np.asarray(out1["margin"])).max())
    assert diff <= 1e-4, f"4-column margin differs by {diff}"
    log(f"[smoke] 4 columns on {len(mesh_devices)} devices: class "
        f"bit-identical, margin max diff {diff:.3g}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-column stream path and its "
                         "one-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devs = device_check(4 if args.four_chips else 1)
    from repro.launch.compile_cache import enable_compile_cache

    log(f"[smoke] compile cache: {enable_compile_cache()}")
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        from repro.serve.engine import ColumnScheduler
        from repro.serve.frontend import ServeFrontend

        front = ServeFrontend(scheduler=ColumnScheduler(devs[:1]))
        biosignal_phase(front, args.seed)
        asr_phase(front, args.seed)
        lm_phase(args.seed)
    log(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

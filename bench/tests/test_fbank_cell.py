"""The `asr.backlog` cell (Kaldi fbank, `kaldi-fbank80` x
`utterance-backlog`) rehearsed on the CPU at a tiny size: every part is
found by name, the bench's reference agrees with the program's, the
program passes the limits and the control (the reference in bfloat16)
fails them, and the pool's lengths are fixed with the longest first."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from bench import readings, run, traffic
from bench.registry import BENCH, ROOT, Registry

CELL = "asr.backlog"
SEED = 2 ** 31 + 77
SECONDS = "1.5"
# four utterances of 1 to 3 s: every length compiles once in set-up
TINY_MIX = {"arrivals": "utterances", "pool": 4, "median_s": 1.5,
            "sigma": 0.7, "min_s": 1.0, "max_s": 3.0, "step_s": 0.01}


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    d = tmp_path_factory.mktemp("tiny")
    (d / "mixes").mkdir()
    (d / "mixes" / "utterance-backlog.json").write_text(json.dumps(TINY_MIX))
    return Registry(json.loads((ROOT / "BENCHMARK.json").read_text()),
                    (d, BENCH))


@pytest.fixture(autouse=True)
def _no_compile_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def test_every_part_is_found_by_name():
    reg = Registry.from_root()
    w = reg.workload(CELL)
    cfg = reg.config(w["config"])
    assert (cfg["graph"], cfg["signal"]) == ("fbank", "speech")
    mix = reg.mix(w["traffic"])
    assert mix["arrivals"] == "utterances"
    for kind, name in (("apps", "fbank"), ("ref", "fbank"), ("work", "fbank"),
                       ("signals", "speech"), ("arrivals", "utterances")):
        assert reg.module(kind, name)
    assert reg.module("apps", "fbank").KERNEL == ("graph_stream_kernel.fbank",)
    names = [m["name"] for m in reg.metrics(CELL, "per_layer")]
    assert names == ["fbank_kernel_roofline.asr", "host_gap_ms.asr",
                     "device_idle_share.asr"]
    assert [m["name"] for m in reg.metrics(CELL, "end_to_end")] == \
        ["frames_per_s", "setup_s"]


def test_bench_reference_agrees_with_the_programs():
    from repro.kernels.pipeline.fbank import fbank_reference

    reg = Registry.from_root()
    cfg = reg.config("kaldi-fbank80")
    p = reg.module("apps", "fbank").params(cfg, None)
    app = reg.module("apps", "fbank").build(cfg, p)
    sig = reg.module("signals", "speech").make(
        np.random.default_rng(5), 3 * 16000 + 123, 16000)
    st = cfg["stream"]
    want = reg.module("ref", "fbank").reference(
        p, sig, window=st["window"], hop=st["hop"],
        fft_size=cfg["app"]["fft_size"])["fbank"]
    got = fbank_reference(app, sig, hop=st["hop"])
    assert got.shape == want.shape == (1 + (sig.shape[0] - 400) // 160, 80)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-11)


def test_build_refuses_an_fft_size_kaldi_would_not_use():
    """The FFT size is the frame length rounded up to a power of two
    (round_to_power_of_two): a configuration naming another is refused."""
    reg = Registry.from_root()
    cfg = reg.config("kaldi-fbank80")
    apps = reg.module("apps", "fbank")
    p = apps.params(cfg, None)
    assert apps.build(cfg, p).fft_size == cfg["app"]["fft_size"] == 512
    with pytest.raises(ValueError, match="fft_size"):
        apps.build(cfg, dict(p, fft_size=1024))


def test_the_signal_is_whole_int16_numbers_and_never_silent():
    sig = Registry.from_root().module("signals", "speech").make(
        np.random.default_rng(7), 5 * 16000, 16000)
    assert sig.dtype == np.float32
    assert np.all(sig == np.round(sig)) and np.abs(sig).max() <= 32768
    frames = sig[: (sig.shape[0] // 400) * 400].reshape(-1, 400)
    rms = np.sqrt(np.mean(frames.astype(np.float64) ** 2, axis=1))
    assert rms.min() > 1.0 and rms.max() > 1000.0


def test_the_pool_is_fixed_with_the_longest_first():
    reg = Registry.from_root()
    mix = reg.mix("utterance-backlog")
    arrivals = reg.module("arrivals", "utterances")
    secs = arrivals.lengths_s(mix)
    assert len(secs) == 32 and secs == sorted(secs, reverse=True)
    assert 1.0 <= secs[-1] and secs[0] <= 30.0
    assert sorted(secs)[15] <= 6.0 <= sorted(secs)[16]     # median 6 s

    def signal(rng, n, sr):
        return np.full(n, rng.integers(1 << 30), np.float32)

    cfg = {"sample_rate_hz": 16000}
    pools = [traffic.build(mix, cfg, s, 10.0, signal, arrivals)
             for s in (1, 2 ** 31 + 5)]
    lens = [[p.shape[0] for p in tr.pool] for tr in pools]
    assert sorted(lens[0]) == sorted(lens[1])
    assert lens[0][0] == lens[1][0] == max(lens[0]) == round(secs[0] * 16000)
    assert lens[0] != lens[1]                   # the order is the seed's
    assert pools[0].lengths == tuple(sorted(set(lens[0])))
    assert pools[0].pool[1][0] != pools[1].pool[1][0]   # so is the data


def test_rehearsal_is_correct(reg, tmp_path):
    """The cell on as many devices as it asks for (CPU devices here, in a
    process of its own): correct, every upload on the first device, and
    nothing compiled in the window."""
    chips = reg.workload(CELL)["chips"]
    code = "\n".join([
        "import json, sys",
        f"sys.path[:0] = [{str(ROOT / 'src')!r}, {str(ROOT)!r}]",
        "import jax",
        "from bench import run",
        "from bench.registry import Registry",
        "from repro.serve import stream",
        f"reg = Registry(json.loads({json.dumps(reg.spec)!r}),",
        f"               {[str(d) for d in reg.dirs]!r})",
        "real = stream.BiosignalStream.process",
        "seen = set()",
        "def process(self, signal):",
        "    seen.add(self.device)",
        "    return real(self, signal)",
        "stream.BiosignalStream.process = process",
        f"rc = run.main(['--workload', {CELL!r}, '--seed', '{SEED}',",
        f"              '--seconds', {SECONDS!r}, '--trace', '0'], reg=reg,",
        "              require_chip=False)",
        "print(json.dumps({'devices': len(jax.devices()),",
        "                  'seen': sorted(d.id for d in seen)}))",
        "sys.exit(rc)"])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    res, where = json.loads(lines[-2]), json.loads(lines[-1])
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == where["devices"] == chips
    assert where["seen"] == [0]
    assert "in-window compilations: 0" in proc.stderr


def test_the_control_is_not_correct(reg):
    import jax

    c = run.load_cell(reg, CELL, jax.devices()[:1])
    r = readings.reading(c, SEED, float(SECONDS), control=True)
    ok, checks = run.judge(c, r["numbers"], r["failed"])
    assert r["numbers"]["uploads_checked"] > 0
    assert not ok, checks
    assert all(checks[k]["value"] > checks[k]["limit"]
               for k in ("energy_err", "log_err")), checks


def test_work_counts_by_hand_and_memory_bound():
    reg = Registry.from_root()
    cfg = reg.config("kaldi-fbank80")
    mel = reg.module("ref", "fbank").mel_banks(
        reg.module("apps", "fbank").params(cfg, None), 512)
    nnz = int(np.count_nonzero(mel))
    # every FFT bin from 20 Hz up lies in one or two triangles
    assert 255 < nnz < 2 * 256
    spectrum = 2 * 512 + 2.5 * 512 * 9 + 4 * 257
    frame = 2 * 400 + 2 * 400 + 400 + spectrum + 2 * nnz + 2 * 80
    ops, nbytes = reg.module("work", "fbank").counts(cfg, frames=7,
                                                      samples=1360)
    assert ops == 7 * frame
    assert nbytes == 4 * 1360 + 7 * 4 * 80      # samples read, 80 words out
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    assert nbytes / peaks["hbm_bytes_per_s"] > ops / peaks["flops_per_s"]


def test_idle_share_reads_the_chips_that_ran_the_kernel():
    """Chip 0 runs the kernel 10-30 ms of a 100 ms window; chip 1 only
    copies and chip 2 does nothing: they hold the host, not the work."""
    from bench.trace import Trace

    ms = 1_000_000
    trace = Trace({"devices": {
        "0": [["graph_stream_kernel.fbank.5", 10 * ms, 30 * ms]],
        "1": [["copy.1", 0, 5 * ms]], "2": []},
        "spans": [["bench.window", 0, 100 * ms]]})
    read = Registry.from_root().module("metrics", "device_idle_share.asr").read
    kernel = ("graph_stream_kernel.fbank",)
    assert read(types.SimpleNamespace(trace=trace, kernel=kernel)) == \
        pytest.approx(80.0)
    assert read(types.SimpleNamespace(trace=None, kernel=kernel)) is None

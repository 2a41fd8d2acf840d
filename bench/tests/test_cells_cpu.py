"""Every cell rehearsed on the CPU through the same code as a chip run, at
a tiny size: the run is correct; with the timed path broken underneath it
is not; and the control (the plain reference in bfloat16 in the program's
place) is not.

The harness's look for a chip is skipped here, and a rehearsal prints no
metric: a CPU time is no device time.
"""
import json

import numpy as np
import pytest

from bench import readings, run
from bench.registry import BENCH, ROOT, Registry

CELLS = ["mbio.archive", "mbio.sync"]
# recordings of 200 frames span four 64-frame dispatches, the last padded
TINY_MIXES = {
    "archive-backlog": {"arrivals": "backlog", "recording_s": 1624,
                        "pool": 2},
    "wearable-sync": {"arrivals": "periodic", "rate_per_s": 2.0,
                      "periods_s": [8, 16], "overlap_samples": 1536,
                      "pool_s": 600},
}
SEED = 2 ** 31 + 99
SECONDS = "1.5"


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    d = tmp_path_factory.mktemp("tiny")
    (d / "mixes").mkdir()
    for name, mix in TINY_MIXES.items():
        (d / "mixes" / f"{name}.json").write_text(json.dumps(mix))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    # built and rehearsed, not yet in BENCHMARK.json: its rate waits for a
    # sweep on the chip
    spec["workloads"].append({"name": "mbio.sync", "config": "mbiotracker",
                              "traffic": "wearable-sync", "chips": 1,
                              "why": "rehearsal"})
    return Registry(spec, (d, BENCH))


@pytest.fixture(autouse=True)
def _no_compile_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def run_cell(reg, cell, capsys, trace=0) -> dict:
    assert run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                     SECONDS, "--trace", str(trace)],
                    reg=reg, require_chip=False) == 0
    out, err = capsys.readouterr()
    res = json.loads(out.strip().splitlines()[-1])
    # the numbers compared end standard error, each beside its limit
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert [ln.split(":")[0] for ln in tail] == \
        [f"check {k}" for k in res["checks"]]
    return res


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal_is_correct(reg, cell, capsys):
    res = run_cell(reg, cell, capsys)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"] == {}
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


def _altered(orig):
    """One answer of every upload altered where it is produced."""
    def process(self, signal):
        out = {k: np.array(v) for k, v in orig(self, signal).items()}
        for v in out.values():
            i = v.shape[0] // 2
            if v.dtype.kind in "iu":
                v[i] = 1 - v[i]
            else:
                v[i] += 1.0
        return out
    return process


def _half(orig):
    """Half of every upload's frames left out: their outputs are zeros."""
    def process(self, signal):
        out = {k: np.array(v) for k, v in orig(self, signal).items()}
        for v in out.values():
            v[v.shape[0] // 2:] = 0
        return out
    return process


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_altered, _half])
def test_a_broken_timed_path_is_not_correct(reg, cell, fault, monkeypatch,
                                            capsys):
    from repro.serve.stream import BiosignalStream

    monkeypatch.setattr(BiosignalStream, "process",
                        fault(BiosignalStream.process))
    res = run_cell(reg, cell, capsys)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(reg, cell):
    import jax

    c = run.load_cell(reg, cell, jax.devices()[:1])
    r = readings.reading(c, SEED, float(SECONDS), control=True)
    ok, checks = run.judge(c, r["numbers"], r["failed"])
    assert r["numbers"]["uploads_checked"] > 0
    assert not ok, checks


def test_a_rehearsal_without_a_chip_refuses_to_run(reg, capsys):
    assert run.main(["--workload", "mbio.archive", "--seed", "1",
                     "--seconds", "1", "--trace", "0"], reg=reg) == 2
    assert capsys.readouterr().out == ""

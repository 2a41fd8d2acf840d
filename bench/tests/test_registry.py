"""Every part of the benchmark is found by its name, in files of its own:
adding a configuration, a mix, an arrival process, a per-layer metric or a
graph's work count takes new files and new entries, and no edit to a file
that is there."""
import hashlib
import json

import pytest

from bench import traffic
from bench.registry import BENCH, ROOT, Registry


def _digest() -> str:
    h = hashlib.sha256()
    for p in sorted(BENCH.rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts:
            h.update(str(p.relative_to(BENCH)).encode() + p.read_bytes())
    return h.hexdigest()


def test_a_new_cell_is_found_by_name_alone(tmp_path):
    before = _digest()
    for kind in ("configs", "mixes", "arrivals", "metrics", "work"):
        (tmp_path / kind).mkdir()
    (tmp_path / "configs" / "dummy-dep.json").write_text(json.dumps(
        {"graph": "dummy", "signal": "respiration", "sample_rate_hz": 8}))
    (tmp_path / "mixes" / "dummy-mix.json").write_text(json.dumps(
        {"arrivals": "dummy_burst", "rate_per_s": 3}))
    (tmp_path / "arrivals" / "dummy_burst.py").write_text(
        "from bench.traffic import Traffic\n"
        "def make(mix, sr, seed, seconds, signal):\n"
        "    return Traffic('open_loop', 1, [], [], (mix['rate_per_s'],))\n")
    (tmp_path / "metrics" / "dummy_ratio.x.py").write_text(
        "def read(ctx):\n    return 2.5\n")
    (tmp_path / "work" / "dummy.py").write_text(
        "def counts(cfg, frames, samples):\n    return frames, samples\n")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "dummy.cell", "config": "dummy-dep",
                              "traffic": "dummy-mix", "chips": 1,
                              "why": "test"})
    spec["end_to_end"][0].setdefault("workloads", []).append("dummy.cell")
    spec["per_layer"].append({"name": "dummy_ratio.x", "unit": "ratio",
                              "better": "lower", "source": "host_clock",
                              "layer": "dummy", "moves": "setup_s",
                              "workloads": ["dummy.cell"]})
    reg = Registry(spec, (tmp_path, BENCH))
    w = reg.workload("dummy.cell")
    assert reg.config(w["config"])["graph"] == "dummy"
    mix = reg.mix(w["traffic"])
    tr = traffic.build(mix, {"sample_rate_hz": 8}, 1, 1.0, None,
                       reg.module("arrivals", mix["arrivals"]))
    assert tr.loop == "open_loop" and tr.lengths == (3,)
    assert reg.module("work", "dummy").counts({}, 3, 4) == (3, 4)
    per_layer = [m["name"] for m in reg.metrics("dummy.cell", "per_layer")]
    assert per_layer == ["dummy_ratio.x"]
    assert reg.module("metrics", "dummy_ratio.x").read(None) == 2.5
    # the shipped files are found alongside, and none of them changed
    assert reg.module("signals", "respiration").make
    assert _digest() == before


def test_the_shipped_benchmark_resolves():
    reg = Registry.from_root()
    spec = reg.spec
    for w in spec["workloads"]:
        cfg = reg.config(w["config"])
        reg.module("arrivals", reg.mix(w["traffic"])["arrivals"])
        for kind in ("apps", "ref", "work"):
            reg.module(kind, cfg["graph"])
        reg.module("signals", cfg["signal"])
        e2e = reg.metrics(w["name"], "end_to_end")
        assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
        assert reg.metrics(w["name"], "per_layer")
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(reg.module("metrics", m["name"]).read)
    for c in spec["configs"]:
        assert reg.config(c["name"])["name"] == c["name"]
        assert (ROOT / c["file"]).is_file()


def test_an_unknown_name_is_an_error():
    reg = Registry.from_root()
    with pytest.raises(KeyError):
        reg.mix("no-such-mix")
    with pytest.raises(KeyError):
        reg.workload("no.such.cell")

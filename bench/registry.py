"""Finds every part of the benchmark by its name, in files of its own.

A later change adds a configuration, a mix, a per-layer metric, a graph's
work count or a signal as new files, and a cell or a metric as new entries
of BENCHMARK.json, and edits nothing:

    configs/<config>.json     one deployment: sizes, stream settings, limits
    mixes/<traffic>.json      one traffic mix's parameters
    arrivals/<arrivals>.py    make(mix, sample_rate, seed, seconds, signal)
                              -> Traffic, named by a mix's ``arrivals``
    metrics/<metric>.py       one per-layer reader: read(ctx) -> number | None
    work/<graph>.py           counts(cfg, frames, samples) -> (ops, bytes)
    apps/<graph>.py           params(cfg, rng), build(cfg, params), KERNEL
    ref/<graph>.py            reference(...), compare(got, want, params), merge(parts)
    signals/<signal>.py       make(rng, n_samples, sample_rate)

``dirs`` are searched in order, so a test can put its own files first.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Registry:
    def __init__(self, spec: dict, dirs=(BENCH,)):
        self.spec = spec
        self.dirs = [Path(d) for d in dirs]
        self._modules: dict = {}

    @classmethod
    def from_root(cls, root: Path = ROOT) -> "Registry":
        with open(Path(root) / "BENCHMARK.json") as f:
            return cls(json.load(f), (Path(root) / "bench",))

    def _find(self, kind: str, name: str, ext: str) -> Path:
        for d in self.dirs:
            p = d / kind / f"{name}{ext}"
            if p.is_file():
                return p
        raise KeyError(f"no {kind[:-1]} named {name!r} "
                       f"(looked for {kind}/{name}{ext})")

    def _json(self, kind: str, name: str) -> dict:
        with open(self._find(kind, name, ".json")) as f:
            return json.load(f)

    def module(self, kind: str, name: str):
        key = (kind, name)
        if key not in self._modules:
            path = self._find(kind, name, ".py")
            spec = importlib.util.spec_from_file_location(
                f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"),
                path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[key] = mod
        return self._modules[key]

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def mix(self, name: str) -> dict:
        return self._json("mixes", name)

    def metrics(self, workload: str, section: str) -> list[dict]:
        """The cell's metrics of one section of BENCHMARK.json: those that
        list it, and those that list no cells and move (or, end to end,
        are) a metric the cell reports."""
        e2e = [m for m in self.spec["end_to_end"]
               if workload in m.get("workloads", [workload])]
        if section == "end_to_end":
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if (workload in m["workloads"] if "workloads" in m
                    else m["moves"] in names)]

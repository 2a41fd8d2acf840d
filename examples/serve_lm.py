"""Serve a small model with batched requests through the unified
admission front-end (typed tickets over the continuous-batching engine,
greedy decode over 4 slots), then re-serve the same traffic through the
fault-tolerant supervision layer with a slot killed mid-decode — the
replayed outputs must be bit-identical.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
import time

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.models import build_model, init_model_params
from repro.serve.engine import Engine, Request
from repro.serve.engine_fault import (FaultInjector, FaultTolerantEngine,
                                      VirtualClock)
from repro.serve.frontend import ServeFrontend

cfg = reduced(get_config("h2o-danube-3-4b"))   # exercises SWA decode
model = build_model(cfg)
params = init_model_params(model)
compiled = Engine.compile_model(model)
eng = Engine(model, params, slots=4, max_len=96, compiled=compiled)

rng = np.random.default_rng(0)
prompts = {rid: rng.integers(1, cfg.vocab_size,
                             size=int(rng.integers(2, 6))).tolist()
           for rid in range(6)}
front = ServeFrontend(engine=eng)
tickets = [front.submit(Request(rid, list(p), max_new=12))
           for rid, p in prompts.items()]

t0 = time.perf_counter()
front.run()
done = [t.result() for t in tickets]
dt = time.perf_counter() - t0
for r in sorted(done, key=lambda r: r.rid):
    print(f"req {r.rid}: {r.prompt} -> {r.out}")
tok = sum(len(r.out) for r in done)
dev = jax.devices()[0]
print(f"{len(done)} requests, {tok} tokens in {dt:.1f}s "
      f"({tok / dt:.1f} tok/s, {dev.platform} {dev.device_kind})")
assert len(done) == 6 and all(len(r.out) == 12 for r in done)

# same traffic, supervised, with slot 0 killed at its 4th dispatch
# (mid-decode): the poisoned slot's request requeues and replays on the
# 3 survivors — bit-identical to the fault-free run above
inj = FaultInjector(kill={0: 3}, clock=VirtualClock())
ft = FaultTolerantEngine(model, params, slots=4, max_len=96,
                         compiled=compiled, injector=inj)
for rid, p in prompts.items():
    ft.add_request(Request(rid, list(p), max_new=12))
recovered = ft.run_to_completion()
assert {r.rid: r.out for r in recovered} == {r.rid: r.out for r in done}
print(f"chaos replay: slot 0 killed mid-decode, {ft.replays} request "
      f"replayed on {len(ft.healthy_slots())} survivors, bit-identical")
print("serve_lm OK")

"""Serving CLI: batched decode with the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --reduced --requests 6 --max-new 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model, init_model_params
from repro.serve.engine import Engine, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)
    params = init_model_params(model, args.seed)
    eng = Engine(model, params, slots=args.slots, max_len=args.max_len,
                 temperature=args.temperature, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    for rid in range(args.requests):
        plen = int(rng.integers(2, 8))
        prompt = rng.integers(1, cfg.vocab_size, size=plen).tolist()
        eng.add_request(Request(rid, prompt, max_new=args.max_new))
    done = eng.run_to_completion()
    dt = time.perf_counter() - t0
    tok = sum(len(r.out) for r in done)
    for r in sorted(done, key=lambda r: r.rid):
        print(f"req {r.rid}: prompt={r.prompt} -> {r.out}")
    dev = jax.devices()[0]
    print(f"[serve] {len(done)} requests, {tok} tokens, "
          f"{tok / dt:.1f} tok/s ({dev.platform} {dev.device_kind})")


if __name__ == "__main__":
    main()

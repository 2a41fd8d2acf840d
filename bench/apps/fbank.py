"""Kaldi's 80-bin fbank front end as the program under test takes it.

`params` hands the reference the configuration's settings (nothing is
drawn: the front end has no weights); `build` makes the program's app from
the same settings, refusing any the program does not implement."""
from __future__ import annotations

import numpy as np

# the stage-graph stream kernel's `pallas_call` name: its launches carry it
# in the device trace, inside the per-upload loop too
KERNEL = ("graph_stream_kernel.fbank",)

# settings the program implements at one value only
FIXED = {"snip_edges": True, "dither": 0.0, "remove_dc_offset": True,
         "window_type": "povey", "povey_power": 0.85,
         "round_to_power_of_two": True, "use_power": True,
         "mel_scale": "htk", "use_log_fbank": True,
         "log_floor": 1.1920929e-07, "use_energy": False}


def params(cfg: dict, rng: np.random.Generator) -> dict:
    return dict(cfg["app"], sample_rate_hz=cfg["sample_rate_hz"])


def build(cfg: dict, p: dict):
    from repro.kernels.pipeline.fbank import KaldiFbankApp

    off = {k: p[k] for k, v in FIXED.items() if p[k] != v}
    app = KaldiFbankApp(sample_rate=float(p["sample_rate_hz"]),
                        frame_length=p["frame_length"],
                        preemph=p["preemph_coeff"], n_mels=p["num_mel_bins"],
                        low_freq=p["low_freq"], high_freq=p["high_freq"])
    if p["fft_size"] != app.fft_size:       # round_to_power_of_two
        off["fft_size"] = p["fft_size"]
    if off or p["frame_shift"] != cfg["stream"]["hop"] or \
            p["frame_length"] != cfg["stream"]["window"]:
        raise ValueError(f"settings the program does not implement: {off}")
    return app

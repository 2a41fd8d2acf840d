"""Stage-graph kernel (Kaldi fbank): the least time the chip could take for the frames
completed (bench/work counts over bench/peaks.json) over the kernel's time
in the device trace."""
import sys

from bench.lib import kernel_roofline_pct


def read(ctx):
    return kernel_roofline_pct(
        ctx, log=lambda m: print(f"[bench] {m}", file=sys.stderr))

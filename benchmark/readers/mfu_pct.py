"""mfu_pct: model FLOP/s utilization of the chip's bf16 peak.

Forward-and-backward FLOPs a sample times samples a second a chip, over the
peak. The rate is that of the intervals no trace control fell into, since this
is read in a traced run. Not a device metric on the CPU: nothing to read.
"""

import statistics

from benchmark.lib.peaks import peak


def read(run):
    clean, flops = run.get("clean_intervals_s"), run.get("flops_per_sample")
    if run.get("platform", "cpu") == "cpu" or not clean or not flops:
        return None
    rate = run["samples_per_unit"] / run["chips"] / statistics.fmean(clean)
    return 100.0 * flops * rate / peak(run["device_kind"], "bf16_flops_per_s")

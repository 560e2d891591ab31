"""recompute_ms_unit: device time of what remat computes a second time, a unit."""

from benchmark.lib import phase_spans


def read(run):
    return phase_spans.metric(run, "recompute_ms_unit")

"""layout_copy_ms_unit: device time of copies and of fusions that only move data, a unit."""

from benchmark.lib import phase_spans


def read(run):
    return phase_spans.metric(run, "layout_copy_ms_unit")

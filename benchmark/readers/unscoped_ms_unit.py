"""unscoped_ms_unit: device time under no registered scope after inheritance, a unit."""

from benchmark.lib import phase_spans


def read(run):
    return phase_spans.metric(run, "unscoped_ms_unit")

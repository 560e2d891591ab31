"""dispatch_host_ms_unit: median host time in the unit's dispatch."""

from benchmark.lib import program_spans


def read(run):
    return program_spans.dispatch_host_ms_unit()

"""mlp_ms_unit: device time in the feed-forward sub-layer a unit."""

from benchmark.lib import program_spans


def read(run):
    return program_spans.scope_ms_unit(run, "mlp")

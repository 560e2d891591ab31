"""optimizer_fused_ms_unit: device time of other scopes' fusions that hold an optimizer update, a unit."""

from benchmark.lib import phase_spans


def read(run):
    return phase_spans.metric(run, "optimizer_fused_ms_unit")

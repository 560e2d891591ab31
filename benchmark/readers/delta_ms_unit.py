"""delta_ms_unit: device time under the scope delta_rule (the recurrence alone) a unit."""

from benchmark.lib import delta_spans


def read(run):
    return delta_spans.under_ms_unit(run, "delta_rule")

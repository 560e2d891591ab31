"""delta_ms_unit: device time under the scope delta_rule (the recurrence alone) a unit."""

from benchmark.lib import scope_spans


def read(run):
    return scope_spans.under_ms_unit(run, "delta_kernels", "delta_rule")

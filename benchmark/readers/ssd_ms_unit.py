"""ssd_ms_unit: device time under the scope ssd (the recurrence alone) a unit."""

from benchmark.lib import scope_spans


def read(run):
    return scope_spans.under_ms_unit(run, "ssm_kernels", "ssd")

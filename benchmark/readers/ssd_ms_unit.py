"""ssd_ms_unit: device time under the scope ssd (the recurrence alone) a unit."""

from benchmark.lib import ssm_spans


def read(run):
    return ssm_spans.under_ms_unit(run, "ssd")

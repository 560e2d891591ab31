"""ssm_ms_unit: device time under the scope ssm (the whole Mamba-2 mixer) a unit."""

from benchmark.lib import ssm_spans


def read(run):
    return ssm_spans.under_ms_unit(run, "ssm")

"""ssd_roofline_pct: the Mamba-2 recurrence's share of its roofline (forward and backward of every layer, over the time under ssd)."""

from benchmark.lib import ssm_spans


def read(run):
    return ssm_spans.ssd_roofline_pct(run)

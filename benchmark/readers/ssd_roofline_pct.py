"""ssd_roofline_pct: the Mamba-2 recurrence's share of its roofline (forward and backward of every layer, over the time under ssd)."""

from benchmark.lib import scope_spans


def read(run):
    return scope_spans.roofline_pct(run, "ssm_kernels", "ssd", "ssd")

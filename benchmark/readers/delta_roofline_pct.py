"""delta_roofline_pct: the gated delta rule's share of its roofline (forward and backward of every layer, over the time under delta_rule)."""

from benchmark.lib import delta_spans


def read(run):
    return delta_spans.delta_roofline_pct(run)

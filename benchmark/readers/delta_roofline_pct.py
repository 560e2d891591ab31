"""delta_roofline_pct: the gated delta rule's share of its roofline (forward and backward of every layer, over the time under delta_rule)."""

from benchmark.lib import scope_spans


def read(run):
    return scope_spans.roofline_pct(run, "delta_kernels", "delta", "delta_rule")

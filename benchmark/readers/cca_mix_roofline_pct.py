"""cca_mix_roofline_pct: CCA's mixing's share of its roofline (forward and backward of every layer, over the time under cca_mix)."""

from benchmark.lib import scope_spans


def read(run):
    return scope_spans.roofline_pct(run, "cca_kernels", "cca_mix", "cca_mix")

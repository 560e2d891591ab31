"""flash_window_roofline_pct: the windowed flash kernels' share of their roofline (forward, dQ, dK/dV together)."""

from benchmark.lib import lm_spans


def read(run):
    return lm_spans.roofline_pct(run, "flash_window")

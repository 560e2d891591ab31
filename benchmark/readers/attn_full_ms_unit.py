"""attn_full_ms_unit: device time of full causal attention a unit: the kernels and their layout."""

from benchmark.lib import lm_spans


def read(run):
    return lm_spans.scope_ms_unit(run, "attn_full")

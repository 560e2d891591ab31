"""moe_experts_ms_unit: device time a unit in the grouped expert products and the shared expert."""

from benchmark.lib import lm_spans


def read(run):
    return lm_spans.scope_ms_unit(run, "moe_experts", "moe_shared")

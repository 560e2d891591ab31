"""moe_route_ms_unit: device time a unit in the expert layers' routing (logits, softmax, top-k) and dispatch (sort, gather, scatter-add by weight)."""

from benchmark.lib import lm_spans


def read(run):
    return lm_spans.scope_ms_unit(run, "moe_router", "moe_dispatch")

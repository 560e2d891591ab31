"""delta_chunk_log_decay_min: the most negative chunk log-decay the gated-delta-rule scans met, the minimum over
the window's steps (the step's own counter, returned beside its loss)."""

def read(run):
    seen = (run.get("counters") or {}).get("delta_chunk_log_decay_min")
    return min(seen) if seen else None

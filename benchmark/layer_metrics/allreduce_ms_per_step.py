"""Milliseconds per optimizer step during which an all-reduce was under way
on chip 0 (device trace: synchronous operations, and asynchronous ones from
their start to their done)."""
import trace_reduce


def read(result, cell):
    w = trace_reduce.traced_window(result, cell)
    if w is None or not result.get("steps"):
        return None
    trace, lo, hi, chips = w
    spans = trace_reduce.collective_spans(chips[0], "all-reduce")
    s = trace_reduce.total(trace_reduce.clip(spans, lo, hi))
    return 1e3 * s / result["steps"]

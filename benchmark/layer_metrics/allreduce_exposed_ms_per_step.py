"""The part of `allreduce_ms_per_step` during which nothing else ran on
chip 0: the collective time the step really waits for."""
import trace_reduce


def read(result, cell):
    w = trace_reduce.traced_window(result, cell)
    if w is None or not result.get("steps"):
        return None
    trace, lo, hi, chips = w
    s = trace_reduce.exposed_seconds(chips[0], lo, hi, "all-reduce")
    return 1e3 * s / result["steps"]

"""Device-busy milliseconds per decode dispatch: for each execution of a
decode-window program in the traced window, the time operations ran on the
chip inside it; the median over the executions."""
import re
import statistics

import serve_cell
import trace_reduce


def read(result, cell):
    w = trace_reduce.traced_window(result, cell)
    if w is None:
        return None
    trace, lo, hi, chips = w
    rx = re.compile(serve_cell.DECODE_PROGRAM)
    busy = [trace_reduce.busy_seconds(chips[0], m.start, m.end)
            for m in chips[0].modules
            if rx.search(m.name) and lo <= m.start and m.end <= hi]
    return 1e3 * statistics.median(busy) if busy else None

"""Rows per decode dispatch over the window, from the batcher's counters:
tokens generated over the sum of k * windows_dispatched[k] (there is no
occupancy counter in the program today; PERF.md §7)."""
import serve_cell


def read(result, cell):
    c0, c1 = result["counters"]
    steps = sum(k * n for k, n in serve_cell.windows_between(c0, c1).items())
    tokens = c1["tokens_generated"] - c0["tokens_generated"]
    return tokens / steps if steps else None

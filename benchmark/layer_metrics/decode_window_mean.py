"""Tokens per decode window as the ladder was really used over the window:
sum of k * windows_dispatched[k] over sum of windows_dispatched[k]."""
import serve_cell


def read(result, cell):
    c0, c1 = result["counters"]
    d = serve_cell.windows_between(c0, c1)
    n = sum(d.values())
    return sum(k * v for k, v in d.items()) / n if n else None

"""The longest stretch of the window in which no token of any request was
delivered. At the cell's rate it is the longest prefill; a second or more is
a stall, and `host_pause_max_ms` says whether the host stood still too."""
import math


def read(result, cell):
    gap = result["delivery_gap_max_s"]
    return 1e3 * gap if math.isfinite(gap) else None

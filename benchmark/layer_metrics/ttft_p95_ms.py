"""Seconds from the instant each request was due to its first token, 95th
percentile over the requests due in the window, misses counted as the
window's length (the same samples as the end-to-end `ttft_p50_ms`). Recorded
without a bound: on the one-chip machine the whole process stands still for a
second or more in about one run in five, and this tail is then the pause's
(PERF.md, section 2). `host_pause_max_ms` and `delivery_gap_max_ms` beside it
say whether a run had one."""
import flops


def read(result, cell):
    return 1e3 * flops.percentile(result["ttft_s"], 95) if result["ttft_s"] else None

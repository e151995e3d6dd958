"""Median over the window's log intervals of milliseconds per optimizer
step (host clock between synced records)."""
import statistics


def read(result, cell):
    return 1e3 * statistics.median(result["per_step_s"])

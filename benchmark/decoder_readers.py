"""What the per-layer readers of a ``decoder_serve`` cell share: counter
differences between two edges, the device time of a named kernel inside the
executions of named programs, and the peaks. A run of a program that lacks
the decoder family (the parent of the PR that added it cannot run such a
cell at all) never gets here; a result without the counters reads None."""

from __future__ import annotations

import bisect
import re

import flops
import trace_reduce

DECODE_PROGRAM = re.compile(r"decoder_window_fn")
PREFILL_PROGRAM = re.compile(r"decoder_(prefill|chunk)_fn")


def delta(result: dict, which: str, group: str) -> dict | None:
    """``group``'s counters at the second edge less the first; ``which`` is
    ``"counters"`` (the window) or ``"trace_counters"`` (the traced part)."""
    pair = result.get(which)
    if not pair or group not in pair[0]:
        return None
    c0, c1 = pair
    return {k: c1[group][k] - c0[group][k] for k in c1[group]}


def peaks() -> dict:
    import jax

    return flops.peaks(jax.devices()[0].device_kind)


def module_spans(chip, lo: float, hi: float, rx) -> list[tuple[float, float]]:
    return [(max(m.start, lo), min(m.end, hi)) for m in chip.modules
            if rx.search(m.name) and m.end > lo and m.start < hi]


def busy_in_modules(result: dict, cell, rx) -> float | None:
    w = trace_reduce.traced_window(result, cell)
    if w is None:
        return None
    _, lo, hi, chips = w
    return sum(trace_reduce.busy_seconds(chips[0], a, b)
               for a, b in module_spans(chips[0], lo, hi, rx))


def kernel_seconds(result: dict, cell, kernel: str, rx=None) -> float | None:
    """Seconds chip 0 spent in operations whose label starts with ``kernel``
    (a Pallas kernel's ``name``) inside the traced window; with ``rx``, only
    those that started inside an execution of a program it matches."""
    w = trace_reduce.traced_window(result, cell)
    if w is None:
        return None
    _, lo, hi, chips = w
    spans = module_spans(chips[0], lo, hi, rx) if rx else [(lo, hi)]
    starts = [a for a, _ in spans]
    total = 0.0
    for op in chips[0].ops:
        if not op.label.startswith(kernel) or not lo <= op.start < hi:
            continue
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.start < spans[i][1]:
            total += op.seconds
    return total

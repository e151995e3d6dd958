"""Milliseconds per optimizer step in the dense LM head's two Pallas kernels
(``lm_head_fwd`` and ``lm_head_dx``, `ops/pallas_xent.py`), from the device
trace of chip 0. In a train step the kernels run under autodiff, so their
operations are labelled ``jvp_lm_head_fwd_.7`` and
``transpose_jvp_lm_head_dx__.7``: every operation whose label holds
``lm_head_`` is counted. A program without them (one that leaves the head to
XLA's operations) reads None."""
import trace_reduce


def read(result, cell):
    w = trace_reduce.traced_window(result, cell)
    if w is None or not result.get("steps"):
        return None
    _, lo, hi, chips = w
    t = sum(min(op.end, hi) - max(op.start, lo) for op in chips[0].ops
            if "lm_head_" in op.label and min(op.end, hi) > max(op.start, lo))
    return 1e3 * t / result["steps"] if t else None

"""Live sessions per decode step, counted where the batch is formed: the
mean of ``rows`` over the ``serve:decode_dispatch`` spans of the traced
window, each weighted by its ``k`` steps. The window is a few seconds of a
Poisson run, so a burst or a lull decides it (2.0-3.5 from seed to seed,
PERF.md section 6): compare it only between runs of one seed."""
import program_spans


def read(result, cell):
    spans = program_spans.thread_in_window(
        result, cell, program_spans.SCHEDULER_ANCHOR)
    dispatches = [s.args for s in spans
                  if s.name == "serve:decode_dispatch"]
    steps = sum(a["k"] for a in dispatches)
    return sum(a["rows"] * a["k"] for a in dispatches) / steps if steps else None

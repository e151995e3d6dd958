"""Share of chip 0's idle time in the traced window that falls in some span
of the scheduler's thread (`program_spans.py`): each idle interval is split
over the innermost span that overlaps each part. The split by span name, in
seconds, goes to ``samples.idle_by_span``."""
import program_spans


def read(result, cell):
    return program_spans.idle_attributed_share(
        result, cell, program_spans.SCHEDULER_ANCHOR)

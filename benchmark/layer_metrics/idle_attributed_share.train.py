"""Share of chip 0's idle time in the traced window that falls in some
``train:*`` span of the training thread (`program_spans.py`); the split by
span name, in seconds, goes to ``samples.idle_by_span``."""
import program_spans


def read(result, cell):
    return program_spans.idle_attributed_share(
        result, cell, program_spans.TRAINER_ANCHOR)

"""The scheduler's own host time per program it launches: the time inside
``serve:iteration`` spans, less the ``engine:fetch`` waits inside them, over
the number of ``engine:launch`` spans, on the scheduler's thread in the
traced window."""
import program_spans


def read(result, cell):
    spans = program_spans.thread_in_window(
        result, cell, program_spans.SCHEDULER_ANCHOR)
    seconds = {name: sum(s.seconds for s in spans if s.name == name)
               for name in ("serve:iteration", "engine:fetch")}
    launches = sum(s.name == "engine:launch" for s in spans)
    if not launches:
        return None
    return 1e3 * (seconds["serve:iteration"] - seconds["engine:fetch"]) / launches

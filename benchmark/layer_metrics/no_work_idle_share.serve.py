"""Share of chip 0's idle time in the traced window that overlaps the
scheduler's ``serve:wait_for_work``: idle because nothing was offered, not
because the host was slow. At a fixed offered rate it is headroom. Over a
few seconds of a Poisson run it swings with the seed's arrivals (52-71%,
PERF.md section 6): compare it only between runs of one seed."""
import program_spans


def read(result, cell):
    split = program_spans.idle_by_span(result, cell,
                                       program_spans.SCHEDULER_ANCHOR)
    idle = sum(split.values()) if split else 0.0
    return 100.0 * split.get("serve:wait_for_work", 0.0) / idle if idle else None

"""How long the scheduler blocks on the device for a decode window: the
median ``engine:fetch`` span of the decode-window program in the traced
window. Beside `decode_dispatch_device_ms` it says whether dispatching ahead
overlaps anything."""
import statistics

import program_spans
import serve_cell


def read(result, cell):
    spans = program_spans.thread_in_window(
        result, cell, program_spans.SCHEDULER_ANCHOR)
    waits = [s.seconds for s in spans
             if s.name == "engine:fetch"
             and s.args.get("program") == serve_cell.DECODE_PROGRAM]
    return 1e3 * statistics.median(waits) if waits else None

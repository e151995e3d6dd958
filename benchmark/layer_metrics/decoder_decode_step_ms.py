"""Device-busy milliseconds of one decode step: busy time inside the decode
window programs of the traced window over the decode steps dispatched between
the trace's edges (the engine's counter)."""
import decoder_readers


def read(result, cell):
    d = decoder_readers.delta(result, "trace_counters", "decoder")
    busy = decoder_readers.busy_in_modules(result, cell, decoder_readers.DECODE_PROGRAM)
    if not d or not busy or not d["decode_steps"]:
        return None
    return 1e3 * busy / d["decode_steps"]

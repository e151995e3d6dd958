"""Milliseconds per decode step in the grouped-query decode attention kernel
(``gqa_decode`` in the device trace), full and window layers together."""
import decoder_readers


def read(result, cell):
    d = decoder_readers.delta(result, "trace_counters", "decoder")
    t = decoder_readers.kernel_seconds(result, cell, "gqa_decode")
    if not d or not t or not d["decode_steps"]:
        return None
    return 1e3 * t / d["decode_steps"]

"""Milliseconds per decode step in the decode attention kernel (``mla_decode``
in the device trace), all layers."""
import decoder_readers


def read(result, cell):
    d = decoder_readers.delta(result, "trace_counters", "decoder")
    t = decoder_readers.kernel_seconds(result, cell, "mla_decode")
    if not d or not t or not d["decode_steps"]:
        return None
    return 1e3 * t / d["decode_steps"]

"""Milliseconds per decode step in the grouped expert products
(``expert_gmm_*`` in the device trace) inside the decode window programs, all
expert layers."""
import decoder_readers


def read(result, cell):
    d = decoder_readers.delta(result, "trace_counters", "decoder")
    t = decoder_readers.kernel_seconds(result, cell, "expert_gmm",
                                       decoder_readers.DECODE_PROGRAM)
    if not d or not t or not d["decode_steps"]:
        return None
    return 1e3 * t / d["decode_steps"]

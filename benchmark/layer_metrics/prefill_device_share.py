"""Percent of the device's busy time in the traced window that ran inside
prefill programs (final and chunk): what new prompts take from decoding."""
import decoder_readers
import trace_reduce


def read(result, cell):
    w = trace_reduce.traced_window(result, cell)
    busy = decoder_readers.busy_in_modules(result, cell, decoder_readers.PREFILL_PROGRAM)
    if w is None or busy is None:
        return None
    _, lo, hi, chips = w
    total = trace_reduce.busy_seconds(chips[0], lo, hi)
    return 100.0 * busy / total if total else None

"""Rows one decode step of the decoder carried, mean over the window: the
engine's counters, live rows x steps dispatched over steps dispatched. A
step reads the same 2.5 GB of weights whatever it carries (PERF.md section 5),
so this is what the scheduler has to raise before tokens/s can rise."""
import decoder_readers


def read(result, cell):
    d = decoder_readers.delta(result, "counters", "decoder")
    if not d or not d["decode_steps"]:
        return None
    return d["decode_row_steps"] / d["decode_steps"]
